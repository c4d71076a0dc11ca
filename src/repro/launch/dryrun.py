import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax-importing module: jax locks the device count on init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds the real step function (train_step for
train_4k, prefill for prefill_32k, serve_step/decode for decode_32k and
long_500k), lowers it with abstract ShapeDtypeStructs against the production
mesh shardings, compiles it, and records:

  * memory_analysis()  -- per-device argument/output/temp/peak bytes
                          (proves the cell fits 16 GB/chip HBM)
  * cost_analysis()    -- per-device HLO flops + bytes accessed
  * collective traffic -- parsed from the compiled HLO (all-gather /
                          all-reduce / reduce-scatter / all-to-all /
                          collective-permute), ring-cost accounted
  * derived roofline terms (seconds) + dominant bottleneck

Results land in ``experiments/dryrun/<arch>__<shape>__<mesh>.json``.

Usage:
  python -m repro.launch.dryrun --arch mamba2-780m --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import pathlib
import time
import traceback

from repro.core.precision import PrecisionPolicy
from repro.distributed.hlo_analysis import HW, parse_collectives, roofline_terms
from repro.distributed.sharding import activation_rules
from repro.distributed.structural import capacity_bytes, model_flops, structural_bytes
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_decode_step, build_prefill_step, build_train_step
from repro.models.common import unroll_scans
from repro.models.registry import SHAPES, get_arch, list_archs
from repro.models.transformer import layer_pattern
from repro.models.whisper import WhisperConfig

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _with_groups(cfg, k: int):
    """A config with k repeat groups (probe depth)."""
    if isinstance(cfg, WhisperConfig):
        return dataclasses.replace(cfg, n_enc_layers=k, n_dec_layers=k)
    return dataclasses.replace(cfg, n_layers=k * len(layer_pattern(cfg)))


def _total_groups(cfg) -> int:
    if isinstance(cfg, WhisperConfig):
        return cfg.n_enc_layers  # enc and dec scale together in probes
    return cfg.n_layers // len(layer_pattern(cfg))


def _mem_dict(ma) -> dict:
    keys = (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "peak_memory_in_bytes",
    )
    return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}


def apply_variant_cfg(arch, shape, variant: str):
    """Config-level changes a variant implies (shared by the step builder and
    the structural-bytes accounting)."""
    cfg = arch.config
    if isinstance(cfg, WhisperConfig):
        return arch
    if variant.endswith("_kv8"):
        cfg = dataclasses.replace(cfg, kv_cache_bits=8)
    if "gqa" in variant:
        cfg = dataclasses.replace(cfg, gqa_flat=True)
    if variant in ("moe_gqa", "ep_megatron") and cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, shard_experts="megatron"))
    if shape.kind == "train":
        if variant in ("headrep", "combo"):
            cfg = dataclasses.replace(cfg, shard_head_dim=False)
        if variant in ("ep_data", "combo") and cfg.moe is not None:
            cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, shard_experts="fsdp"))
    if cfg is not arch.config:
        arch = dataclasses.replace(arch, config=cfg)
    return arch


def build_step(arch, shape, mesh, *, quant_bits: int | None = None, variant: str = "baseline"):
    """Variants (the section-Perf hillclimb knobs):

      baseline    -- training layout everywhere (f32 FSDP+TP params)
      serve_opt   -- bf16 TP-only serving params (kills per-step all-gathers)
      serve_q8/q4 -- serve_opt + int8/int4 quant_matmul weights (the paper's
                     precision knob applied at LM scale)
      *_kv8       -- int8 KV cache on top (state-precision knob)
      bf16gather  -- train: cast params to bf16 at step start so FSDP
                     all-gathers move half the bytes
      headrep     -- train: replicate the embed/lm_head d_model axis so the
                     chunked-CE head matmul contracts locally
      ep_data / ep_megatron -- MoE expert-sharding alternatives
      combo       -- bf16gather + headrep + ep_data
    """
    arch = apply_variant_cfg(arch, shape, variant)
    serve_optimized = variant.startswith("serve")
    base_variant = variant.removesuffix("_kv8")
    if base_variant == "serve_q8":
        quant_bits = 8
    elif base_variant == "serve_q4":
        quant_bits = 4
    quant = (
        PrecisionPolicy(rules=(("(wq|wk|wv|wo|w_gate|w_up|w_down|in_proj|out_proj)$", quant_bits),))
        if quant_bits
        else None
    )
    if shape.kind == "train":
        return build_train_step(
            arch, shape, mesh, bf16_gather=variant in ("bf16gather", "combo")
        )
    if shape.kind == "prefill":
        return build_prefill_step(arch, shape, mesh, quant=quant, serve_optimized=serve_optimized)
    shard_seq = shape.name == "long_500k"
    return build_decode_step(
        arch, shape, mesh, quant=quant, shard_cache_seq=shard_seq, serve_optimized=serve_optimized
    )


def run_cell(arch_name: str, shape_name: str, multi_pod: bool, *, quant_bits=None, variant="baseline", out_dir=OUT_DIR, verbose=True):
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    record = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": mesh_name,
        "variant": variant,
        "kind": shape.kind,
        "status": "skipped",
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = "" if variant == "baseline" else f"__{variant}"
    out_path = out_dir / f"{arch_name}__{shape_name}__{mesh_name}{suffix}.json"

    if not arch.runs_shape(shape_name):
        record["reason"] = arch.skip_reason
        out_path.write_text(json.dumps(record, indent=2))
        if verbose:
            print(f"[dryrun] SKIP {arch_name} x {shape_name} ({arch.skip_reason})")
        return record

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        with mesh, activation_rules(mesh):
            # ---- full-depth compile: proves lowering + gives true memory ----
            bundle = build_step(arch, shape, mesh, quant_bits=quant_bits, variant=variant)
            lowered = bundle.lower()
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = _mem_dict(compiled.memory_analysis())
            surface = parse_collectives(compiled.as_text())  # scan bodies counted once

            # ---- probe compiles: 1-group and 2-group, all scans unrolled ----
            # XLA's HLO cost analysis counts a while body once (not x trips),
            # so flops/bytes/collectives come from unrolled shallow probes,
            # extrapolated linearly over the repeat groups (which are
            # identical by construction): F(ng) = F(1) + (ng-1) * (F(2)-F(1)).
            ng = _total_groups(arch.config)
            probes = {}
            with unroll_scans():
                for k in (1, 2):
                    if ng == 1 and k == 2:
                        break
                    cfg_k = _with_groups(arch.config, k)
                    b_k = build_step(
                        dataclasses.replace(arch, config=cfg_k), shape, mesh,
                        quant_bits=quant_bits, variant=variant,
                    )
                    c_k = b_k.lower().compile()
                    cost_k = c_k.cost_analysis() or {}
                    coll_k = parse_collectives(c_k.as_text())
                    probes[k] = {
                        "flops": float(cost_k.get("flops", 0.0)),
                        "bytes": float(cost_k.get("bytes accessed", 0.0)),
                        "wire": coll_k.per_device_wire_bytes,
                        "by_op": coll_k.by_op,
                    }
            if ng == 1:
                flops, bytes_accessed, wire = probes[1]["flops"], probes[1]["bytes"], probes[1]["wire"]
            else:
                d = {k: probes[2][k] - probes[1][k] for k in ("flops", "bytes", "wire")}
                flops = probes[1]["flops"] + (ng - 1) * d["flops"]
                bytes_accessed = probes[1]["bytes"] + (ng - 1) * d["bytes"]
                wire = probes[1]["wire"] + (ng - 1) * d["wire"]

            # structural (fusion-aware lower-bound) memory model + capacity
            arch_v = apply_variant_cfg(arch, shape, variant)
            serve_opt = variant.startswith("serve")
            q_eff = {"serve_q8": 8, "serve_q4": 4}.get(variant.removesuffix("_kv8"), quant_bits)
            struct = structural_bytes(
                arch_v, shape, multi_pod=multi_pod, quant_bits=q_eff,
                serve_optimized=serve_opt, cfg=arch_v.config,
            )
            if serve_opt:
                from repro.distributed.structural import capacity_bytes_serve_optimized

                cap = capacity_bytes_serve_optimized(
                    arch_v, shape, multi_pod=multi_pod, quant_bits=q_eff, cfg=arch_v.config
                )
            else:
                cap = capacity_bytes(arch_v, shape, multi_pod=multi_pod, quant_bits=q_eff, cfg=arch_v.config)
            mf = model_flops(arch, shape)

            terms = roofline_terms(flops, struct["total"], wire)
            terms_hlo = roofline_terms(flops, bytes_accessed, wire)

            record.update(
                status="ok",
                n_devices=int(mesh.devices.size),
                n_groups=ng,
                lower_s=round(t_lower, 2),
                compile_s=round(t_compile, 2),
                flops_per_device=flops,
                model_flops_global=mf,
                model_flops_per_device=mf / mesh.devices.size,
                useful_flops_ratio=(mf / mesh.devices.size) / flops if flops else None,
                bytes_per_device_hlo=bytes_accessed,
                bytes_per_device_structural=struct,
                wire_bytes_per_device=wire,
                memory=mem,
                capacity_structural=cap,
                fits_hbm=cap["total"] <= HW.hbm_bytes,
                collectives_surface=surface.summary(),
                probe_collectives=probes.get(2, probes.get(1, {})).get("by_op", {}),
                roofline=terms,
                roofline_hlo_bytes=terms_hlo,
            )
    except Exception as e:  # record the failure; dry-run failures are bugs
        record.update(status="error", error=f"{type(e).__name__}: {e}", traceback=traceback.format_exc()[-2000:])
    record["wall_s"] = round(time.time() - t0, 2)
    out_path.write_text(json.dumps(record, indent=2))
    if verbose:
        if record["status"] == "ok":
            r = record["roofline"]
            print(
                f"[dryrun] OK {arch_name} x {shape_name} x {mesh_name}{suffix} "
                f"({record['wall_s']}s) peak={record['memory'].get('peak_memory_in_bytes',0)/1e9:.2f}GB "
                f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
                f"collective={r['collective_s']:.3e}s dom={r['dominant']}"
            )
        else:
            print(f"[dryrun] {record['status'].upper()} {arch_name} x {shape_name} x {mesh_name}: {record.get('error','')}")
    return record


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all four)")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="every (arch x shape)")
    ap.add_argument("--quant-bits", type=int, default=None, help="serve-side weight quantization (4 or 8)")
    ap.add_argument("--variant", default="baseline", help="label for optimized re-runs")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()

    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_err = n_skip = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(
                    arch, shape, mp, quant_bits=args.quant_bits, variant=args.variant,
                    out_dir=pathlib.Path(args.out),
                )
                n_ok += rec["status"] == "ok"
                n_err += rec["status"] == "error"
                n_skip += rec["status"] == "skipped"
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
