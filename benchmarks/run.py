"""Benchmark harness: one module per paper table/figure + framework extras.

Prints ``name,us_per_call,derived`` CSV (one line per measurement).

  table1    -- paper Table 1 (neuron x topology x dataset accuracy sweep)
  table2    -- paper Table 2 (MNIST design point: resources/latency/energy)
  fig11     -- paper Fig. 11 (precision-DSE cost landscape, ATA-F on DVS)
  cg_error  -- section 4.1.2 CG approximation-error claims
  lm_dse    -- Flex-plorer generalised to LM serving precision (beyond paper)
  kernels   -- kernel micro-benchmarks (oracle timing + modeled TPU time)
  backend   -- inference-backend throughput + DSE candidate rate
               (reference vs fused, serial vs population; BENCH_backend.json)
  event     -- event-driven backend throughput vs input sparsity
               (reference vs fused vs event; BENCH_event.json)
  serve     -- continuous-batching SNN service vs serial run_int
               (closed-loop + offered-load p50/p99; BENCH_serve.json)
  shard     -- multi-device scaling: eval/DSE/serving at 1/2/4 forced host
               devices (worker subprocesses; BENCH_shard.json)
  qat       -- post-training quant vs quantization-aware training accuracy
               at w_bits 2/3/4 + refined-front DSE (BENCH_qat.json)
  dse       -- search-strategy quality: anneal vs NSGA-II front hypervolume
               at equal budget, resume fidelity, population-sweep
               candidates/sec at 1/4 forced host devices (BENCH_dse.json)
  roofline  -- per (arch x shape) roofline terms from the dry-run records

Usage: python -m benchmarks.run [--only table1,roofline] [--fast]
       python -m benchmarks.run --check-regression          # gate BENCH_*.json
                                                            # against baselines

The persistent jit compilation cache is on: ``JAX_COMPILATION_CACHE_DIR``
when set, else ``.jax_cache/`` at the checkout root
(``repro.distributed.compat.enable_compilation_cache``).

``--check-regression`` compares the repo-root ``BENCH_*.json`` files (the
committed perf trajectory, refreshed by a full ``benchmarks.run`` pass)
against ``benchmarks/baselines/`` and exits nonzero when any throughput
metric (``*_per_sec`` keys; offered-load *inputs* excluded) regresses by
more than the threshold (default 25%).  Record a new baseline by copying
the fresh ``BENCH_*.json`` into ``benchmarks/baselines/``.
"""

import argparse
import json
import pathlib
import re
import sys
import traceback

MODULES = ["cg_error", "kernels", "backend", "event", "serve", "shard", "qat", "dse", "roofline", "lm_dse", "table2", "table1", "fig11"]

_ROOT = pathlib.Path(__file__).resolve().parent.parent
BASELINE_DIR = _ROOT / "benchmarks" / "baselines"

# Throughput metrics: higher is better.  `offered_rate_per_sec` is a load
# *parameter* (what the generator asked for), not a measurement -- skip it.
_THROUGHPUT_KEY = re.compile(r"per_sec$")
_EXCLUDE_KEY = re.compile(r"^offered_rate")


def _rows(name: str, fast: bool):
    if name == "table1":
        from benchmarks import table1_accuracy

        return table1_accuracy.run(epochs=2 if fast else 8)
    if name == "table2":
        from benchmarks import table2_resources

        return table2_resources.run(epochs=3 if fast else 8)
    if name == "fig11":
        from benchmarks import fig11_dse

        return fig11_dse.run(epochs=2 if fast else 5)
    if name == "cg_error":
        from benchmarks import cg_error

        return cg_error.run()
    if name == "lm_dse":
        from benchmarks import lm_dse

        return lm_dse.run(archs=("mamba2-780m",) if fast else ("gemma2-27b", "qwen2-moe-a2.7b", "mamba2-780m"))
    if name == "kernels":
        from benchmarks import kernels_micro

        return kernels_micro.run()
    if name == "backend":
        from benchmarks import backend_bench

        return backend_bench.run(fast=fast)
    if name == "event":
        from benchmarks import event_bench

        return event_bench.run(fast=fast)
    if name == "serve":
        from benchmarks import serve_bench

        return serve_bench.run(fast=fast)
    if name == "shard":
        from benchmarks import shard_bench

        return shard_bench.run(fast=fast)
    if name == "qat":
        from benchmarks import qat_bench

        return qat_bench.run(fast=fast)
    if name == "dse":
        from benchmarks import dse_bench

        return dse_bench.run(fast=fast)
    if name == "roofline":
        from benchmarks import roofline

        return roofline.run()
    raise KeyError(name)


def _throughput_leaves(obj, prefix: str = "") -> dict[str, float]:
    """Flatten a bench report to {dotted.path: value} for throughput keys."""
    leaves: dict[str, float] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            path = f"{prefix}.{k}" if prefix else str(k)
            if isinstance(v, (dict, list)):
                leaves.update(_throughput_leaves(v, path))
            elif (
                isinstance(v, (int, float))
                and _THROUGHPUT_KEY.search(str(k))
                and not _EXCLUDE_KEY.search(str(k))
            ):
                leaves[path] = float(v)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            leaves.update(_throughput_leaves(v, f"{prefix}[{i}]"))
    return leaves


def check_regression(
    fresh_dir: pathlib.Path = _ROOT,
    baseline_dir: pathlib.Path = BASELINE_DIR,
    threshold: float = 0.25,
) -> list[str]:
    """Compare fresh BENCH_*.json against baselines; return regression lines.

    A metric regresses when ``fresh < (1 - threshold) * baseline``.  Metrics
    missing from the fresh report (renamed/removed) are reported too --
    silently dropping a measurement must not read as "no regression".
    Baselines that do not exist yet are skipped (that is how the trajectory
    starts; record one by copying the fresh file into the baseline dir).
    """
    problems: list[str] = []
    for base_file in sorted(baseline_dir.glob("BENCH_*.json")):
        fresh_file = fresh_dir / base_file.name
        if not fresh_file.exists():
            problems.append(f"{base_file.name}: fresh report missing (run the bench first)")
            continue
        base = _throughput_leaves(json.loads(base_file.read_text()))
        fresh = _throughput_leaves(json.loads(fresh_file.read_text()))
        for path, base_val in sorted(base.items()):
            got = fresh.get(path)
            if got is None:
                problems.append(f"{base_file.name}: {path} missing from fresh report")
            elif got < (1.0 - threshold) * base_val:
                problems.append(
                    f"{base_file.name}: {path} regressed {base_val:.1f} -> {got:.1f} "
                    f"({got / base_val:.2f}x, floor {1.0 - threshold:.2f}x)"
                )
    return problems


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--check-regression", action="store_true",
                    help="compare repo-root BENCH_*.json against "
                    "benchmarks/baselines/ and exit nonzero on regression")
    ap.add_argument("--baseline-dir", default=None,
                    help="baseline directory for --check-regression")
    ap.add_argument("--regression-threshold", type=float, default=0.25,
                    help="allowed fractional throughput drop (default 0.25)")
    args = ap.parse_args()

    if args.check_regression:
        baseline_dir = pathlib.Path(args.baseline_dir) if args.baseline_dir else BASELINE_DIR
        problems = check_regression(threshold=args.regression_threshold, baseline_dir=baseline_dir)
        if problems:
            print(f"{len(problems)} throughput regression(s) vs {baseline_dir}:")
            for p in problems:
                print(f"  {p}")
            raise SystemExit(1)
        print(f"no throughput regressions vs {baseline_dir}")
        return

    from repro.distributed.compat import enable_compilation_cache

    enable_compilation_cache()

    names = args.only.split(",") if args.only else MODULES
    print("name,us_per_call,derived")
    failed = False
    for name in names:
        try:
            for row_name, us, derived in _rows(name, args.fast):
                print(f"{row_name},{us:.1f},{derived}")
                sys.stdout.flush()
        except Exception as e:
            failed = True
            print(f"{name},0.0,EXCEPTION:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
