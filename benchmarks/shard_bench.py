"""Shard benchmark: multi-device scaling of eval, DSE fan-out, and serving.

Measures the ``repro.core.shard`` execution layer at 1/2/4 forced host
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=N`` -- the
flag must be set before jax initialises, so every measurement runs in a
fresh worker subprocess):

* ``eval``  -- ``run_int_sharded`` samples/sec, sample axis split across
  the mesh (the ``eval_int`` hot path);
* ``dse``   -- ``run_int_population_sharded`` candidates/sec, candidate
  axis split across the mesh (the population Flex-plorer's fan-out);
* ``serve`` -- ``SNNServeEngine(data_parallel=N)`` served samples/sec,
  lane pool partitioned into per-device shards.

Methodology: device-level scaling is only visible when a device is a fixed
execution resource, so the workers pin XLA to the legacy single-threaded
CPU runtime (``--xla_cpu_use_thunk_runtime=false
--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1``) --
otherwise the 1-device baseline silently spreads over every core and the
comparison measures thread-pool contention, not sharding.  Device counts
are *interleaved* across rounds (1,2,4,1,2,4,...) and each config keeps its
best round, so slow-host noise hits every config equally.  The report also
records a **process-parallel calibration**: the combined throughput of two
*independent* single-device worker processes, i.e. the host's actual
parallel headroom -- on a 2-core container the in-process 4-device speedup
is bounded by (and should be read against) that ceiling, while CI's
4-vCPU leg and real multi-device hardware have room to show the full
fan-out.

The workload is a deep 256-wide LIF chain (the paper's 256-neuron cores
stacked five deep): wide enough per layer to be compute-bound, the regime
where device sharding pays.

Emits ``BENCH_shard.json`` at the repo root (full runs) or
``experiments/BENCH_shard_fast.json`` (``--fast`` smoke; what CI uploads)
and returns the harness's ``(name, us_per_call, derived)`` rows.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
OUT = _ROOT / "BENCH_shard.json"
FAST_OUT = _ROOT / "experiments" / "BENCH_shard_fast.json"

#: Per-device single-thread pinning (see module docstring).
SINGLE_THREAD_FLAGS = (
    "--xla_cpu_use_thunk_runtime=false "
    "--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1"
)
DEVICE_COUNTS = (1, 2, 4)


# ---------------------------------------------------------------------------
# Worker: runs in a fresh process with the forced device count
# ---------------------------------------------------------------------------


def _worker(cfg: dict) -> None:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import backend as backend_lib
    from repro.core import shard as shard_lib
    from repro.core.network import NetworkConfig, init_float_params, quantize_params
    from repro.core.snn_layer import LayerConfig, NeuronModel
    from repro.serve.snn_engine import SNNRequest, SNNServeEngine

    n_dev = len(jax.devices())
    assert n_dev == cfg["devices"], (n_dev, cfg)
    fast = cfg["fast"]
    T = 8 if fast else 16
    B = 256 if fast else 1024  # eval batch (divisible by every device count)
    P = 8  # DSE population width
    dse_batch = 64 if fast else 128
    rounds, calls = (2, 1) if fast else (4, 2)

    def wide(n_out=256):
        return LayerConfig(n_in=256, n_out=n_out, neuron=NeuronModel.LIF, w_bits=6, u_bits=16)

    net = NetworkConfig(
        layers=(wide(), wide(), wide(), wide(), wide(10)),
        n_steps=T,
        name="shard-bench-256x4-10",
    )
    params = init_float_params(jax.random.PRNGKey(0), net)
    qparams, _ = quantize_params(net, params)
    mesh = shard_lib.make_mesh()  # all (forced) devices; 1 device -> serial path
    spikes = (jax.random.uniform(jax.random.PRNGKey(1), (T, B, 256)) < 0.15).astype(jnp.int32)

    def best_of(fn) -> float:
        """Best (min) seconds-per-call over interleave-friendly rounds."""
        fn()  # compile
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(calls):
                fn()
            best = min(best, (time.perf_counter() - t0) / calls)
        return best

    report: dict = {"devices": n_dev}

    if cfg["metric"] in ("all", "eval"):
        sec = best_of(
            lambda: shard_lib.run_int_sharded(
                net, qparams, spikes, mesh
            ).spike_counts.block_until_ready()
        )
        report["eval"] = {"seconds_per_pass": sec, "samples_per_sec": B / sec}

    if cfg["metric"] in ("all", "dse"):
        bits = (4, 5, 6, 8, 12, 16, 4, 8)
        cands = [net.replace_precisions(w_bits=b) for b in bits[:P]]
        qps = [quantize_params(c, params)[0] for c in cands]
        stacked, beta, alpha = backend_lib.stack_population(cands, qps)
        sp = spikes[:, :dse_batch]
        sec = best_of(
            lambda: shard_lib.run_int_population_sharded(
                net, stacked, beta, alpha, sp, mesh
            ).block_until_ready()
        )
        report["dse"] = {
            "seconds_per_sweep": sec,
            "population": P,
            "eval_batch": dse_batch,
            "candidates_per_sec": P / sec,
        }

    if cfg["metric"] in ("all", "serve"):
        n_req = 16 if fast else 64
        rng = np.random.default_rng(0)
        rasters = [(rng.random((T, 256)) < 0.15).astype(np.uint8) for _ in range(n_req)]

        def serve_pass():
            eng = SNNServeEngine(
                net, qparams, max_batch=8, data_parallel=n_dev, tick_stride=T
            )
            reqs = [SNNRequest(uid=i, raster=r) for i, r in enumerate(rasters)]
            for r in reqs:
                eng.submit(r)
            t0 = time.perf_counter()
            done = eng.drain()
            assert len(done) == n_req
            return time.perf_counter() - t0

        serve_pass()  # compile
        best = min(serve_pass() for _ in range(rounds))
        report["serve"] = {
            "seconds_per_pass": best,
            "requests": n_req,
            "samples_per_sec": n_req / best,
        }

    print("SHARD_WORKER_RESULT " + json.dumps(report))


def _spawn(devices: int, fast: bool, metric: str = "all") -> subprocess.Popen:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} {SINGLE_THREAD_FLAGS}"
    )
    env["JAX_PLATFORMS"] = "cpu"  # host-device scaling is a CPU measurement
    env["PYTHONPATH"] = str(_ROOT / "src") + os.pathsep + env.get("PYTHONPATH", "")
    cfg = json.dumps({"devices": devices, "fast": fast, "metric": metric})
    return subprocess.Popen(
        [sys.executable, "-m", "benchmarks.shard_bench", "--worker", cfg],
        cwd=_ROOT,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )


def _collect(proc: subprocess.Popen) -> dict:
    out, err = proc.communicate()
    for line in out.splitlines():
        if line.startswith("SHARD_WORKER_RESULT "):
            return json.loads(line[len("SHARD_WORKER_RESULT "):])
    raise RuntimeError(f"shard worker failed:\n{err[-2000:]}")


def run(fast: bool = False, device_counts=DEVICE_COUNTS, rounds: int | None = None):
    rounds = (2 if fast else 3) if rounds is None else rounds
    best: dict[int, dict] = {n: {} for n in device_counts}
    # interleave device counts across rounds: host noise hits every config
    for _ in range(rounds):
        for n in device_counts:
            res = _collect(_spawn(n, fast))
            for metric in ("eval", "dse", "serve"):
                key = "candidates_per_sec" if metric == "dse" else "samples_per_sec"
                cur = best[n].get(metric)
                if cur is None or res[metric][key] > cur[key]:
                    best[n][metric] = res[metric]

    # calibration: two independent 1-device processes = the host's real
    # parallel headroom (ideal on unshared multi-core hardware: ~2.0)
    procs = [_spawn(1, fast, metric="eval") for _ in range(2)]
    combined = sum(_collect(p)["eval"]["samples_per_sec"] for p in procs)
    ceiling = combined / best[device_counts[0]]["eval"]["samples_per_sec"]

    base = best[device_counts[0]]
    top = best[device_counts[-1]]
    report = {
        "workload": "shard-bench-256x4-10",
        # workers run on forced host devices (JAX_PLATFORMS=cpu): a CPU
        # measurement, even on a host with a chip
        "platform": "cpu",
        # in-process fan-out speedup relative to what the host can physically
        # deliver (1.0 = the sharded layer extracted every available core)
        "parallel_efficiency_vs_ceiling": (
            top["eval"]["samples_per_sec"] / base["eval"]["samples_per_sec"]
        ) / max(ceiling, 1e-9),
        "device_counts": list(device_counts),
        "xla_flags": SINGLE_THREAD_FLAGS,
        "host_cpu_count": os.cpu_count(),
        "process_parallel_ceiling_x2": ceiling,
        "by_devices": {str(n): best[n] for n in device_counts},
        "speedups_vs_1_device": {
            str(n): {
                "eval_samples_per_sec_x": best[n]["eval"]["samples_per_sec"]
                / base["eval"]["samples_per_sec"],
                "dse_candidates_per_sec_x": best[n]["dse"]["candidates_per_sec"]
                / base["dse"]["candidates_per_sec"],
                "serve_samples_per_sec_x": best[n]["serve"]["samples_per_sec"]
                / base["serve"]["samples_per_sec"],
            }
            for n in device_counts[1:]
        },
    }

    out = FAST_OUT if fast else OUT
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(report, indent=2))

    rows = []
    for n in device_counts:
        b = best[n]
        rows.append(
            (
                f"shard/eval-{n}dev",
                b["eval"]["seconds_per_pass"] * 1e6,
                f"samples_per_sec={b['eval']['samples_per_sec']:.1f};platform=cpu",
            )
        )
        rows.append(
            (
                f"shard/dse-{n}dev",
                b["dse"]["seconds_per_sweep"] * 1e6,
                f"cand_per_sec={b['dse']['candidates_per_sec']:.2f};platform=cpu",
            )
        )
        rows.append(
            (
                f"shard/serve-{n}dev",
                b["serve"]["seconds_per_pass"] * 1e6,
                f"samples_per_sec={b['serve']['samples_per_sec']:.1f};platform=cpu",
            )
        )
    for n, s in report["speedups_vs_1_device"].items():
        rows.append(
            (
                f"shard/speedup-{n}dev",
                0.0,
                f"eval={s['eval_samples_per_sec_x']:.2f}x;dse={s['dse_candidates_per_sec_x']:.2f}x"
                f";serve={s['serve_samples_per_sec_x']:.2f}x;ceiling_x2={ceiling:.2f}x;platform=cpu",
            )
        )
    return rows


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        _worker(json.loads(sys.argv[2]))
    else:
        fast = "--fast" in sys.argv
        for name, us, derived in run(fast=fast):
            print(f"{name},{us:.1f},{derived}")
