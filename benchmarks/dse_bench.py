"""DSE benchmark: search-strategy quality + population-sweep throughput.

Three measurements around the Flex-plorer's pluggable search strategies:

* **Front quality at equal budget** -- on the MNIST-scale 256-128-10 LIF
  network (ATA-F hidden layer, so all three precision knobs are live and
  the space is 1800 configurations -- large relative to the budget; a
  feed-forward 2-knob space is small enough that any schedule enumerates
  it and every strategy trivially ties), run the population annealer to
  completion, then give NSGA-II (population 64 and 512) *the same
  evaluation budget* and compare 2-D Pareto-front hypervolume (accuracy x
  total hardware cost, both minimized as ``(1 - acc, hw)`` against the
  ``(1, 1)`` reference point) over each run's first ``budget`` unique
  evaluations.  The annealer optimises one scalar and concentrates near
  its optimum; NSGA-II's non-dominated/crowding selection spends the
  identical budget covering the trade-off curve, so its hypervolume
  should be >= the annealer's (recorded as ``nsga2_hv_ge_anneal``).
* **Resume fidelity** -- kill an NSGA-II search mid-generation (the sweep
  call raises after the snapshot of an earlier round) and resume from the
  checkpoint directory: the final front must be *identical* to the
  uninterrupted run's (``resume_front_identical``).
* **Sweep throughput** -- ``eval_int_population`` candidates/sec at
  population widths 64/512/2048 (16/64 in ``--fast``), at 1 vs 4 forced
  host devices.  The device-count comparison reuses the ``shard_bench``
  methodology: fresh worker subprocesses (``XLA_FLAGS`` must precede jax
  init) pinned to the single-threaded CPU runtime, interleaved rounds,
  best-of per config.  On a 1-core container the 4-device row measures
  sharding overhead, not speedup -- read it against ``shard_bench``'s
  process-parallel ceiling.

Emits ``BENCH_dse.json`` at the repo root (full runs; the committed perf
trajectory gated by ``--check-regression``) or
``experiments/BENCH_dse_fast.json`` (``--fast`` smoke; what CI uploads)
and returns the harness's ``(name, us_per_call, derived)`` rows.
"""

from __future__ import annotations

import itertools
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent
OUT = _ROOT / "BENCH_dse.json"
FAST_OUT = _ROOT / "experiments" / "BENCH_dse_fast.json"

#: Same per-device single-thread pinning as ``shard_bench`` (see there).
SINGLE_THREAD_FLAGS = (
    "--xla_cpu_use_thunk_runtime=false "
    "--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1"
)
DEVICE_COUNTS = (1, 4)


# ---------------------------------------------------------------------------
# Pareto-front hypervolume (2-D, minimization, reference point (1, 1))
# ---------------------------------------------------------------------------


def _hypervolume(points, ref=(1.0, 1.0)) -> float:
    """Area dominated by ``points`` (minimized) up to ``ref``."""
    pts = sorted({(min(a, ref[0]), min(b, ref[1])) for a, b in points})
    hv, best_b = 0.0, ref[1]
    for a, b in pts:  # ascending first objective
        if b < best_b:
            hv += (ref[0] - a) * (best_b - b)
            best_b = b
    return hv


def _trace_points(trace, budget: int):
    """(1 - accuracy, hw_cost) of the first ``budget`` unique evaluations."""
    return [(1.0 - r["accuracy"], r["hw"]) for r in trace[:budget]]


# ---------------------------------------------------------------------------
# Worker: sweep throughput in a fresh process with forced device count
# ---------------------------------------------------------------------------


def _worker(cfg: dict) -> None:
    import jax

    from repro.core import shard as shard_lib
    from repro.core.network import NetworkConfig, init_float_params, quantize_params
    from repro.core.snn_layer import LayerConfig, NeuronModel
    from repro.data.snn_datasets import mnist_like
    from repro.snn.train import eval_int_population

    n_dev = len(jax.devices())
    assert n_dev == cfg["devices"], (n_dev, cfg)
    T = 6 if cfg["fast"] else 10
    B = 8  # eval batch: the sweep scales the *candidate* axis, keep data tiny
    rounds = 2

    net = NetworkConfig(
        layers=(
            LayerConfig(n_in=256, n_out=128, neuron=NeuronModel.LIF, w_bits=6, u_bits=16),
            LayerConfig(n_in=128, n_out=10, neuron=NeuronModel.LIF, w_bits=6, u_bits=16),
        ),
        n_steps=T,
        name="dse-bench-mnist-256-128-10",
    )
    params = init_float_params(jax.random.PRNGKey(0), net)
    ds = mnist_like(n=B, T=T, seed=0)
    mesh = shard_lib.make_mesh()  # all (forced) devices; 1 device -> serial

    # distinct precision candidates, cycled to fill the sweep width; the
    # per-unique-config quantization is hoisted (the explorer caches it too)
    grid = list(itertools.product((2, 3, 4, 5, 6, 8, 10, 12, 16), (1, 2, 3, 4, 6, 8)))
    uniq = {
        bits: net.replace_precisions(w_bits=bits[0], leak_bits=bits[1]) for bits in grid
    }
    uniq_q = {bits: quantize_params(c, params)[0] for bits, c in uniq.items()}

    report = {"devices": n_dev, "widths": {}}
    for width in cfg["widths"]:
        cands = [uniq[grid[i % len(grid)]] for i in range(width)]
        qps = [uniq_q[grid[i % len(grid)]] for i in range(width)]

        def sweep():
            # stacking is part of the measured cost: it is what the
            # explorer pays per proposal round
            accs = eval_int_population(net, cands, qps, ds, batch_size=B, mesh=mesh)
            jax.block_until_ready(accs)

        sweep()  # compile
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            sweep()
            best = min(best, time.perf_counter() - t0)
        report["widths"][str(width)] = {
            "seconds_per_sweep": best,
            "candidates_per_sec": width / best,
        }
    print("DSE_WORKER_RESULT " + json.dumps(report))


def _spawn(devices: int, fast: bool, widths) -> subprocess.Popen:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} {SINGLE_THREAD_FLAGS}"
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(_ROOT / "src") + os.pathsep + env.get("PYTHONPATH", "")
    cfg = json.dumps({"devices": devices, "fast": fast, "widths": list(widths)})
    return subprocess.Popen(
        [sys.executable, "-m", "benchmarks.dse_bench", "--worker", cfg],
        cwd=_ROOT,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )


def _collect(proc: subprocess.Popen) -> dict:
    out, err = proc.communicate()
    for line in out.splitlines():
        if line.startswith("DSE_WORKER_RESULT "):
            return json.loads(line[len("DSE_WORKER_RESULT "):])
    raise RuntimeError(f"dse worker failed:\n{err[-2000:]}")


# ---------------------------------------------------------------------------
# Front quality + resume fidelity (in-process)
# ---------------------------------------------------------------------------


def _strategy_quality(fast: bool) -> tuple[dict, list]:
    import jax  # noqa: F401  (imported here so --worker runs never pay it twice)

    from repro.core.flexplorer import strategies as S
    from repro.core.flexplorer.explorer import EvalSpec, SearchSpec, SNNSearchSpace, explore_snn
    from repro.core.network import NetworkConfig
    from repro.core.snn_layer import LayerConfig, NeuronModel, Topology
    from repro.data.snn_datasets import mnist_like
    from repro.snn.train import train_snn

    # the qat_bench training recipe: enough timesteps/samples that accuracy
    # genuinely degrades at low precision (a chance-level net has a flat
    # accuracy axis and the front collapses to the min-hw point)
    T = 6 if fast else 20
    n = 128 if fast else 1536
    ds = mnist_like(n=n, T=T, seed=0)
    train, test = ds.split()
    net = NetworkConfig(
        layers=(
            LayerConfig(
                n_in=256, n_out=128, neuron=NeuronModel.LIF,
                topology=Topology.FF if fast else Topology.ATA_F,
                w_bits=6, u_bits=16,
            ),
            LayerConfig(n_in=128, n_out=10, neuron=NeuronModel.LIF, w_bits=6, u_bits=16),
        ),
        n_steps=T,
        name="dse-bench-mnist-256-128-10",
    )
    res = train_snn(net, train, epochs=1 if fast else 6, batch_size=128, lr=2e-3)

    if fast:
        space = SNNSearchSpace(ff_bits=(2, 4, 6, 8), leak_bits=(2, 4, 8))
        pairs = ((16, 4),)
    else:
        bits = tuple(range(2, 17))
        space = SNNSearchSpace(
            ff_bits=bits, rec_bits=bits, leak_bits=(1, 2, 3, 4, 5, 6, 7, 8)
        )
        pairs = ((64, 40), (512, 16))
    ev = EvalSpec(batch=max(64, len(test.labels)))

    rows, report = [], {}
    report["train_acc"] = res.history[-1]["train_acc"]

    # -- anneal vs NSGA-II at equal budget, one pairing per population ------
    # Each pairing runs the annealer to completion, takes its evaluation
    # count as the shared budget, and caps NSGA-II at that budget.  The
    # annealer's eval_divisor picks the budget regime: it must stay well
    # under the 1800-configuration space (near-exhaustive budgets make
    # every strategy find the same front -- a degenerate tie) yet exceed
    # the NSGA population (a budget below the population ends inside the
    # random initial generation, before any selection pressure exists).
    # divisor 40 -> ~440 evals for pop 64; divisor 16 -> ~990 for pop 512.
    for pop, divisor in pairs:
        anneal_cfg = S.AnnealConfig(
            t_start=1.0, t_min=0.05, alpha=0.7, eval_divisor=divisor, seed=0
        )
        t0 = time.perf_counter()
        anneal = explore_snn(
            net, res.params, test,
            search=SearchSpec(space=space, config=anneal_cfg, population=8),
            evaluate=ev,
        )
        anneal_s = time.perf_counter() - t0
        budget = anneal.search.evaluations
        anneal_hv = _hypervolume(_trace_points(anneal.search.trace, budget))
        rows.append(
            (
                f"dse/front-anneal-b{budget}",
                anneal_s * 1e6,
                f"hv={anneal_hv:.4f};evals={budget}",
            )
        )

        cfg = S.NSGAConfig(population=pop, generations=64, seed=0)
        t0 = time.perf_counter()
        nsga = explore_snn(
            net, res.params, test,
            search=SearchSpec(
                space=space, strategy="nsga2", config=cfg, max_evaluations=budget
            ),
            evaluate=ev,
        )
        nsga_s = time.perf_counter() - t0
        # the final round may overshoot the cap; score both runs on exactly
        # the first `budget` unique evaluations for a fair comparison
        hv = _hypervolume(_trace_points(nsga.search.trace, budget))
        report[f"nsga2_pop{pop}"] = {
            "budget_evaluations": budget,
            "anneal": {
                "hypervolume": anneal_hv,
                "seconds": round(anneal_s, 2),
                "front_size": len(anneal.search.front),
            },
            "hypervolume": hv,
            "seconds": round(nsga_s, 2),
            "evaluations": min(budget, nsga.search.evaluations),
            "front_size": len(nsga.search.front),
            "hv_vs_anneal": hv / anneal_hv if anneal_hv else float("inf"),
        }
        rows.append(
            (
                f"dse/front-nsga2-pop{pop}",
                nsga_s * 1e6,
                f"hv={hv:.4f};anneal_hv={anneal_hv:.4f};ratio={hv / max(anneal_hv, 1e-12):.3f}",
            )
        )
    report["nsga2_hv_ge_anneal"] = all(
        report[f"nsga2_pop{p}"]["hypervolume"]
        >= report[f"nsga2_pop{p}"]["anneal"]["hypervolume"] - 1e-12
        for p, _ in pairs
    )

    # -- resume fidelity: kill mid-generation, resume, compare fronts -------
    from repro.snn import train as train_mod

    spec = dict(
        space=space,
        strategy="nsga2",
        config=S.NSGAConfig(population=16, generations=3, seed=1),
    )
    with tempfile.TemporaryDirectory() as tmp:
        full = explore_snn(
            net, res.params, test,
            search=SearchSpec(**spec, checkpoint_dir=f"{tmp}/full"),
            evaluate=ev,
        )
        real_sweep = train_mod.eval_int_population
        calls = {"n": 0}

        def dies(*args, **kw):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("killed mid-generation")
            return real_sweep(*args, **kw)

        import repro.core.flexplorer.explorer as explorer_mod

        explorer_mod.eval_int_population = dies
        try:
            try:
                explore_snn(
                    net, res.params, test,
                    search=SearchSpec(**spec, checkpoint_dir=f"{tmp}/killed"),
                    evaluate=ev,
                )
            except RuntimeError:
                pass
        finally:
            explorer_mod.eval_int_population = real_sweep
        resumed = explore_snn(
            net, res.params, test,
            search=SearchSpec(**spec, checkpoint_dir=f"{tmp}/killed"),
            evaluate=ev,
        )
    identical = (
        resumed.search.front == full.search.front
        and resumed.search.best == full.search.best
    )
    report["resume_front_identical"] = identical
    rows.append(("dse/resume-identical", 0.0, f"identical={identical};killed_at_call=2"))
    return report, rows


def run(fast: bool = False, device_counts=DEVICE_COUNTS, rounds: int | None = None):
    rounds = 1 if fast else (2 if rounds is None else rounds)
    widths = (16, 64) if fast else (64, 512, 2048)

    quality, rows = _strategy_quality(fast)

    # interleave device counts across rounds (shard_bench methodology)
    best: dict[int, dict] = {n: {} for n in device_counts}
    for _ in range(rounds):
        for n_dev in device_counts:
            res = _collect(_spawn(n_dev, fast, widths))
            for w, m in res["widths"].items():
                cur = best[n_dev].get(w)
                if cur is None or m["candidates_per_sec"] > cur["candidates_per_sec"]:
                    best[n_dev][w] = m

    report = {
        "workload": "dse-bench-mnist-256-128-10",
        "strategy_quality": quality,
        "sweep": {
            # workers run on forced host devices (JAX_PLATFORMS=cpu): a CPU
            # measurement, even on a host with a chip
            "platform": "cpu",
            "widths": list(widths),
            "device_counts": list(device_counts),
            "xla_flags": SINGLE_THREAD_FLAGS,
            "host_cpu_count": os.cpu_count(),
            "by_devices": {str(n): best[n] for n in device_counts},
        },
    }
    for n_dev in device_counts:
        for w in widths:
            m = best[n_dev][str(w)]
            rows.append(
                (
                    f"dse/sweep-w{w}-{n_dev}dev",
                    m["seconds_per_sweep"] * 1e6,
                    f"cand_per_sec={m['candidates_per_sec']:.1f};platform=cpu",
                )
            )

    out = FAST_OUT if fast else OUT
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
    rows.append(("dse/report-written", 0.0, str(out.relative_to(_ROOT))))
    return rows


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        _worker(json.loads(sys.argv[2]))
    else:
        for name, us, derived in run(fast="--fast" in sys.argv):
            print(f"{name},{us:.1f},{derived}")
