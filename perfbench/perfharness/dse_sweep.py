"""Flex-plorer population sweep: ``eval_int_population`` over a precision grid, pass after pass.

Set-up quantizes one float draw at every candidate's widths, makes the
held-out rasters, and runs one pass to compile. The window repeats passes
until ``--seconds`` have gone by; the last pass ends the window. Once it
has closed, what every pass returned for every candidate (accuracy, and
with ``return_stats`` the spikes each core emitted and the input events at
each step, as dataset means) is held to the reference.

A traced run traces the passes that start in the first ``trace_seconds``.
"""

from __future__ import annotations

import gc
import itertools
import time

import jax
import numpy as np

from perfharness import opcount, program, traffic, weights
from perfharness.harness import Run, load_trace, memory_peak, start_trace, trace_dir

TRACE_SECONDS = 2.0


def candidates(config: dict, grid: dict) -> list[dict]:
    """Every point of the grid, in a fixed order, as per-layer overrides."""
    keys = ("w_bits", "w_rec_bits", "leak_bits")
    return [dict(zip(keys, point)) for point in itertools.product(*(grid[k] for k in keys))]


def _spanned(module, attr: str, label: str):
    orig = getattr(module, attr)

    def call(*args, **kwargs):
        with jax.profiler.TraceAnnotation(label):
            return orig(*args, **kwargs)

    setattr(module, attr, call)
    return lambda: setattr(module, attr, orig)


def run(ctx, devices) -> Run:
    from repro.data.snn_datasets import SpikeDataset
    from repro.snn.train import eval_int_population

    cfg, mix = ctx.config, ctx.traffic
    layers = cfg["layers"]
    T, n_in, n_samples, batch = cfg["n_steps"], layers[0]["n_in"], mix["samples"], mix["batch_size"]
    mesh = mix.get("mesh")
    points = candidates(cfg, mix["grid"])
    base = program.network(cfg)
    nets = [base.replace_precisions(**p) for p in points]
    widths = [(p["w_bits"], p["w_rec_bits"]) for p in points]
    cand_w = weights.make_weights(cfg, traffic.seed_key(ctx.seed, 3), widths)
    qps = [program.qparams(w) for w in cand_w]
    spikes, labels = traffic.rasters(mix["raster"], n_samples, T, n_in, ctx.seed)
    ds = SpikeDataset(spikes, labels, base.n_classes, ctx.name)

    def sweep():
        return eval_int_population(
            base, nets, qps, ds, batch_size=batch, return_stats=True, mesh=mesh
        )

    sweep()
    ctx.note(
        f"{len(points)} candidates x {n_samples} samples per pass, batch {batch}, mesh {mesh}"
    )
    restore = []
    if ctx.trace:
        from repro.core import shard
        from repro.snn import train

        tracing = trace_dir()
        restore = [
            _spanned(train, "_population_fwd", "population_call"),
            _spanned(shard, "run_int_population_sharded", "population_call"),
        ]
    setup_s = ctx.start_window()
    if ctx.trace:
        start_trace(tracing)
    results, ends = [], []
    t0 = time.perf_counter()
    traced_until = t0 + min(TRACE_SECONDS, ctx.seconds / 2) if ctx.trace else None
    while True:
        with jax.profiler.TraceAnnotation("bench.pass"):
            results.append(sweep())
        now = time.perf_counter()
        ends.append(now - t0)
        if traced_until is not None and now >= traced_until:
            jax.profiler.stop_trace()
            traced_until = None
        if now - t0 >= ctx.seconds:
            break
    elapsed = ends[-1]
    ctx.end_window()
    mem = memory_peak(devices)
    ctx.note(f"{len(results)} passes in {elapsed} s")
    took = np.diff(np.asarray([0.0] + ends))
    med = float(np.median(took))
    ctx.note(
        f"pass seconds: min {took.min():.4f} median {med:.4f} max {took.max():.4f};"
        f" {int((took > 1.5 * med).sum())} passes over 1.5 x median,"
        f" {float(np.clip(took - med, 0, None).sum()):.3f} s above the median in all"
    )
    for undo in restore:
        undo()
    gc.collect()

    # the reference, once per candidate (every pass scores the same data)
    x = jax.numpy.asarray(spikes.transpose(1, 0, 2))
    ref_correct, ref_events = [], []
    for p, w in zip(points, cand_w):
        c, e = ctx.reference.simulate([dict(c, **p) for c in layers], w, x)
        pred = np.argmax(np.asarray(c), axis=-1)
        ref_correct.append(int((pred == labels).sum()))
        ref_events.append(np.asarray(e).sum(axis=2))  # [T, L] summed over samples
    ref_in = np.count_nonzero(spikes, axis=-1).sum(axis=0)  # [T]
    def counts(mean):  # dataset means back to whole counts
        return np.rint(np.asarray(mean, np.float64) * n_samples).astype(np.int64)

    wrong = 0
    ops_per_pass = 0
    for accs, stats in results:
        for j in range(len(points)):
            got_ev = counts(np.stack(stats[j]["layer_events_per_step"], axis=1))
            wrong += int(
                counts(accs[j]) != ref_correct[j]
                or not np.array_equal(got_ev, ref_events[j])
                or not np.array_equal(counts(stats[j]["input_events_per_step"]), ref_in)
            )
    accs, stats = results[-1]
    for j in range(len(points)):
        ev = counts(np.stack(stats[j]["layer_events_per_step"], axis=1))
        ops_per_pass += opcount.synaptic_ops(layers, ref_in, ev)
    base_j = next(
        (j for j, p in enumerate(points) if all(p[k] == layers[0][k] for k in p)), 0
    )
    rate = ref_events[base_j].sum(0) / (n_samples * T) / np.asarray([c["n_out"] for c in layers])
    ctx.note(
        f"firing rate per core at {points[base_j]} (reference): "
        + " ".join(f"{r:.4f}" for r in rate)
        + f"; accuracy range {min(accs)}..{max(accs)}"
    )
    data = {
        "passes": len(results),
        "evals": len(results) * len(points) * n_samples,
        "elapsed_s": elapsed,
        "synaptic_ops": ops_per_pass * len(results),
    }
    trace = load_trace(tracing) if ctx.trace else None
    return Run(
        setup_s=setup_s,
        window_s=elapsed,
        attempted=len(results) * len(points),
        failed=0,
        checks=[("wrong_results", wrong, 0)],
        memory_peak_bytes=mem,
        chips=len(devices),
        peaks=ctx.peaks,
        data=data,
        trace=trace,
    )
