"""Open-loop serving: a Poisson schedule of distinct rasters through ``SNNServeEngine.run``.

Set-up builds the engine with its own defaults, warms it with
``engine.warmup()``, and makes every request of the window. The window is
one ``engine.run`` over the schedule: a request's latency runs from its due
time to its completion, so queueing and stalls count. Once the window has
closed, every request due in it is held to the reference: its output spike
counts, and the spikes each core emitted and the input events it took at
every step.

A traced run opens the profiler before the window and closes it after
``trace_seconds``; the per-layer numbers describe that traced part of the
window, and the run goes on to the end of the window untraced.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from perfharness import opcount, program, traffic, weights
from perfharness.harness import Run, load_trace, memory_peak, start_trace, trace_dir

REF_BLOCK = 2048  # requests per reference call
TRACE_SECONDS = 2.0


class _Spans:
    """Harness spans around the engine's calls, with the engine's tick time inside each poll."""

    def __init__(self, engine, t_stop: float | None, on_stop):
        from repro.serve import snn_engine

        self.polls: list[tuple] = []  # (start, end, tick seconds, ticks)
        self.t_stop, self.on_stop = t_stop, on_stop
        self._module = snn_engine
        self._window = snn_engine._lane_window_packed
        orig_poll, orig_dispatch, orig_tick = engine.poll, engine._dispatch, engine.tick

        def window(*args, **kwargs):
            with jax.profiler.TraceAnnotation("lane_window_call"):
                return self._window(*args, **kwargs)

        def dispatch(now):
            with jax.profiler.TraceAnnotation("dispatch"):
                return orig_dispatch(now)

        def tick():
            with jax.profiler.TraceAnnotation("tick"):
                return orig_tick()

        def poll():
            m = engine.metrics
            t0, k0, n0 = time.perf_counter(), m.tick_s, m.n_ticks
            with jax.profiler.TraceAnnotation("bench.poll"):
                out = orig_poll()
            t1 = time.perf_counter()
            self.polls.append((t0, t1, m.tick_s - k0, m.n_ticks - n0))
            if self.t_stop is not None and t1 >= self.t_stop:
                self.t_stop = None
                self.on_stop(t1)
            return out

        engine.poll, engine._dispatch, engine.tick = poll, dispatch, tick
        snn_engine._lane_window_packed = window

    def close(self):
        self._module._lane_window_packed = self._window


def run(ctx, devices) -> Run:
    from repro.serve.snn_engine import SNNRequest, SNNServeEngine

    cfg, mix = ctx.config, ctx.traffic
    layers = cfg["layers"]
    T, n_in = cfg["n_steps"], layers[0]["n_in"]
    net = program.network(cfg)
    w = weights.make_weights(cfg, traffic.seed_key(ctx.seed, 3))[0]
    arrivals = traffic.poisson_arrivals(mix["arrivals"]["rate_per_s"], ctx.seconds, ctx.seed)
    rasters, _ = traffic.rasters(mix["raster"], len(arrivals), T, n_in, ctx.seed)
    requests = [
        SNNRequest(uid=i, raster=rasters[i], arrival_s=float(a)) for i, a in enumerate(arrivals)
    ]
    engine = SNNServeEngine(net, program.qparams(w))
    engine.warmup()
    ctx.note(
        f"{len(requests)} requests over {ctx.seconds} s, offered {mix['arrivals']['rate_per_s']}/s;"
        f" engine max_batch={engine.max_batch} tick_stride={engine.tick_stride}"
        f" backend={engine.backend_name}; lowerings {engine.route_lowerings()}"
    )

    spans = tracing = None
    if ctx.trace:
        tracing = trace_dir()
        stopped = {}

        def stop(t):
            jax.profiler.stop_trace()
            stopped["t"] = t

        spans = _Spans(engine, None, stop)  # its stop time is set when the profiler starts
    setup_s = ctx.start_window()
    if tracing is not None:
        start_trace(tracing)
        spans.t_stop = time.perf_counter() + min(TRACE_SECONDS, ctx.seconds / 2)
    with jax.profiler.TraceAnnotation("engine.run"):  # the loop outside poll(): mostly sleep
        done = engine.run(requests)
    ctx.end_window()
    mem = memory_peak(devices)
    if spans is not None:
        spans.close()
        if spans.t_stop is not None:  # the window ended before the traced part did
            jax.profiler.stop_trace()
            stopped["t"] = time.perf_counter()

    t0 = requests[0]._arrival_wall - requests[0].arrival_s
    due = np.asarray([r.arrival_s for r in requests])
    completed = np.asarray([r.status == "completed" for r in requests])
    latency = np.asarray([r.latency_s if r.latency_s is not None else np.inf for r in requests])
    latency[~completed] = np.inf
    service = np.asarray([r.service_s if r.service_s is not None else np.nan for r in requests])
    finish = due + latency
    counts = np.zeros((len(requests), net.n_classes), np.int64)
    events = np.zeros((len(requests), T, len(layers)), np.int64)
    in_events = np.zeros((len(requests), T), np.int64)
    for i, r in enumerate(requests):
        if completed[i]:
            counts[i] = r.spike_counts
            st = r.event_stats
            events[i] = np.stack(st["layer_events_per_step"], axis=1)
            in_events[i] = st["input_events_per_step"]
    ctx.note(f"served {int(completed.sum())} of {len(requests)}; {len(done)} returned by run()")

    del engine, done
    gc.collect()

    # the reference, over every request due in the window, in blocks
    ref_counts = np.zeros_like(counts)
    ref_events = np.zeros_like(events)
    for lo in range(0, len(requests), REF_BLOCK):
        block = np.zeros((REF_BLOCK, T, n_in), np.uint8)
        part = rasters[lo : lo + REF_BLOCK]
        block[: len(part)] = part
        c, e = ctx.reference.simulate(layers, w, jax.numpy.asarray(block.transpose(1, 0, 2)))
        ref_counts[lo : lo + len(part)] = np.asarray(c)[: len(part)]
        ref_events[lo : lo + len(part)] = np.asarray(e).transpose(2, 0, 1)[: len(part)]
    ref_in = np.count_nonzero(rasters, axis=-1)
    wrong = completed & (
        (counts != ref_counts).any(axis=1)
        | (events != ref_events).any(axis=(1, 2))
        | (in_events != ref_in).any(axis=1)
    )
    rate = ref_events.mean(axis=(0, 1)) / np.asarray([c["n_out"] for c in layers])
    ctx.note("firing rate per core (reference): " + " ".join(f"{x:.4f}" for x in rate))

    in_window = completed & (finish <= ctx.seconds)
    ops = opcount.synaptic_ops(layers, in_events[in_window].sum(0), events[in_window].sum(0))
    data = {
        "due_s": due,
        "latency_s": latency,
        "service_s": service,
        "completed": completed,
        "in_window": in_window,
        "synaptic_ops": ops,
    }
    trace = None
    if tracing is not None:
        trace = load_trace(tracing)
        traced_s = stopped["t"] - t0
        polls = np.asarray(spans.polls, np.float64).reshape(-1, 4)
        polls[:, :2] -= t0
        data["traced_s"] = traced_s
        data["polls"] = polls[polls[:, 0] < traced_s]
    return Run(
        setup_s=setup_s,
        window_s=float(ctx.seconds),
        attempted=len(requests),
        failed=int((~completed).sum()),
        checks=[
            ("wrong_answers", int(wrong.sum()), 0),
            ("missing_answers", int((~completed).sum()), 0),
        ],
        memory_peak_bytes=mem,
        chips=len(devices),
        peaks=ctx.peaks,
        data=data,
        trace=trace,
    )
