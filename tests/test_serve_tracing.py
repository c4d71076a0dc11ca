"""Profiler spans of the serve tick loop and the population sweep.

The engine's dispatch round and tick, ``eval_int_population`` and the
mesh padding of ``run_int_population_sharded`` open
``jax.profiler.TraceAnnotation`` spans named in
``repro.serve.metrics.SPAN_NAMES``. Each run here is captured with
``jax.profiler.start_trace`` on the CPU and read back with
``jax.profiler.ProfileData``: the spans nest as documented, carry the
counters the code used, and change no output.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.network import NetworkConfig, init_float_params, quantize_params
from repro.core.snn_layer import LayerConfig, NeuronModel, ResetMode, Topology
from repro.data.snn_datasets import mnist_like
from repro.serve.metrics import SPAN_NAMES
from repro.serve.snn_engine import SNNRequest, SNNServeEngine
from repro.snn.train import eval_int_population

HARNESS_SPANS = {"tick", "dispatch", "lane_window_call", "population_call", "engine.run"}


def _host_events(path):
    """``(name, start_ns, end_ns, stats)`` of every ``neura.`` host event, by start."""
    from jax.profiler import ProfileData

    (xplane,) = path.glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("neura."):
                        out.append((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _traced(path, fn):
    jax.profiler.start_trace(str(path))
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    return result, _host_events(path)


def _children(events, parent):
    _, lo, hi, _ = parent
    return [e for e in events if e is not parent and lo <= e[1] and e[2] <= hi]


# -- serving -----------------------------------------------------------------


def _serve_net(T=16, n_in=24):
    return NetworkConfig(
        layers=(
            LayerConfig(n_in=n_in, n_out=12, neuron=NeuronModel.LIF,
                        topology=Topology.FF, reset=ResetMode.SUBTRACT, beta=0.9),
            LayerConfig(n_in=12, n_out=5, neuron=NeuronModel.LIF,
                        reset=ResetMode.ZERO, beta=0.77),
        ),
        n_steps=T,
    )  # fmt: skip


@pytest.fixture(scope="module")
def serve_setup():
    net = _serve_net()
    params = init_float_params(jax.random.PRNGKey(0), net)
    qparams, _ = quantize_params(net, params)
    return net, qparams


def _serve(net, qparams):
    """Five requests of mixed windows through two lanes; returns polls, ticks, outputs."""
    eng = SNNServeEngine(net, qparams, max_batch=2, tick_stride=4)
    ticks = []
    record = eng.metrics.record_tick

    def spy(k, wall_s, queue_depth, active, n_lanes, now, launch_s=0.0):
        ticks.append((k, active))
        record(k, wall_s, queue_depth, active, n_lanes, now, launch_s)

    eng.metrics.record_tick = spy
    rng = np.random.default_rng(1)
    for uid, T in enumerate([8, 5, 16, 3, 9]):
        raster = (rng.random((T, net.n_in)) < 0.4).astype(np.uint8)
        eng.submit(SNNRequest(uid=uid, raster=raster))
    polls, done = 0, []
    while eng.in_flight:
        done.extend(eng.poll())
        polls += 1
    counts = {r.uid: np.asarray(r.spike_counts) for r in done}
    return polls, ticks, counts, eng


@pytest.fixture(scope="module")
def serve_trace(serve_setup, tmp_path_factory):
    net, qparams = serve_setup
    _serve(net, qparams)  # compile outside the trace
    path = tmp_path_factory.mktemp("serve_trace")
    return _traced(path, lambda: _serve(net, qparams))


def test_one_dispatch_span_per_poll(serve_trace):
    (polls, ticks, counts, _), events = serve_trace
    dispatch = [e for e in events if e[0] == "neura.serve.dispatch"]
    assert len(dispatch) == polls
    assert sum(e[3]["admitted"] for e in dispatch) == len(counts) == 5
    assert dispatch[0][3]["queued"] == 5  # every request was queued before the first poll


def test_tick_spans_hold_their_steps_in_order_with_the_tick_counters(serve_trace):
    (polls, ticks, counts, _), events = serve_trace
    tick_spans = [e for e in events if e[0] == "neura.serve.tick"]
    assert len(tick_spans) == len(ticks) > 0
    for span, (k, active) in zip(tick_spans, ticks):
        kids = _children(events, span)
        assert [e[0] for e in kids] == [
            "neura.serve.pack", "neura.serve.launch", "neura.serve.readback",
            "neura.serve.complete",
        ]  # fmt: skip
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))  # one after another
        assert span[3]["k"] == k and span[3]["active"] == active
        assert span[3]["route"] == "dense" and span[3]["ff_mode"] in ("f32_exact", "int32")
    finished = [e[3]["finished"] for e in events if e[0] == "neura.serve.complete"]
    assert sum(finished) == len(counts)


def test_tick_seconds_split_into_launch_and_readback(serve_trace):
    (_, _, _, eng), _ = serve_trace
    m = eng.metrics
    assert m.launch_s > 0 and m.readback_s > 0
    assert m.launch_s + m.readback_s == pytest.approx(m.tick_s, rel=1e-12)
    snap = m.snapshot()
    assert (snap["launch_s"], snap["readback_s"]) == (m.launch_s, m.readback_s)
    text = m.prometheus_text()
    assert f"neura_tick_launch_seconds_total {m.launch_s:.6g}" in text
    assert f"neura_tick_readback_seconds_total {m.readback_s:.6g}" in text


def test_serving_outputs_identical_with_the_profiler_on(serve_setup, serve_trace):
    net, qparams = serve_setup
    (_, ticks_on, counts_on, _), _ = serve_trace
    _, ticks_off, counts_off, _ = _serve(net, qparams)
    assert ticks_on == ticks_off
    assert counts_on.keys() == counts_off.keys()
    for uid in counts_off:
        np.testing.assert_array_equal(counts_on[uid], counts_off[uid])


# -- the population sweep ----------------------------------------------------


def _dse_case():
    net = NetworkConfig(
        layers=(
            LayerConfig(n_in=256, n_out=16, neuron=NeuronModel.LIF, w_bits=6, u_bits=16,
                        topology=Topology.ATA_F, beta=0.9),
            LayerConfig(n_in=16, n_out=10, neuron=NeuronModel.LIF, w_bits=6, u_bits=16),
        ),
        n_steps=6,
    )  # fmt: skip
    params = init_float_params(jax.random.PRNGKey(0), net)
    cands = [net.replace_precisions(w_bits=b, w_rec_bits=b, leak_bits=l)
             for b, l in [(4, 3), (6, 8), (8, 8)]]  # fmt: skip
    qps = [quantize_params(c, params)[0] for c in cands]
    ds = mnist_like(n=40, T=6, seed=5)
    return net, cands, qps, ds


@pytest.fixture(scope="module")
def dse_trace(tmp_path_factory):
    net, cands, qps, ds = _dse_case()

    def sweep():
        return eval_int_population(net, cands, qps, ds, batch_size=16, return_stats=True)

    sweep()  # compile outside the trace
    return _traced(tmp_path_factory.mktemp("dse_trace"), sweep), sweep


def test_population_spans_one_stack_and_one_batch_per_batch(dse_trace):
    (_, events), _ = dse_trace
    stack = [e for e in events if e[0] == "neura.dse.stack"]
    assert len(stack) == 1 and stack[0][3]["candidates"] == 3
    assert stack[0][3]["shards"] == 1
    assert stack[0][3]["cores"] == 2 and stack[0][3]["recurrent_macs"] == 0  # ATA-F, no ATA-T
    batches = [e for e in events if e[0] == "neura.dse.batch"]
    assert [(b[3]["index"], b[3]["samples"]) for b in batches] == [(0, 16), (1, 16), (2, 8)]
    assert {b[3]["steps"] for b in batches} == {6}
    assert stack[0][2] <= batches[0][1]
    for b in batches:
        kids = [e[0] for e in _children(events, b)]
        assert kids == ["neura.dse.launch", "neura.dse.readback"]


def _place_before_batches(events):
    """The one ``neura.dse.place`` of a traced call, checked to lie between stack and batches."""
    (stack,) = [e for e in events if e[0] == "neura.dse.stack"]
    (place,) = [e for e in events if e[0] == "neura.dse.place"]
    batches = [e for e in events if e[0] == "neura.dse.batch"]
    assert stack[2] <= place[1] and place[2] <= batches[0][1]
    for b in batches:  # on a mesh the launch holds the (empty) padding span too
        kids = [e[0] for e in _children(events, b) if e[0] != "neura.dse.shard_pad"]
        assert kids == ["neura.dse.launch", "neura.dse.readback"]
    return place


def test_population_places_the_rasters_once_between_stack_and_batches(dse_trace):
    (_, events), _ = dse_trace
    place = _place_before_batches(events)
    n, T, C = _dse_case()[3].spikes.shape
    assert place[3]["bytes"] == n * T * C == 40 * 6 * 256  # uint8, in the stored layout
    assert place[3]["samples"] == n


def test_population_outputs_identical_with_the_profiler_on(dse_trace):
    ((accs_on, stats_on), _), sweep = dse_trace
    accs_off, stats_off = sweep()
    np.testing.assert_array_equal(accs_on, accs_off)
    for a, b in zip(stats_on, stats_off):
        np.testing.assert_array_equal(a["input_events_per_step"], b["input_events_per_step"])
        for u, v in zip(a["layer_events_per_step"], b["layer_events_per_step"]):
            np.testing.assert_array_equal(u, v)


_MESH_PROG = """
import os, sys, json, pathlib
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {tests!r})
import jax
from test_serve_tracing import _dse_case, _host_events, _traced
from repro.snn.train import eval_int_population

assert len(jax.devices()) == 4
net, cands, qps, ds = _dse_case()
sweep = lambda: eval_int_population(net, cands, qps, ds, batch_size=16, mesh=4)
sweep()
_, events = _traced(pathlib.Path({path!r}), sweep)
print(json.dumps(events))
"""


@pytest.fixture(scope="module")
def mesh_events(tmp_path_factory):
    """The same sweep over a mesh of four forced host devices, in a fresh interpreter."""
    if jax.default_backend() != "cpu":
        pytest.skip("forces host devices")
    path = tmp_path_factory.mktemp("mesh_trace")
    prog = _MESH_PROG.format(tests=os.path.dirname(__file__), path=str(path))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([p for p in sys.path if p] + [env.get("PYTHONPATH", "")])
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(prog)], capture_output=True,
                         text=True, env=env, timeout=300)  # fmt: skip
    assert res.returncode == 0, res.stderr[-2000:]
    return [tuple(e) for e in json.loads(res.stdout.strip().splitlines()[-1])]


def test_mesh_padding_is_a_child_of_the_launch(mesh_events):
    launches = [e for e in mesh_events if e[0] == "neura.dse.launch"]
    pads = [e for e in mesh_events if e[0] == "neura.dse.shard_pad"]
    assert len(pads) == len(launches) == 3
    for pad, launch in zip(pads, launches):
        assert launch[1] <= pad[1] and pad[2] <= launch[2]


def test_mesh_places_the_rasters_once_with_the_bytes_of_one_device(mesh_events):
    place = _place_before_batches(mesh_events)
    assert place[3]["bytes"] == 40 * 6 * 256 and place[3]["samples"] == 40


def test_stack_span_counts_the_devices_the_population_was_placed_over(mesh_events):
    (stack,) = [e for e in mesh_events if e[0] == "neura.dse.stack"]
    assert stack[3]["candidates"] == 3 and stack[3]["shards"] == 4


def test_every_span_name_is_emitted_and_none_is_a_harness_name(
    serve_trace, dse_trace, mesh_events
):
    (_, serve_events), ((_, dse_events), _) = serve_trace, dse_trace
    seen = {e[0] for e in serve_events + dse_events + mesh_events}
    assert seen == set(SPAN_NAMES)
    assert len(SPAN_NAMES) == len(set(SPAN_NAMES)) == 12
    for name in SPAN_NAMES:
        assert name.startswith("neura.") and not name.startswith("bench.")
        assert name not in HARNESS_SPANS
