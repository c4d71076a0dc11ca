"""Reduction of the program's ``neura.*`` spans, and the readers built on it.

The reductions are checked on synthetic traces whose answers are worked out
by hand; the argument reader on a trace written by the profiler here. Every
new reader returns ``None`` where there is nothing to read: on the two
traces recorded on the chip before the program had spans, and on an
untraced run.
"""

import gzip
import pathlib
import types

import numpy as np
import pytest

from perfharness import harness, spans, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
SERVE = ("dispatch_ms.serve", "tick_launch_ms.serve", "tick_readback_ms.serve",
         "tick_host_ms.serve", "lanes_per_tick.serve")  # fmt: skip
DSE = ("stack_ms.dse", "batch_launch_ms.dse", "batch_readback_ms.dse")


def _device(modules, names):
    mods = np.asarray(modules, float).reshape(-1, 2)
    return trace.Device(ops=mods.copy(), op_names=["op"] * len(mods), modules=mods,
                        module_names=names)  # fmt: skip


def _serve_trace():
    # two ticks in one poll window [0, 2000]; a third tick after the window
    host = [
        ("bench.poll", 0, 2000),
        ("neura.serve.dispatch", 10, 60),
        ("neura.serve.tick", 100, 500),
        ("neura.serve.pack", 110, 140),
        ("neura.serve.launch", 150, 250),
        ("neura.serve.readback", 260, 480),
        ("neura.serve.complete", 485, 495),
        ("neura.serve.dispatch", 550, 570),
        ("neura.serve.tick", 600, 900),
        ("neura.serve.pack", 605, 615),
        ("neura.serve.launch", 620, 700),
        ("neura.serve.readback", 710, 850),
        ("neura.serve.complete", 860, 890),
        ("neura.serve.launch", 3000, 3100),  # outside the window: not counted
    ]
    dev = _device(
        [[50, 90], [300, 350], [720, 880], [3200, 3300]],
        ["jit_other(1)", "jit__lane_window_packed(2)", "jit__lane_window_packed(2)",
         "jit__lane_window_packed(2)"],
    )  # fmt: skip
    return trace.Trace(
        {0: dev}, np.asarray([h[1:] for h in host], float), [h[0] for h in host]
    )


def _dse_trace():
    # one pass: a stack, then two batches, each launched on four chips
    host = [
        ("bench.pass", 0, 10_000),
        ("neura.dse.stack", 10, 1010),
        ("neura.dse.batch", 1100, 4000),
        ("neura.dse.launch", 1110, 1510),
        ("neura.dse.readback", 1520, 3500),
        ("neura.dse.batch", 4100, 7000),
        ("neura.dse.launch", 4110, 4310),
        ("neura.dse.readback", 4320, 6000),
    ]
    name = "jit__population_sharded_jit(4)"
    ends = [(3000, 6100), (3100, 5500), (3400, 5900), (3200, 5000)]
    devices = {
        i: _device([[1600, e1], [4400, e2]], [name, name]) for i, (e1, e2) in enumerate(ends)
    }
    return trace.Trace(devices, np.asarray([h[1:] for h in host], float), [h[0] for h in host])


def test_spans_are_named_sorted_and_inside_the_window():
    s = spans.spans(_serve_trace(), "neura.serve.launch")
    assert np.array_equal(s, [[150, 250], [620, 700]])
    assert spans.spans(None, "neura.serve.launch").shape == (0, 2)
    assert spans.mean_ms(_serve_trace(), "neura.serve.dispatch") == pytest.approx(35e-6)
    assert spans.mean_ms(_serve_trace(), "neura.absent") is None


def test_holder_finds_the_enclosing_span_or_none():
    outer = np.asarray([[0, 10], [20, 30]], float)
    inner = np.asarray([[1, 2], [21, 29], [12, 15], [25, 35], [-5, -1]], float)
    assert list(spans.holder(outer, inner)) == [0, 1, -1, -1, -1]
    assert list(spans.holder(np.zeros((0, 2)), inner)) == [-1] * 5


def test_self_time_is_the_parent_less_the_children_it_holds():
    # tick 1: 400 - 100 - 220 = 80; tick 2: 300 - 80 - 140 = 80
    t = _serve_trace()
    got = spans.self_ms(t, "neura.serve.tick", ("neura.serve.launch", "neura.serve.readback"))
    assert got == pytest.approx(80e-6)
    assert spans.self_ms(t, "neura.serve.tick", ()) == pytest.approx(350e-6)
    assert spans.self_ms(t, "neura.absent", ()) is None


def test_readback_tail_matches_each_tick_to_its_execution_and_clips_at_zero():
    # tick 1: readback ends 480, its execution (first after 150) ends 350: 130.
    # tick 2: readback ends 850, its execution ends 880: -30, clipped to 0.
    t = _serve_trace()
    got = spans.readback_tail_ms(
        t, "neura.serve.launch", "neura.serve.readback", ("_lane_window_packed",)
    )
    assert got == pytest.approx(65e-6)
    assert spans.readback_tail_ms(t, "neura.serve.launch", "neura.serve.readback", ("_x",)) is None


def test_readback_tail_takes_the_latest_end_over_four_chips():
    # batch 1: readback ends 3500, latest chip 3400: 100; batch 2: 6000 - 6100 -> 0
    t = _dse_trace()
    got = spans.readback_tail_ms(
        t, "neura.dse.launch", "neura.dse.readback", ("_population_sharded_jit",)
    )
    assert got == pytest.approx(50e-6)


def test_an_execution_after_the_readback_is_not_the_batch_s():
    # one chip ran nothing for batch 2 before its readback ended: it is left out
    t = _dse_trace()
    t.devices[0] = _device([[1600, 3000], [6500, 6600]], ["jit__population_fwd(1)"] * 2)
    programs = ("_population_fwd", "_population_sharded_jit")
    got = spans.readback_tail_ms(t, "neura.dse.launch", "neura.dse.readback", programs)
    # batch 1: 3500 - 3400 = 100; batch 2: chips 1-3 end by 5900, so 6000 - 5900 = 100
    assert got == pytest.approx(100e-6)


@pytest.mark.parametrize(
    "metric, value",
    [
        ("dispatch_ms.serve", 35e-6),
        ("tick_launch_ms.serve", 90e-6),
        ("tick_readback_ms.serve", 65e-6),
        ("tick_host_ms.serve", 80e-6),
    ],
)
def test_serve_readers_on_a_synthetic_trace(metric, value):
    run = types.SimpleNamespace(trace=_serve_trace(), data={})
    assert harness.reader(metric).read(run) == pytest.approx(value)


@pytest.mark.parametrize(
    "metric, value",
    [("stack_ms.dse", 1000e-6), ("batch_launch_ms.dse", 300e-6), ("batch_readback_ms.dse", 50e-6)],
)
def test_dse_readers_on_a_synthetic_trace(metric, value):
    run = types.SimpleNamespace(trace=_dse_trace(), data={})
    assert harness.reader(metric).read(run) == pytest.approx(value)


def test_lanes_per_tick_reads_the_span_argument_from_the_trace_file(tmp_path, monkeypatch):
    import jax

    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    path = tmp_path / "trace"
    jax.profiler.start_trace(str(path))
    try:
        with jax.profiler.TraceAnnotation("bench.poll"):
            for active in (3, 5, 8):
                with jax.profiler.TraceAnnotation("neura.serve.tick", active=active, k=4):
                    pass
    finally:
        jax.profiler.stop_trace()
    t = harness.load_trace(path)
    assert t.host_names.count("neura.serve.tick") == 3
    run = types.SimpleNamespace(trace=t, data={})
    assert harness.reader("lanes_per_tick.serve").read(run) == pytest.approx(16 / 3)
    assert spans.arg_mean(t, "neura.serve.tick", "k") == pytest.approx(4.0)
    assert spans.arg_mean(t, "neura.serve.tick", "absent") is None
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path / "none")
    assert spans.trace_file() is None
    assert harness.reader("lanes_per_tick.serve").read(run) is None


def _recorded(name, tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / f"{name}.xplane.pb"
    out.write_bytes(gzip.decompress((DATA / f"{name}.xplane.pb.gz").read_bytes()))
    return trace.load(out)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    return {n: _recorded(n, tmp_path_factory) for n in ("mnist_small", "dse_small")}


@pytest.mark.parametrize("metric", SERVE + DSE)
@pytest.mark.parametrize("which", ["mnist_small", "dse_small", "untraced"])
def test_new_readers_find_nothing_before_the_spans(metric, which, recorded):
    t = None if which == "untraced" else recorded[which]
    run = types.SimpleNamespace(trace=t, data={})
    assert harness.reader(metric).read(run) is None
