"""Trace reduction on traces recorded on one TPU v5e chip.

``data/mnist_small.xplane.pb.gz`` is the traced half second of a
``mnist-serve-open`` run (``--seconds 1 --trace 1``), and
``data/dse_small.xplane.pb.gz`` that of a ``dvs-dse-sweep`` run. Busy time
is worked out here a second way, by a plain sweep over the op events, and
the readers are held to the numbers the chip run printed.
"""

import gzip
import pathlib
import types

import numpy as np
import pytest

from perfharness import harness, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
HARNESS_SPANS = {"bench.poll", "tick", "dispatch", "lane_window_call", "engine.run",
                 "bench.pass", "population_call"}  # fmt: skip


def _load(name, tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / f"{name}.xplane.pb"
    out.write_bytes(gzip.decompress((DATA / f"{name}.xplane.pb.gz").read_bytes()))
    return trace.load(out)


@pytest.fixture(scope="module")
def serve(tmp_path_factory):
    return _load("mnist_small", tmp_path_factory)


@pytest.fixture(scope="module")
def dse(tmp_path_factory):
    return _load("dse_small", tmp_path_factory)


def _busy_by_sweep(spans, lo, hi):
    total, reach = 0.0, lo
    for start, end in sorted(map(tuple, spans)):
        start, end = max(start, reach), min(end, hi)
        if end > start:
            total += end - start
            reach = end
    return total * 1e-9


@pytest.mark.parametrize("which", ["serve", "dse"])
def test_busy_matches_a_plain_sweep(which, request):
    t = request.getfixturevalue(which)
    assert list(t.devices) == [0]
    lo, hi = t.window
    busy = trace.busy_s(t)[0]
    assert busy == pytest.approx(_busy_by_sweep(t.devices[0].ops, lo, hi), rel=1e-12)
    assert 0 < busy < t.window_s


@pytest.mark.parametrize(
    "which, window_s, busy_s, idle_pct",
    [
        ("serve", 0.499609746, 0.016656172, 96.66616351395196),
        ("dse", 0.589747133, 0.10482585200000001, 82.22528841017697),
    ],
)
def test_numbers_the_chip_run_printed(which, window_s, busy_s, idle_pct, request):
    t = request.getfixturevalue(which)
    assert t.window_s == pytest.approx(window_s, rel=1e-9)
    assert trace.busy_s(t)[0] == pytest.approx(busy_s, rel=1e-9)
    assert trace.idle_pct(t) == pytest.approx(idle_pct, rel=1e-9)


@pytest.mark.parametrize(
    "which, metric, value",
    [
        ("serve", "lane_window_ms.serve", 0.05944488297872341),
        ("serve", "device_idle_pct.serve", 96.66616351395196),
        ("dse", "sweep_call_ms.dse", 4.339835666666667),
        ("dse", "device_idle_pct.dse", 82.22528841017697),
    ],
)
def test_readers_on_the_recorded_trace(which, metric, value, request):
    t = request.getfixturevalue(which)
    run = types.SimpleNamespace(trace=t, data={})
    assert harness.reader(metric).read(run) == pytest.approx(value, rel=1e-9)


def test_program_calls_are_the_named_jit_programs(serve, dse):
    lane = trace.program_calls(serve, ["_lane_window_packed"])[0]
    sweep = trace.program_calls(dse, ["_population_fwd"])[0]
    assert len(lane) > 100 and np.all((lane > 1e-6) & (lane < 1e-3))
    assert len(sweep) >= 4 and np.all((sweep > 1e-3) & (sweep < 1e-2))
    assert trace.program_ms(serve, ["_population_fwd"]) is None


@pytest.mark.parametrize("which", ["serve", "dse"])
def test_idle_gaps_and_top_ops(which, request):
    t = request.getfixturevalue(which)
    gaps = trace.idle_gaps(t)
    idle = t.window_s - trace.busy_s(t)[0]
    assert 0 < len(gaps) <= 10
    assert sum(s for _, s in gaps) <= idle * (1 + 1e-9)
    assert HARNESS_SPANS & {name for name, _ in gaps}
    top = trace.top_ops(t)
    assert 0 < len(top) <= 10 and all(s > 0 for _, s in top)
