"""Trace reduction: busy union, program time, top ops and idle gaps."""

import numpy as np
import pytest

from perfharness import trace


def _trace():
    # device 0: ops at [10,20] [15,30] [50,60]; device 1: [10,40]
    d0 = trace.Device(
        ops=np.array([[10, 20], [15, 30], [50, 60]], float),
        op_names=["fusion.1", "dot.2", "fusion.1"],
        modules=np.array([[10, 30], [50, 60], [200, 210]], float),
        module_names=["jit__lane_window_packed(3)", "jit_other(1)", "jit__lane_window_packed(3)"],
    )
    d1 = trace.Device(
        ops=np.array([[10, 40]], float),
        op_names=["fusion.1"],
        modules=np.array([[10, 40]], float),
        module_names=["jit__lane_window_packed(7)"],
    )
    host = np.array([[0, 100], [5, 45], [31, 44], [46, 95], [60, 70]], float)
    names = ["bench.poll", "tick", "lane_window_call", "dispatch", "inner"]
    return trace.Trace({0: d0, 1: d1}, host, names)


def test_window_is_the_bench_spans():
    t = _trace()
    assert t.window == (0.0, 100.0)
    assert t.window_s == pytest.approx(100e-9)


def test_busy_is_the_union_of_ops():
    busy = trace.busy_s(_trace())
    assert busy[0] == pytest.approx(30e-9)  # [10,30] + [50,60]
    assert busy[1] == pytest.approx(30e-9)
    assert trace.idle_pct(_trace()) == pytest.approx(70.0)


def test_program_calls_by_jit_name_inside_the_window():
    calls = trace.program_calls(_trace(), ["_lane_window_packed"])
    assert np.allclose(calls[0], [20e-9])  # the call at 200 is outside the window
    assert np.allclose(calls[1], [30e-9])
    assert trace.program_ms(_trace(), ["_lane_window_packed"]) == pytest.approx(25e-6)
    assert trace.program_ms(_trace(), ["_absent"]) is None


def test_top_ops_sum_over_devices():
    top = trace.top_ops(_trace())
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(50e-9)
    assert top[1] == ["dot.2", pytest.approx(15e-9)]


def test_idle_gaps_labelled_by_innermost_host_span():
    gaps = dict(trace.idle_gaps(_trace()))
    # gaps of device 0: [0,10] in tick, [30,50]: mid 40 in lane_window_call,
    # [60,100]: mid 80 in dispatch (inner ended at 70)
    assert gaps == {
        "dispatch": pytest.approx(40e-9),
        "lane_window_call": pytest.approx(20e-9),
        "tick": pytest.approx(10e-9),
    }


def test_union_merges_overlaps_and_clips():
    u = trace._union(np.array([[5, 8], [0, 3], [2, 4], [7, 12]], float), 1, 10)
    assert np.array_equal(u, [[1, 4], [5, 10]])
