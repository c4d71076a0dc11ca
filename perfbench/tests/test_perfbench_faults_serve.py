"""A serving run with its timed path broken underneath reads `correct: false`.

Each fault is planted in the program's lane-window call, where the tick
produces its answers; the rest of the run (traffic, engine, window,
reference, checks) is the benchmark's own. The control, the program fed
the same weights held at int4, must fail too.
"""

import jax
import jax.numpy as jnp
import lif_int
import pytest

import _faults
from perfharness import program

CELL = "mnist-serve-open"


def _state_unchanged(orig):
    def call(net, qparams, states, x, meta, ff_mode, dmesh=None, event_budget=None):
        keep = jax.tree.map(jnp.copy, states)
        _, packed = orig(net, qparams, states, x, meta, ff_mode, dmesh, event_budget)
        return keep, packed

    return call


def _half_batch_left_out(orig):
    def call(*args, **kwargs):
        states, packed = orig(*args, **kwargs)
        return states, packed.at[:, packed.shape[1] // 2 :, :].set(0)

    return call


def _answer_altered(orig):
    def call(*args, **kwargs):
        states, packed = orig(*args, **kwargs)
        return states, packed.at[0, :, 0].add(1)

    return call


def test_sound_run_is_correct():
    res = _faults.run(CELL)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 2000 and res["failed"] == 0
    assert set(res["metrics"]) == {"samples_per_s", "p95_latency_ms", "setup_s"}


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch_left_out, _answer_altered])
def test_fault_reads_incorrect(monkeypatch, fault):
    from repro.serve import snn_engine

    monkeypatch.setattr(snn_engine, "_lane_window_packed", fault(snn_engine._lane_window_packed))
    res = _faults.run(CELL)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_int4_control_reads_incorrect(monkeypatch):
    config, _ = _faults.small_cell(CELL)
    orig = program.qparams
    monkeypatch.setattr(
        program, "qparams", lambda w: orig(lif_int.control_weights(config["layers"], w))
    )
    res = _faults.run(CELL)
    assert not res["correct"]
