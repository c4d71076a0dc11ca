"""A population sweep with its timed path broken underneath reads `correct: false`.

Faults are planted in the program's population step and program, where the
candidates' answers are produced; the exchange between chips is left out
in a four-device run on virtual CPU devices, in a process of its own.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import lif_int
import pytest

import _faults
from perfharness import BENCH_DIR, ROOT, program

CELL = "dvs-dse-sweep"


def _state_unchanged(monkeypatch):
    from repro.core import backend

    orig = backend.int_layer_step_dynamic

    def step(cfg, p, state, s_in, beta, alpha):
        _, spk = orig(cfg, p, state, s_in, beta, alpha)
        return state, spk

    monkeypatch.setattr(backend, "int_layer_step_dynamic", step)


def _half_batch_left_out(monkeypatch):
    from repro.snn import train

    orig = train._population_fwd

    def fwd(net, stacked, beta, alpha, spikes):
        half = spikes.shape[1] // 2
        preds, evs, iev = orig(net, stacked, beta, alpha, spikes[:, :half])
        return jnp.concatenate([preds, preds], axis=1), evs, iev

    monkeypatch.setattr(train, "_population_fwd", fwd)


def _answer_altered(monkeypatch):
    from repro.snn import train

    orig = train._population_fwd

    def fwd(*args):
        preds, evs, iev = orig(*args)
        return preds.at[0].add(1), evs, iev

    monkeypatch.setattr(train, "_population_fwd", fwd)


def test_sound_run_is_correct():
    jax.clear_caches()
    res = _faults.run(CELL)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"dse_evals_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch_left_out, _answer_altered])
def test_fault_reads_incorrect(monkeypatch, fault):
    jax.clear_caches()  # the population program is traced anew with the fault in it
    fault(monkeypatch)
    res = _faults.run(CELL)
    jax.clear_caches()
    assert not res["correct"]
    assert res["checks"]["wrong_results"]["value"] > 0


def test_int4_control_reads_incorrect(monkeypatch):
    config, _ = _faults.small_cell(CELL)
    orig = program.qparams
    monkeypatch.setattr(
        program, "qparams", lambda w: orig(lif_int.control_weights(config["layers"], w))
    )
    res = _faults.run(CELL)
    assert not res["correct"]


_FOUR_DEVICES = """
import sys
sys.path[:0] = [{bench!r}, {bench!r} + "/tests", {bench!r} + "/references", {src!r}]
import jax, jax.numpy as jnp
import _faults
from repro.core import shard

assert len(jax.devices()) == 4
sound = _faults.run("dvs-dse-sweep-4chip")
orig = shard.run_int_population_sharded

def no_exchange(net, stacked, beta, alpha, spikes, mesh, return_events=False):
    counts, emitted = orig(net, stacked, beta, alpha, spikes, mesh, return_events=True)
    local = counts.shape[0] // 4  # each chip's own candidates, never gathered
    counts = jnp.concatenate([counts[:local]] * 4)
    emitted = jnp.concatenate([emitted[:local]] * 4)
    return (counts, emitted) if return_events else counts

shard.run_int_population_sharded = no_exchange
broken = _faults.run("dvs-dse-sweep-4chip")
print("RESULT", sound["correct"], broken["correct"], broken["checks"]["wrong_results"]["value"])
"""


def test_four_chips_exchange_left_out_reads_incorrect():
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4"
    )
    code = _FOUR_DEVICES.format(bench=str(BENCH_DIR), src=str(ROOT / "src"))
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=600
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT")][-1].split()
    assert line[1] == "True"  # the sound four-device run is correct
    assert line[2] == "False" and int(line[3]) > 0
