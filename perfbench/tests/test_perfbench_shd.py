"""The SHD population cell on the CPU, and the readers of scoped device time.

``shd-dse-sweep`` runs the 700-200-20 Synaptic ATA-T network, whose first
layer is split over three cores; at a small size its sweep is correct, and
fed int4 weights or a broken step it is not. The scoped readers take each
op's ``tf_op`` from the trace file (``perfharness.opmeta``), checked here on
the trace recorded on the chip, where ``jnp.einsum`` left its own scope
(``bi,io->bo``) on the population sweep's products.
"""

import gzip
import pathlib
import types

import jax
import lif_int
import numpy as np
import pytest

import _faults
from perfharness import harness, opmeta, program, spans, trace

CELL = "shd-dse-sweep"
DATA = pathlib.Path(__file__).resolve().parent / "data"
PROGRAMS = ("_population_fwd", "_population_sharded_jit")


def test_sound_run_is_correct():
    jax.clear_caches()
    res = _faults.run(CELL)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"dse_evals_per_s", "setup_s"}


def test_state_left_unchanged_reads_incorrect(monkeypatch):
    from repro.core import backend

    orig = backend.int_layer_step_dynamic

    def step(cfg, p, state, s_in, beta, alpha):
        _, spk = orig(cfg, p, state, s_in, beta, alpha)
        return state, spk

    jax.clear_caches()
    monkeypatch.setattr(backend, "int_layer_step_dynamic", step)
    res = _faults.run(CELL)
    jax.clear_caches()
    assert not res["correct"] and res["checks"]["wrong_results"]["value"] > 0


def test_int4_control_reads_incorrect(monkeypatch):
    config, _ = _faults.small_cell(CELL)
    orig = program.qparams
    monkeypatch.setattr(
        program, "qparams", lambda w: orig(lif_int.control_weights(config["layers"], w))
    )
    assert not _faults.run(CELL)["correct"]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    out = tmp_path_factory.mktemp("out")
    path = out / "trace" / "plugins" / "profile" / "run" / "dse.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(gzip.decompress((DATA / "dse_small.xplane.pb.gz").read_bytes()))
    return out, path, trace.load(path)


def test_wire_reader_finds_every_op_profile_data_finds(recorded):
    _, path, t = recorded
    ops = opmeta.device_ops(path)
    assert list(ops) == list(t.devices) == [0]
    spans_ns, names = ops[0]
    assert spans_ns.shape == t.devices[0].ops.shape
    assert np.abs(spans_ns - t.devices[0].ops).max() < 10.0  # ns: the two readers round alike
    dots = [n for n in names if "/bi,io->bo/" in n]
    assert dots and all(n.startswith("jit(") for n in dots)


def test_scoped_time_is_a_part_of_the_call(recorded, monkeypatch):
    out, _, t = recorded
    monkeypatch.setattr(harness, "OUT_DIR", out)
    dot = opmeta.scoped_ms(t, "bi,io->bo", PROGRAMS)
    call = trace.program_ms(t, PROGRAMS)
    assert 0 < dot < call
    assert opmeta.scoped_ms(t, "no.such.scope", PROGRAMS) is None
    run = types.SimpleNamespace(trace=t, data={}, peaks={"int8_ops_per_s": 393e12})
    for metric in ("ff_ms.dse", "recurrent_ms.dse", "recurrent_roofline_pct.dse"):
        assert harness.reader(metric).read(run) is None  # a program older than the scopes
    monkeypatch.setattr(harness, "OUT_DIR", out / "none")
    assert spans.trace_file() is None
    assert opmeta.scoped_ms(t, "bi,io->bo", PROGRAMS) is None


def test_recurrent_roofline_counts_the_dense_product(monkeypatch):
    roof = harness.reader("recurrent_roofline_pct.dse")
    assert roof.recurrent_ops(32, 100, 256, 200**2) == 2 * 32 * 100 * 256 * 200 * 200
    args = {
        ("neura.dse.stack", "candidates"): 32.0,
        ("neura.dse.stack", "shards"): 1.0,
        ("neura.dse.stack", "recurrent_macs"): 40000.0,
        ("neura.dse.batch", "steps"): 100.0,
        ("neura.dse.batch", "samples"): 256.0,
    }
    monkeypatch.setattr(spans, "arg_mean", lambda t, name, key: args[(name, key)])
    monkeypatch.setattr(opmeta, "scoped_ms", lambda t, scope, programs: 10.0)
    run = types.SimpleNamespace(trace=object(), data={}, peaks={"int8_ops_per_s": 393e12})
    want = 100.0 * 2 * 32 * 100 * 256 * 40000 / 10e-3 / 393e12
    assert roof.read(run) == pytest.approx(want)
    args[("neura.dse.stack", "shards")] = 4.0  # a mesh: each chip holds its 8 candidates
    assert roof.read(run) == pytest.approx(want / 4)
