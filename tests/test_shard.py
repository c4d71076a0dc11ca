"""Sharded execution parity: every sharded path is bit-exact with serial.

These tests build a mesh over *all* ambient devices, so the same suite
covers both regimes:

* default host (1 device): the single-device fallback paths run -- they
  must be the serial code verbatim;
* CI's multi-device leg (``XLA_FLAGS=--xla_force_host_platform_device_count=4``):
  real ``shard_map`` partitioning runs, including ragged remainders.

``test_forced_multidevice_parity_subprocess`` additionally forces 2 host
devices in a fresh interpreter, so genuine cross-device sharding is
exercised even when the ambient suite runs on one device.
"""

import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import shard
from repro.core.backend import EventBackend, run_int_batched
from repro.core.network import (
    NetworkConfig,
    init_float_params,
    quantize_params,
    run_int,
)
from repro.core.snn_layer import LayerConfig, NeuronModel, ResetMode, Topology
from repro.data.snn_datasets import mnist_like
from repro.snn.surrogate import fast_sigmoid
from repro.snn.train import eval_float, eval_int, eval_int_population

N_DEV = len(jax.devices())


def _make_net(topology=Topology.FF, neuron=NeuronModel.LIF, T=6):
    return NetworkConfig(
        layers=(
            LayerConfig(n_in=256, n_out=32, neuron=neuron, w_bits=6, u_bits=16,
                        topology=topology, reset=ResetMode.SUBTRACT, beta=0.9),
            LayerConfig(n_in=32, n_out=10, neuron=neuron, w_bits=6, u_bits=16, beta=0.77),
        ),
        n_steps=T,
    )


def _quantized(net, seed=0):
    params = init_float_params(jax.random.PRNGKey(seed), net)
    return params, quantize_params(net, params)[0]


def _spikes(T, batch, n_in=256, seed=1, rate=0.3):
    u = jax.random.uniform(jax.random.PRNGKey(seed), (T, batch, n_in))
    return (u < rate).astype(jnp.int32)


def _assert_records_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.spike_counts), np.asarray(b.spike_counts))
    assert len(a.layer_spikes) == len(b.layer_spikes)
    for x, y in zip(a.layer_spikes, b.layer_spikes):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(a.input_events), np.asarray(b.input_events))


# ---------------------------------------------------------------------------
# Mesh plumbing
# ---------------------------------------------------------------------------


def test_make_mesh_and_resolve():
    dm = shard.make_mesh()
    assert dm.n_shards == N_DEV
    assert shard.make_mesh(1).mesh is None  # 1 device = serial fallback
    assert shard.resolve_mesh(None) is None
    assert shard.resolve_mesh("auto").n_shards == N_DEV
    assert shard.resolve_mesh(1).n_shards == 1
    assert shard.resolve_mesh(dm) is dm
    with pytest.raises(ValueError, match="exceeds"):
        shard.make_mesh(N_DEV + 1)
    with pytest.raises(ValueError, match="cannot interpret"):
        shard.resolve_mesh(3.5)
    # a raw 1-D jax Mesh resolves; its axis name is adopted
    from jax.sharding import Mesh

    raw = Mesh(np.asarray(jax.devices()), ("lanes",))
    assert shard.resolve_mesh(raw).axis == "lanes"


def test_device_mesh_is_hashable_static_arg():
    dm = shard.make_mesh()
    assert hash(dm) == hash(shard.make_mesh())  # stable across rebuilds


def test_pad_to_shards_modes():
    dm = shard.make_mesh()
    x = jnp.arange(2 * 5 * 3).reshape(2, 5, 3)
    padded = shard.pad_to_shards(x, dm, axis=1)
    assert padded.shape[1] % dm.n_shards == 0
    np.testing.assert_array_equal(np.asarray(padded[:, :5]), np.asarray(x))
    if padded.shape[1] > 5:
        assert int(jnp.sum(jnp.abs(padded[:, 5:]))) == 0
    edge = shard.pad_to_shards(x, dm, axis=1, mode="edge")
    if edge.shape[1] > 5:
        np.testing.assert_array_equal(np.asarray(edge[:, -1]), np.asarray(x[:, -1]))


# ---------------------------------------------------------------------------
# Sample-axis parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch", [8, 7], ids=["even", "ragged"])
@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_run_int_sharded_bit_exact(batch, backend):
    net = _make_net()
    _, qparams = _quantized(net)
    spikes = _spikes(6, batch)
    ref = run_int(net, qparams, spikes)
    got = shard.run_int_sharded(net, qparams, spikes, "auto", backend=backend)
    _assert_records_equal(ref, got)


def test_run_int_sharded_recurrent_and_synaptic():
    for topology, neuron in [(Topology.ATA_F, NeuronModel.LIF), (Topology.FF, NeuronModel.SYNAPTIC)]:
        net = _make_net(topology=topology, neuron=neuron)
        _, qparams = _quantized(net)
        spikes = _spikes(6, 5)
        _assert_records_equal(
            run_int(net, qparams, spikes),
            shard.run_int_sharded(net, qparams, spikes, "auto"),
        )


def test_run_int_sharded_event_backend_shards_or_warns():
    """event x mesh: auto/gather/pallas shard via the pallas surrogate; only
    an explicit csr opt-in abandons the mesh -- with a warning, and only
    when a real multi-device partition is being given up."""
    net = _make_net()
    _, qparams = _quantized(net)
    spikes = _spikes(6, 4)
    ref = run_int(net, qparams, spikes)
    # a 1-device mesh honors jit_compatible=False silently: the serial path
    # was the contract anyway, so there is no partition to warn about
    for backend in ["event", EventBackend("csr")]:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            rec = shard.run_int_sharded(net, qparams, spikes, 1, backend=backend)
        assert not [w for w in caught if "mesh ignored" in str(w.message)]
        _assert_records_equal(ref, rec)
    if N_DEV > 1:
        # auto upgrades to the jit-compatible pallas surrogate: a real
        # sharded run, bit-exact, no warning
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            rec = shard.run_int_sharded(net, qparams, spikes, "auto", backend="event")
        assert not [w for w in caught if "mesh ignored" in str(w.message)]
        _assert_records_equal(ref, rec)
        rec = shard.run_int_sharded(
            net, qparams, spikes, "auto", backend=EventBackend("pallas")
        )
        _assert_records_equal(ref, rec)
        # explicit csr is host-side by design: warn, run serially, stay exact
        with pytest.warns(UserWarning, match="mesh ignored"):
            rec = shard.run_int_sharded(
                net, qparams, spikes, "auto", backend=EventBackend("csr")
            )
        _assert_records_equal(ref, rec)


def test_run_float_sharded_bit_exact():
    net = _make_net()
    params, _ = _quantized(net)
    spike_fn = fast_sigmoid(25.0)
    spikes = _spikes(6, 7).astype(jnp.float32)
    from repro.core.network import run_float

    ref = run_float(net, params, spikes, spike_fn)
    got = shard.run_float_sharded(net, params, spikes, spike_fn, "auto")
    np.testing.assert_array_equal(
        np.asarray(ref.predictions()), np.asarray(got.predictions())
    )
    np.testing.assert_allclose(
        np.asarray(ref.spike_counts), np.asarray(got.spike_counts)
    )


def test_eval_int_mesh_matches_serial():
    net = _make_net()
    _, qparams = _quantized(net)
    ds = mnist_like(n=50, T=6, seed=3)  # 50: ragged final batch AND ragged shards
    acc_a, st_a = eval_int(net, qparams, ds, batch_size=24, return_stats=True)
    acc_b, st_b = eval_int(net, qparams, ds, batch_size=24, return_stats=True, mesh="auto")
    assert acc_a == acc_b
    np.testing.assert_allclose(st_a["input_events_per_step"], st_b["input_events_per_step"])
    for x, y in zip(st_a["layer_events_per_step"], st_b["layer_events_per_step"]):
        np.testing.assert_allclose(x, y)


def test_eval_int_event_backend_mesh_warns_and_matches():
    net = _make_net()
    _, qparams = _quantized(net)
    ds = mnist_like(n=24, T=6, seed=3)
    serial = eval_int(net, qparams, ds, batch_size=12, backend="event")
    if N_DEV > 1:
        # auto shards through the pallas surrogate: bit-exact, no warning
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            sharded = eval_int(net, qparams, ds, batch_size=12, backend="event", mesh="auto")
        assert not [w for w in caught if "mesh ignored" in str(w.message)]
        assert serial == sharded
        # explicit csr is host-side: warns and runs serially, same result
        with pytest.warns(UserWarning, match="mesh ignored"):
            csr = eval_int(
                net, qparams, ds, batch_size=12, backend=EventBackend("csr"), mesh="auto"
            )
        assert serial == csr
    else:
        sharded = eval_int(net, qparams, ds, batch_size=12, backend="event", mesh="auto")
        assert serial == sharded


def test_eval_float_mesh_matches_serial():
    net = _make_net()
    params, _ = _quantized(net)
    ds = mnist_like(n=50, T=6, seed=4)
    assert eval_float(net, params, ds, batch_size=24) == eval_float(
        net, params, ds, batch_size=24, mesh="auto"
    )


# ---------------------------------------------------------------------------
# Candidate-axis parity (the DSE fan-out)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_cands", [4, 3], ids=["even", "ragged"])
def test_eval_int_population_mesh_matches_serial(n_cands):
    net = _make_net(topology=Topology.ATA_F)
    params, _ = _quantized(net)
    ds = mnist_like(n=48, T=6, seed=5)
    cands = [
        net.replace_precisions(w_bits=b, w_rec_bits=b, leak_bits=l)
        for b, l in [(4, 3), (6, 8), (8, 8), (5, 4)][:n_cands]
    ]
    qps = [quantize_params(c, params)[0] for c in cands]
    pa, sta = eval_int_population(net, cands, qps, ds, batch_size=24, return_stats=True)
    pb, stb = eval_int_population(
        net, cands, qps, ds, batch_size=24, return_stats=True, mesh="auto"
    )
    np.testing.assert_array_equal(pa, pb)
    for x, y in zip(sta, stb):
        np.testing.assert_allclose(x["input_events_per_step"], y["input_events_per_step"])
        for u, v in zip(x["layer_events_per_step"], y["layer_events_per_step"]):
            np.testing.assert_allclose(u, v)
    # and the population sweep agrees with per-candidate serial eval_int
    serial = np.asarray([eval_int(c, q, ds, batch_size=24) for c, q in zip(cands, qps)])
    np.testing.assert_array_equal(serial, pb)


def test_explore_snn_mesh_scores_match():
    from repro.core.flexplorer import annealer as annealer_lib
    from repro.core.flexplorer.explorer import EvalSpec, SearchSpec, SNNSearchSpace, explore_snn

    net = _make_net()
    params, _ = _quantized(net)
    ds = mnist_like(n=48, T=6, seed=6)
    space = SNNSearchSpace(ff_bits=(4, 6, 8), leak_bits=(3, 8))
    cfg = annealer_lib.AnnealConfig(t_start=1.0, t_min=0.3, alpha=0.5, seed=0)
    spec = SearchSpec(space=space, config=cfg, population=4)
    plain = explore_snn(net, params, ds, search=spec, evaluate=EvalSpec(batch=24))
    meshed = explore_snn(net, params, ds, search=spec, evaluate=EvalSpec(batch=24, mesh="auto"))
    shared = plain.anneal.cache.keys() & meshed.anneal.cache.keys()
    assert shared
    for c in shared:
        assert plain.anneal.cache[c][3] == meshed.anneal.cache[c][3]  # accuracy


# ---------------------------------------------------------------------------
# Ragged batched runner parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch", [8, 5], ids=["even", "ragged"])
def test_run_int_batched_mesh_matches_serial(batch):
    net = _make_net(T=8)
    _, qparams = _quantized(net)
    rast = _spikes(8, batch, seed=5, rate=0.25)
    lens = jnp.asarray(([8, 3, 5, 1, 7, 2, 8, 4])[:batch], jnp.int32)
    _assert_records_equal(
        run_int_batched(net, qparams, rast, lens),
        run_int_batched(net, qparams, rast, lens, mesh="auto"),
    )


# ---------------------------------------------------------------------------
# Device-sharded serving lanes
# ---------------------------------------------------------------------------


def test_sharded_serve_lanes_bit_exact():
    from repro.serve.snn_engine import SNNRequest, SNNServeEngine

    net = _make_net(T=8)
    _, qparams = _quantized(net)
    # data_parallel over-asks clamp, with a warning, to the largest usable
    # shard count
    expected = min(8, N_DEV)
    while 8 % expected:
        expected -= 1
    if expected < 8:
        with pytest.warns(RuntimeWarning, match="clamped"):
            eng = SNNServeEngine(net, qparams, max_batch=8, data_parallel=8)
    else:
        eng = SNNServeEngine(net, qparams, max_batch=8, data_parallel=8)
    assert eng.data_parallel == expected
    rng = np.random.default_rng(0)
    reqs = [
        SNNRequest(uid=i, raster=(rng.random((int(rng.integers(2, 9)), 256)) < 0.3).astype(np.uint8))
        for i in range(20)
    ]
    for r in reqs:
        eng.submit(r)
    done = eng.drain()
    assert len(done) == 20
    for r in done:
        ref = run_int(net, qparams, jnp.asarray(r.raster[:, None, :], jnp.int32))
        np.testing.assert_array_equal(r.spike_counts, np.asarray(ref.spike_counts)[0])
        assert r.route == "lanes"


def test_sharded_serve_rejects_indivisible_pool():
    from repro.serve.snn_engine import SNNServeEngine

    net = _make_net()
    _, qparams = _quantized(net)
    if N_DEV > 1:
        with pytest.raises(ValueError, match="divide max_batch"):
            SNNServeEngine(net, qparams, max_batch=N_DEV + 1, data_parallel=N_DEV)
    else:  # single device: any pool size degrades to the serial engine
        with pytest.warns(RuntimeWarning, match="clamped to 1"):
            eng = SNNServeEngine(net, qparams, max_batch=3, data_parallel=2)
        assert eng.data_parallel == 1


def test_sharded_serve_warmup_then_serve():
    from repro.serve.snn_engine import SNNRequest, SNNServeEngine

    net = _make_net(T=8)
    _, qparams = _quantized(net)
    eng = SNNServeEngine(net, qparams, max_batch=4, data_parallel=N_DEV if 4 % N_DEV == 0 else 1)
    eng.warmup()
    assert eng.n_served == 0
    r = SNNRequest(uid=0, raster=np.asarray(_spikes(8, 1, seed=9)[:, 0]).astype(np.uint8))
    eng.submit(r)
    done = eng.drain()
    ref = run_int(net, qparams, jnp.asarray(done[0].raster[:, None, :], jnp.int32))
    np.testing.assert_array_equal(done[0].spike_counts, np.asarray(ref.spike_counts)[0])


# ---------------------------------------------------------------------------
# Genuine multi-device execution in a fresh interpreter
# ---------------------------------------------------------------------------


@pytest.mark.skipif(jax.default_backend() != "cpu", reason="forces host devices")
def test_forced_multidevice_parity_subprocess():
    """2 forced host devices: sharded eval + population == serial, bit-exact."""
    prog = textwrap.dedent(
        """
        import os, sys, json
        # replace (not append): the ambient suite may force its own count
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import shard
        from repro.core.network import NetworkConfig, init_float_params, quantize_params, run_int
        from repro.core.snn_layer import LayerConfig, NeuronModel

        assert len(jax.devices()) == 2
        net = NetworkConfig(layers=(
            LayerConfig(n_in=64, n_out=16, neuron=NeuronModel.LIF, w_bits=6, u_bits=16),
            LayerConfig(n_in=16, n_out=4, neuron=NeuronModel.LIF, w_bits=6, u_bits=16)), n_steps=5)
        params = init_float_params(jax.random.PRNGKey(0), net)
        qp, _ = quantize_params(net, params)
        spikes = (jax.random.uniform(jax.random.PRNGKey(1), (5, 5, 64)) < 0.3).astype(jnp.int32)
        a = run_int(net, qp, spikes)
        b = shard.run_int_sharded(net, qp, spikes, "auto")
        np.testing.assert_array_equal(np.asarray(a.spike_counts), np.asarray(b.spike_counts))
        np.testing.assert_array_equal(np.asarray(a.input_events), np.asarray(b.input_events))
        # event backend: auto shards through the pallas surrogate (no warning);
        # explicit csr warns "mesh ignored" and runs serially -- both bit-exact
        import warnings
        from repro.core.backend import EventBackend
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            ev = shard.run_int_sharded(net, qp, spikes, "auto", backend="event")
        assert not [w for w in caught if "mesh ignored" in str(w.message)]
        np.testing.assert_array_equal(np.asarray(a.spike_counts), np.asarray(ev.spike_counts))
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            cs = shard.run_int_sharded(net, qp, spikes, "auto", backend=EventBackend("csr"))
        assert [w for w in caught if "mesh ignored" in str(w.message)]
        np.testing.assert_array_equal(np.asarray(a.spike_counts), np.asarray(cs.spike_counts))
        print("SUBPROCESS_PARITY_OK")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(p) for p in sys.path if p] + [env.get("PYTHONPATH", "")]
    )
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True, env=env, timeout=300
    )
    assert "SUBPROCESS_PARITY_OK" in res.stdout, res.stderr[-2000:]
