"""Backend parity: every backend is bit-exact against ``reference``.

The fused backend is exercised with ``use_pallas=True, interpret=True`` so
the *actual Pallas kernels* (int spike matmul + lif_scan) run on CPU, not
just their jnp oracles.  No hypothesis dependency -- this suite is the
always-on floor under the property tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as backend_lib
from repro.core import coeff_gen, lowering
from repro.core.backend import EventBackend, FusedBackend, get_backend
from repro.core.network import (
    NetworkConfig,
    init_float_params,
    quantize_params,
    run_int,
)
from repro.core.snn_layer import (
    LayerConfig,
    NeuronModel,
    ResetMode,
    Topology,
    fused_eligible,
)
from repro.data.snn_datasets import mnist_like
from repro.snn.train import eval_int, eval_int_population

NEURONS = [NeuronModel.IF, NeuronModel.LIF]
RESETS = [ResetMode.ZERO, ResetMode.SUBTRACT]
# (n_in, hidden, n_out, T, batch): odd/prime shapes plus a tile-aligned one
SHAPES = [(19, 11, 5, 7, 3), (256, 128, 10, 6, 8)]


def _make_net(n_in, hidden, n_out, T, neuron, reset, topology=Topology.FF, **kw):
    return NetworkConfig(
        layers=(
            LayerConfig(n_in=n_in, n_out=hidden, neuron=neuron, reset=reset,
                        topology=topology, beta=0.9, **kw),
            LayerConfig(n_in=hidden, n_out=n_out, neuron=neuron, reset=reset,
                        beta=0.77, **kw),
        ),
        n_steps=T,
    )


def _quantized(net, seed=0):
    params = init_float_params(jax.random.PRNGKey(seed), net)
    qparams, _ = quantize_params(net, params)
    return qparams


def _spikes(net, T, batch, seed=1, rate=0.3):
    u = jax.random.uniform(jax.random.PRNGKey(seed), (T, batch, net.n_in))
    return (u < rate).astype(jnp.int32)


def _assert_records_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.spike_counts), np.asarray(b.spike_counts))
    assert len(a.layer_spikes) == len(b.layer_spikes)
    for x, y in zip(a.layer_spikes, b.layer_spikes):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert a.input_events is not None and b.input_events is not None
    np.testing.assert_array_equal(np.asarray(a.input_events), np.asarray(b.input_events))


@pytest.mark.parametrize("neuron", NEURONS)
@pytest.mark.parametrize("reset", RESETS)
@pytest.mark.parametrize("shape", SHAPES, ids=["odd", "tiled"])
def test_fused_bit_exact_ff(neuron, reset, shape):
    """Fused kernel path == reference on IF/LIF x reset x FF, odd + tiled shapes."""
    n_in, hidden, n_out, T, batch = shape
    net = _make_net(n_in, hidden, n_out, T, neuron, reset)
    qparams = _quantized(net)
    spikes = _spikes(net, T, batch)
    ref = run_int(net, qparams, spikes)
    fused = run_int(
        net, qparams, spikes, backend=FusedBackend(use_pallas=True, interpret=True)
    )
    _assert_records_equal(ref, fused)


@pytest.mark.parametrize(
    "w_bits,max_val",
    [(6, 1), (6, 200), (12, 1)],
    ids=["int8_binary", "int32_graded", "int32_wide_weights"],
)
def test_fused_kernel_lowering_eager_and_jitted(w_bits, max_val):
    """The kernel path's MXU feed follows ``lowering.mxu_feed``: eager runs
    pick it from the measured maximum (named in the record), traced runs
    pick on the device; every choice is bit-exact."""
    net = _make_net(19, 11, 5, 6, NeuronModel.LIF, ResetMode.SUBTRACT, w_bits=w_bits)
    qparams = _quantized(net)
    spikes = _spikes(net, 6, 3) * max_val
    fused = FusedBackend(use_pallas=True, interpret=True)
    ref = run_int(net, qparams, spikes)
    eager = run_int(net, qparams, spikes, backend=fused)
    _assert_records_equal(ref, eager)
    assert eager.lowerings == [
        f"{lowering.mxu_feed(w_bits, max_val)}+lif_scan",
        f"{lowering.mxu_feed(w_bits, 1)}+lif_scan",
    ]
    jitted = jax.jit(lambda s: run_int(net, qparams, s, backend=fused).spike_counts)
    np.testing.assert_array_equal(np.asarray(jitted(spikes)), np.asarray(ref.spike_counts))


@pytest.mark.parametrize("leak_bits", [2, 5, 8])
def test_fused_bit_exact_across_leak_precisions(leak_bits):
    net = _make_net(13, 9, 4, 8, NeuronModel.LIF, ResetMode.SUBTRACT, leak_bits=leak_bits)
    qparams = _quantized(net)
    spikes = _spikes(net, 8, 5)
    ref = run_int(net, qparams, spikes)
    fused = run_int(net, qparams, spikes, backend="fused")
    _assert_records_equal(ref, fused)


@pytest.mark.parametrize(
    "neuron,topology",
    [
        (NeuronModel.SYNAPTIC, Topology.FF),
        (NeuronModel.LIF, Topology.ATA_F),
        (NeuronModel.LIF, Topology.ATA_T),
    ],
    ids=["synaptic", "ata_f", "ata_t"],
)
def test_fused_fallback_configs_bit_exact(neuron, topology):
    """Synaptic/recurrent cores transparently fall back, staying bit-exact."""
    net = _make_net(17, 10, 6, 9, neuron, ResetMode.SUBTRACT, topology=topology)
    assert not fused_eligible(net.layers[0])
    qparams = _quantized(net)
    spikes = _spikes(net, 9, 4)
    ref = run_int(net, qparams, spikes)
    fused = run_int(net, qparams, spikes, backend="fused")
    _assert_records_equal(ref, fused)


def test_mixed_network_fuses_eligible_layers_only():
    """A net mixing a recurrent hidden core and an FF output core is exact."""
    net = NetworkConfig(
        layers=(
            LayerConfig(n_in=21, n_out=13, neuron=NeuronModel.LIF, topology=Topology.ATA_F),
            LayerConfig(n_in=13, n_out=7, neuron=NeuronModel.LIF, topology=Topology.FF),
        ),
        n_steps=10,
    )
    assert [fused_eligible(lc) for lc in net.layers] == [False, True]
    qparams = _quantized(net)
    spikes = _spikes(net, 10, 3)
    _assert_records_equal(
        run_int(net, qparams, spikes), run_int(net, qparams, spikes, backend="fused")
    )


def test_eval_int_backend_parity_on_dataset():
    net = _make_net(256, 32, 10, 8, NeuronModel.LIF, ResetMode.SUBTRACT)
    qparams = _quantized(net)
    ds = mnist_like(n=96, T=8, seed=3)
    assert eval_int(net, qparams, ds, batch_size=48) == eval_int(
        net, qparams, ds, batch_size=48, backend="fused"
    )


def test_population_eval_matches_serial():
    """One vmapped population sweep == per-candidate serial evaluation."""
    net = _make_net(256, 32, 10, 8, NeuronModel.LIF, ResetMode.SUBTRACT)
    params = init_float_params(jax.random.PRNGKey(0), net)
    ds = mnist_like(n=96, T=8, seed=4)
    cands = [
        net.replace_precisions(w_bits=b, leak_bits=l)
        for b, l in [(4, 3), (6, 8), (8, 8), (5, 4)]
    ]
    qps = [quantize_params(c, params)[0] for c in cands]
    serial = np.asarray([eval_int(c, q, ds, batch_size=48) for c, q in zip(cands, qps)])
    pop = eval_int_population(net, cands, qps, ds, batch_size=48)
    np.testing.assert_array_equal(serial, pop)


def test_population_eval_recurrent_candidates():
    net = _make_net(19, 12, 6, 7, NeuronModel.LIF, ResetMode.ZERO, topology=Topology.ATA_F)
    params = init_float_params(jax.random.PRNGKey(2), net)
    ds = mnist_like(n=48, T=7, seed=5)
    # mnist_like has 256 channels; re-rate-limit input width by slicing
    ds.spikes = ds.spikes[:, :, : net.n_in]
    cands = [net.replace_precisions(w_bits=b, w_rec_bits=b, leak_bits=l) for b, l in [(4, 3), (8, 8)]]
    qps = [quantize_params(c, params)[0] for c in cands]
    serial = np.asarray([eval_int(c, q, ds, batch_size=24) for c, q in zip(cands, qps)])
    pop = eval_int_population(net, cands, qps, ds, batch_size=24)
    np.testing.assert_array_equal(serial, pop)


def test_population_rejects_static_structure_mismatch():
    """Candidates differing in a non-DSE field must fail loudly, not misscore."""
    import dataclasses

    net = _make_net(16, 8, 4, 5, NeuronModel.LIF, ResetMode.SUBTRACT)
    params = init_float_params(jax.random.PRNGKey(0), net)
    ds = mnist_like(n=16, T=5, seed=7)
    ds.spikes = ds.spikes[:, :, : net.n_in]
    bad = dataclasses.replace(
        net, layers=(dataclasses.replace(net.layers[0], u_bits=12), net.layers[1])
    )
    qps = [quantize_params(c, params)[0] for c in (net, bad)]
    with pytest.raises(ValueError, match="static field 'u_bits'"):
        eval_int_population(net, [net, bad], qps, ds, batch_size=16)


def test_traced_decay_matches_static():
    """apply_decay_traced == apply_decay for every register value incl. bypass."""
    x = jnp.asarray(np.random.default_rng(0).integers(-(2**15), 2**15, (64,)), jnp.int32)
    for leak_bits in (1, 3, 8):
        for beta in (0.0, 0.3, 0.59765625, 0.95, 1.0):
            code = coeff_gen.encode_decay(beta, leak_bits)
            got = coeff_gen.apply_decay_traced(x, code.decay_rate_register)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(coeff_gen.apply_decay(x, code)))


def test_explore_snn_population_mode_agrees_with_serial():
    """Population DSE scores every config it shares with serial identically."""
    from repro.core.flexplorer import annealer as annealer_lib
    from repro.core.flexplorer.explorer import EvalSpec, SearchSpec, SNNSearchSpace, explore_snn

    net = _make_net(32, 16, 4, 6, NeuronModel.LIF, ResetMode.SUBTRACT)
    params = init_float_params(jax.random.PRNGKey(1), net)
    ds = mnist_like(n=64, T=6, seed=6)
    ds.spikes = ds.spikes[:, :, : net.n_in]
    ds.labels = ds.labels % 4
    space = SNNSearchSpace(ff_bits=(4, 6, 8), leak_bits=(3, 8))
    cfg = annealer_lib.AnnealConfig(t_start=1.0, t_min=0.2, alpha=0.5, seed=0)
    ev = EvalSpec(batch=32)
    serial = explore_snn(net, params, ds, search=SearchSpec(space=space, config=cfg), evaluate=ev)
    pop = explore_snn(
        net, params, ds, search=SearchSpec(space=space, config=cfg, population=4), evaluate=ev
    )
    shared = serial.anneal.cache.keys() & pop.anneal.cache.keys()
    assert shared  # both searches touched overlapping candidates
    for c in shared:
        assert serial.anneal.cache[c][3] == pop.anneal.cache[c][3]  # accuracy
    assert pop.anneal.best in pop.anneal.cache
    assert 0.0 <= pop.anneal.best_breakdown["accuracy"] <= 1.0


def test_backend_registry():
    assert {"reference", "fused", "event"} <= set(backend_lib.available_backends())
    assert get_backend("fused").name == "fused"
    assert get_backend("event").name == "event"
    assert get_backend("reference").jit_compatible
    assert not get_backend("event").jit_compatible
    inst = FusedBackend(use_pallas=False)
    assert get_backend(inst) is inst
    with pytest.raises(ValueError, match="unknown inference backend"):
        get_backend("warp-drive")


# ---------------------------------------------------------------------------
# Event-driven backend: bit-exact sparse execution incl. every fallback
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("neuron", NEURONS)
@pytest.mark.parametrize("reset", RESETS)
@pytest.mark.parametrize("rate", [0.02, 0.1, 0.3], ids=["sparse2", "sparse10", "mid30"])
def test_event_bit_exact_ff(neuron, reset, rate):
    """Event backend == reference on IF/LIF x reset x input sparsity levels."""
    net = _make_net(19, 11, 5, 7, neuron, reset)
    qparams = _quantized(net)
    spikes = _spikes(net, 7, 3, rate=rate)
    ref = run_int(net, qparams, spikes)
    ev = run_int(net, qparams, spikes, backend="event")
    _assert_records_equal(ref, ev)


@pytest.mark.parametrize(
    "neuron,topology",
    [
        (NeuronModel.SYNAPTIC, Topology.FF),
        (NeuronModel.LIF, Topology.ATA_F),
        (NeuronModel.LIF, Topology.ATA_T),
        (NeuronModel.SYNAPTIC, Topology.ATA_T),
    ],
    ids=["synaptic", "ata_f", "ata_t", "synaptic_ata_t"],
)
def test_event_covers_recurrent_and_synaptic_sparsely(neuron, topology):
    """Unlike fused, the event path covers every config: the sparse gather
    feeds precomputed FF currents into the shared step scan."""
    net = _make_net(17, 10, 6, 9, neuron, ResetMode.SUBTRACT, topology=topology)
    qparams = _quantized(net)
    spikes = _spikes(net, 9, 4, rate=0.15)
    _assert_records_equal(
        run_int(net, qparams, spikes), run_int(net, qparams, spikes, backend="event")
    )


def test_event_dense_fallback_bit_exact():
    """Near-dense input trips the density fallback; numerics must not move."""
    net = _make_net(19, 11, 5, 6, NeuronModel.LIF, ResetMode.SUBTRACT)
    qparams = _quantized(net)
    spikes = _spikes(net, 6, 3, rate=0.95)
    backend = EventBackend(dense_threshold=0.3)
    # budget for a 95%-dense raster exceeds the threshold on layer 0
    k_max = int(np.asarray(spikes.sum(-1)).max())
    assert k_max > 0.3 * net.n_in
    _assert_records_equal(
        run_int(net, qparams, spikes), run_int(net, qparams, spikes, backend=backend)
    )


def test_event_traced_fallback_under_outer_jit():
    """Inside a caller's jit there are no concrete counts; the event backend
    must transparently delegate to reference semantics, still bit-exact."""
    net = _make_net(16, 8, 4, 5, NeuronModel.LIF, ResetMode.ZERO)
    qparams = _quantized(net)
    spikes = _spikes(net, 5, 2, rate=0.2)

    @jax.jit
    def fwd(s):
        return run_int(net, qparams, s, backend="event").spike_counts

    np.testing.assert_array_equal(
        np.asarray(fwd(spikes)), np.asarray(run_int(net, qparams, spikes).spike_counts)
    )


def test_event_zero_input_window():
    """An all-silent raster (zero events) must not break budget sizing."""
    net = _make_net(16, 8, 4, 5, NeuronModel.LIF, ResetMode.SUBTRACT)
    qparams = _quantized(net)
    spikes = jnp.zeros((5, 3, 16), jnp.int32)
    _assert_records_equal(
        run_int(net, qparams, spikes), run_int(net, qparams, spikes, backend="event")
    )


def test_eval_int_event_backend_parity_on_dataset():
    """eval_int resolves the event backend without the outer jit and matches."""
    net = _make_net(256, 32, 10, 8, NeuronModel.LIF, ResetMode.SUBTRACT)
    qparams = _quantized(net)
    ds = mnist_like(n=96, T=8, seed=3)
    ref_acc, ref_stats = eval_int(net, qparams, ds, batch_size=48, return_stats=True)
    ev_acc, ev_stats = eval_int(
        net, qparams, ds, batch_size=48, return_stats=True, backend="event"
    )
    assert ref_acc == ev_acc
    np.testing.assert_allclose(
        ref_stats["input_events_per_step"], ev_stats["input_events_per_step"]
    )
    for a, b in zip(ref_stats["layer_events_per_step"], ev_stats["layer_events_per_step"]):
        np.testing.assert_allclose(a, b)


def test_record_event_stats_shapes():
    net = _make_net(19, 11, 5, 7, NeuronModel.LIF, ResetMode.SUBTRACT)
    qparams = _quantized(net)
    rec = run_int(net, qparams, _spikes(net, 7, 3), backend="event")
    stats = rec.event_stats()
    assert stats["input_events_per_step"].shape == (7,)
    assert [e.shape for e in stats["layer_events_per_step"]] == [(7,), (7,)]
    total = rec.total_events_per_image()
    assert total == pytest.approx(
        stats["input_events_per_step"].sum()
        + sum(e.sum() for e in stats["layer_events_per_step"])
    )


def test_explore_snn_event_aware_perf_cost():
    """c_perf > 0 adds the event-driven latency/energy term; serial and
    population modes score shared candidates identically on acc AND perf."""
    from repro.core.flexplorer import annealer as annealer_lib
    from repro.core.flexplorer import cost as cost_lib
    from repro.core.flexplorer.explorer import EvalSpec, SearchSpec, SNNSearchSpace, explore_snn

    net = _make_net(32, 16, 4, 6, NeuronModel.LIF, ResetMode.SUBTRACT)
    params = init_float_params(jax.random.PRNGKey(1), net)
    ds = mnist_like(n=64, T=6, seed=6)
    ds.spikes = ds.spikes[:, :, : net.n_in]
    ds.labels = ds.labels % 4
    space = SNNSearchSpace(ff_bits=(4, 6, 8), leak_bits=(3, 8))
    cfg = annealer_lib.AnnealConfig(t_start=1.0, t_min=0.2, alpha=0.5, seed=0)
    w = cost_lib.CostWeights(c_hw=0.4, c_acc=0.4, c_perf=0.2)
    ev = EvalSpec(batch=32)
    serial = explore_snn(
        net, params, ds, search=SearchSpec(space=space, config=cfg, weights=w), evaluate=ev
    )
    pop = explore_snn(
        net, params, ds,
        search=SearchSpec(space=space, config=cfg, weights=w, population=4), evaluate=ev,
    )
    assert serial.anneal.best_breakdown["perf_cost"] > 0
    shared = serial.anneal.cache.keys() & pop.anneal.cache.keys()
    assert shared
    for c in shared:
        assert serial.anneal.cache[c][3] == pop.anneal.cache[c][3]  # accuracy
        assert serial.anneal.cache[c][4] == pytest.approx(pop.anneal.cache[c][4])  # perf
