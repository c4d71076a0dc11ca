"""Layers wider than one core: the split is a mapping of cores, not a second numerics.

A layer with more than 256 input addresses maps onto several fan-in cores
whose int32 partial currents merge before phase A's one saturation.  Every
path (the backends, the population sweep, the serving lanes) keeps computing
the layer as one product; these tests hold each, bit for bit, to the plain
reference ``perfbench/references/lif_int.py`` and to the layer computed core
by core.  The per-event model (``SplitEventLayer``: 8-bit-address cores plus
the merge) is held to the vectorised step, and ``hw_model`` charges each core
at its own slice while a net that fits single cores costs what it did.
"""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hw_model, lowering
from repro.core.backend import EventBackend, FusedBackend
from repro.core.events import EventDrivenCore, SplitEventLayer
from repro.core.network import NetworkConfig, init_float_params, quantize_params, run_int
from repro.core.snn_layer import (
    CORE_WIDTH,
    IntLayerParams,
    LayerConfig,
    NeuronModel,
    ResetMode,
    Topology,
    int_layer_init,
    int_layer_step,
    int_layer_window_from_currents,
)
from repro.data.snn_datasets import SpikeDataset
from repro.serve.snn_engine import SNNRequest, SNNServeEngine
from repro.snn.train import eval_int, eval_int_population

ROOT = pathlib.Path(__file__).resolve().parent.parent
T, BATCH, HIDDEN, N_OUT = 8, 6, 24, 5


def _lif_int():
    path = ROOT / "perfbench" / "references" / "lif_int.py"
    spec = importlib.util.spec_from_file_location("lif_int_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


LIF_INT = _lif_int()


def _net(n_in, w_bits=8):
    syn = dict(neuron=NeuronModel.SYNAPTIC, reset=ResetMode.ZERO, beta=0.9048, alpha=0.8187)
    return NetworkConfig(
        layers=(
            LayerConfig(n_in=n_in, n_out=HIDDEN, topology=Topology.ATA_T, w_bits=w_bits,
                        w_rec_bits=w_bits, **syn),
            LayerConfig(n_in=HIDDEN, n_out=N_OUT, w_bits=w_bits, w_rec_bits=w_bits, **syn),
        ),
        n_steps=T,
        name=f"split-{n_in}",
    )  # fmt: skip


def _layer_dicts(net):
    keys = ("n_in", "n_out", "neuron", "topology", "reset", "w_bits", "w_rec_bits", "u_bits",
            "i_bits", "leak_bits", "beta", "alpha", "threshold")  # fmt: skip
    out = []
    for lc in net.layers:
        d = {k: getattr(lc, k) for k in keys}
        d.update(neuron=lc.neuron.value, topology=lc.topology.value, reset=lc.reset.value)
        out.append(d)
    return out


def _weights(q):
    return [{"w_ff": p.w_ff, "w_rec": p.w_rec, "theta_q": p.theta_q} for p in q]


def _raster(n_in, seed=1, rate=0.08, batch=BATCH):
    u = jax.random.uniform(jax.random.PRNGKey(seed), (T, batch, n_in))
    return (u < rate).astype(jnp.int32)


def _core_by_core(net, qparams, x):
    """Each layer as its physical cores: per neuron slice, the fan-in cores' partial
    currents merged by int32 addition, then the shared step scan."""
    emitted = []
    for cfg, p in zip(net.layers, qparams):
        currents = jnp.zeros((x.shape[0], x.shape[1], cfg.n_out), jnp.int32)
        for rows, cols, _ in cfg.core_slices():
            part = jnp.einsum("tbi,io->tbo", x[..., rows.start : rows.stop],
                              p.w_ff[rows.start : rows.stop, cols.start : cols.stop])  # fmt: skip
            currents = currents.at[..., cols.start : cols.stop].add(part)
        x = int_layer_window_from_currents(cfg, p, currents)
        emitted.append(jnp.sum(x, axis=-1))
    return jnp.sum(x, axis=0), jnp.stack(emitted, axis=1)  # [B, C], [T, L, B]


@pytest.fixture(scope="module", params=[300, 700], ids=["n_in300", "n_in700"])
def split_case(request):
    n_in = request.param
    net = _net(n_in)
    qparams, _ = quantize_params(net, init_float_params(jax.random.PRNGKey(3), net))
    x = _raster(n_in)
    counts, emitted = LIF_INT.simulate(_layer_dicts(net), _weights(qparams), x)
    assert int(np.asarray(emitted).sum()) > 0, "the case must spike to test anything"
    return net, qparams, x, np.asarray(counts), np.asarray(emitted)


# -- mapping ---------------------------------------------------------------


def test_a_700_wide_layer_maps_onto_three_fan_in_cores():
    net = _net(700)
    l0 = net.layers[0]
    assert (l0.fan_in_cores, l0.neuron_cores, l0.n_cores) == (3, 1, 3)
    slices = l0.core_slices()
    assert [(r.start, r.stop, c.start, c.stop) for r, c, _ in slices] == [
        (0, 256, 0, HIDDEN), (256, 512, 0, HIDDEN), (512, 700, 0, HIDDEN)
    ]  # fmt: skip
    assert [core.topology for _, _, core in slices] == [Topology.ATA_T, Topology.FF, Topology.FF]
    assert all(core.n_in <= CORE_WIDTH and core.n_out <= CORE_WIDTH for _, _, core in slices)
    assert net.n_cores == 4
    wide = LayerConfig(n_in=300, n_out=300, topology=Topology.ATA_F)
    assert wide.n_cores == 4 and [c.n_out for _, _, c in wide.core_slices()] == [256, 256, 44, 44]
    one = LayerConfig(n_in=256, n_out=200)
    assert one.core_slices() == [(range(256), range(200), one)]


@pytest.mark.parametrize("n_out", [257, 512])
def test_an_ata_t_layer_wider_than_one_core_is_rejected(n_out):
    with pytest.raises(ValueError, match="ATA-T layer's dense recurrence must stay inside one"):
        LayerConfig(n_in=64, n_out=n_out, topology=Topology.ATA_T)
    LayerConfig(n_in=64, n_out=n_out, topology=Topology.ATA_F)  # self-feedback splits fine


# -- vectorised paths against the reference and the core-by-core layer ------------


BACKENDS = {
    "reference": "reference",
    "fused": "fused",
    "fused-pallas": FusedBackend(use_pallas=True, interpret=True),
    "event": "event",
    "event-gather": EventBackend("gather"),
    "event-pallas": EventBackend("pallas"),
}


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_backends_equal_lif_int_and_the_core_by_core_layer(split_case, backend):
    net, qparams, x, ref_counts, ref_emitted = split_case
    rec = run_int(net, qparams, x, backend=BACKENDS[backend])
    np.testing.assert_array_equal(np.asarray(rec.spike_counts), ref_counts)
    emitted = np.stack([np.asarray(s) for s in rec.layer_spikes], axis=1)  # [T, L, B]
    np.testing.assert_array_equal(emitted, ref_emitted)
    cores_counts, cores_emitted = _core_by_core(net, qparams, x)
    np.testing.assert_array_equal(np.asarray(cores_counts), ref_counts)
    np.testing.assert_array_equal(np.asarray(cores_emitted), ref_emitted)


def test_population_sweep_equals_serial_eval_and_lif_int(split_case):
    net, _, x, _, _ = split_case
    params = init_float_params(jax.random.PRNGKey(3), net)
    cands = [net.replace_precisions(w_bits=b, w_rec_bits=r, leak_bits=lk)
             for b, r, lk in [(4, 8, 3), (8, 8, 8), (12, 4, 8), (16, 16, 3)]]  # fmt: skip
    qps = [quantize_params(c, params)[0] for c in cands]
    spikes = np.asarray(x).transpose(1, 0, 2).astype(np.uint8)  # [B, T, n_in]
    labels = np.arange(BATCH, dtype=np.int32) % N_OUT
    ds = SpikeDataset(spikes, labels, N_OUT, "split")
    accs, stats = eval_int_population(net, cands, qps, ds, batch_size=4, return_stats=True)
    for j, (c, q) in enumerate(zip(cands, qps)):
        acc, st = eval_int(c, q, ds, batch_size=4, return_stats=True)
        assert accs[j] == acc
        for a, b in zip(stats[j]["layer_events_per_step"], st["layer_events_per_step"]):
            np.testing.assert_array_equal(a, b)
        counts, emitted = LIF_INT.simulate(_layer_dicts(c), _weights(q), x)
        pred = np.argmax(np.asarray(counts), axis=-1)
        assert accs[j] == np.mean(pred == labels)
        ref_ev = np.asarray(emitted).mean(axis=2)  # [T, L]
        np.testing.assert_allclose(np.stack(stats[j]["layer_events_per_step"], 1), ref_ev)


def test_serving_lanes_equal_lif_int_and_serial_run_int(split_case):
    net, qparams, x, ref_counts, ref_emitted = split_case
    engine = SNNServeEngine(net, qparams, max_batch=4, tick_stride=4)
    rasters = np.asarray(x).transpose(1, 0, 2)
    done = engine.run([SNNRequest(uid=i, raster=r) for i, r in enumerate(rasters)])
    assert len(done) == BATCH
    for req in done:
        assert req.status == "completed"
        np.testing.assert_array_equal(req.spike_counts, ref_counts[req.uid])
        got = np.stack(req.event_stats["layer_events_per_step"], axis=1)  # [T, L]
        np.testing.assert_array_equal(got, ref_emitted[:, :, req.uid])
        serial = run_int(net, qparams, jnp.asarray(rasters[req.uid])[:, None, :])
        np.testing.assert_array_equal(req.spike_counts, np.asarray(serial.spike_counts)[0])


# -- the per-event model -------------------------------------------------------


PER_EVENT = [
    LayerConfig(n_in=700, n_out=20, neuron=NeuronModel.SYNAPTIC, topology=Topology.ATA_T,
                reset=ResetMode.ZERO, w_bits=8, w_rec_bits=8, beta=0.9048, alpha=0.8187),
    LayerConfig(n_in=300, n_out=300, neuron=NeuronModel.LIF, topology=Topology.ATA_F, w_bits=8),
    LayerConfig(n_in=600, n_out=40, neuron=NeuronModel.SYNAPTIC, reset=ResetMode.ZERO),
]  # fmt: skip


@pytest.mark.parametrize("cfg", PER_EVENT, ids=["syn-atat-700x20", "lif-ataf-300x300",
                                                "syn-ff-600x40"])  # fmt: skip
def test_per_event_split_equals_the_vectorised_step_and_the_unsplit_core(cfg):
    rng = np.random.default_rng(0)
    w_ff = rng.integers(-20, 21, (cfg.n_in, cfg.n_out)).astype(np.int32)
    if cfg.topology == Topology.ATA_T:
        w_rec = rng.integers(-20, 21, (cfg.n_out, cfg.n_out)).astype(np.int32)
    elif cfg.topology == Topology.ATA_F:
        w_rec = np.int32(5)
    else:
        w_rec = np.zeros((0,), np.int32)
    theta = 200
    params = IntLayerParams(jnp.asarray(w_ff), jnp.asarray(w_rec), jnp.int32(theta))
    split = SplitEventLayer(cfg, w_ff, w_rec, theta)
    assert len(split.cores) == cfg.n_cores
    assert all(c.cfg.n_in <= CORE_WIDTH and c.cfg.n_out <= CORE_WIDTH for c in split.cores)
    whole = EventDrivenCore(cfg, w_ff, w_rec, theta)  # one core with wide addresses
    state = int_layer_init(cfg, 1)
    fired_total = 0
    for _ in range(10):
        s = (rng.random(cfg.n_in) < 0.1).astype(np.int32)
        src = [int(a) for a in np.nonzero(s)[0]]
        state, spk = int_layer_step(cfg, params, state, jnp.asarray(s)[None])
        want = np.flatnonzero(np.asarray(spk[0])).tolist()
        assert sorted(split.step(src)) == want
        assert sorted(whole.step(src)) == want
        fired_total += len(want)
    assert fired_total > 0
    # the same sweeps, plus one merge visit per neuron and extra fan-in core
    merges = 10 * (cfg.fan_in_cores - 1) * cfg.n_out
    assert split.cycle_count == whole.cycle_count + merges


# -- hardware model --------------------------------------------------------------


def test_hw_model_charges_a_700_input_layer_three_fan_in_cores():
    net = _net(700)
    l0, l1 = net.layers
    cores = [core for _, _, core in l0.core_slices()]
    assert [c.n_in for c in cores] == [256, 256, 188]
    want = hw_model.core_resources(l1)
    for c in cores:
        want = want + hw_model.core_resources(c)
    assert hw_model.network_resources(net) == want
    # fan-in cores integrate in parallel; the state core merges the other two
    in_ev, rec_ev = 30.0, 5.0
    cycles = in_ev * (256 / 700) * HIDDEN + 2 * HIDDEN + rec_ev * HIDDEN + HIDDEN + 100
    assert hw_model.step_cycles(l0, in_ev, rec_ev) == pytest.approx(cycles, rel=1e-12)
    traffic = hw_model.EventTraffic.constant_rate(T, in_ev, (rec_ev, 1.0))
    assert hw_model.design_point(net, traffic).latency_s > 0


_SMALL_NETS = {
    "mnist": NetworkConfig((LayerConfig(256, 128, w_bits=6), LayerConfig(128, 10, w_bits=6)), 25),
    "dvs": NetworkConfig((LayerConfig(256, 200, topology=Topology.ATA_F, w_bits=8, w_rec_bits=8),
                          LayerConfig(200, 11, w_bits=8)), 70),
    "syn": NetworkConfig((LayerConfig(140, 200, neuron=NeuronModel.SYNAPTIC, topology=Topology.ATA_T,
                                      reset=ResetMode.ZERO, w_bits=8, w_rec_bits=8),
                          LayerConfig(200, 20, neuron=NeuronModel.SYNAPTIC)), 100),
}  # fmt: skip

# (lut, ff, bram, latency_s, power_w, energy_j, events, bw_bytes_s) before splits existed
_SMALL_NUMBERS = {
    "mnist": (1304.0, 913.0, 7, 0.0003676, 0.1113128321341256, 4.0918597092504566e-05,
              256.25, 71077597.93253537),
    "dvs": (1408.0, 945.0, 17, 0.0015269916666666664, 0.12171184238225233,
            0.0001858529690523461, 717.5, 85492935.45588003),
    "syn": (1864.0, 1265.0, 35, 0.0027483333333333335, 0.14275399193572527,
            0.00039233555450335166, 1025.0000000000002, 113896300.78835654),
}  # fmt: skip


@pytest.mark.parametrize("name", list(_SMALL_NETS))
def test_hw_model_numbers_of_single_core_layers_are_unchanged(name):
    net = _SMALL_NETS[name]
    traffic = hw_model.EventTraffic(
        np.linspace(1, 9, net.n_steps),
        tuple(np.linspace(0.5, 3, net.n_steps) * (i + 1) for i in range(len(net.layers))),
    )
    r = hw_model.network_resources(net)
    dp = hw_model.design_point(net, traffic)
    got = (r.lut, r.ff, r.bram, dp.latency_s, dp.power_w, dp.energy_per_image_j,
           dp.events_per_image, dp.bw_demand_bytes_s)  # fmt: skip
    assert got == _SMALL_NUMBERS[name]


# -- lowering and device-op names ---------------------------------------------------


def test_f32_lowering_is_refused_for_a_700_wide_product_at_16_bits(monkeypatch):
    assert not lowering.f32_exact(16, 1, 700)  # 32768 * 700 > 2**24 on any platform
    monkeypatch.setattr(lowering, "on_tpu", lambda: True)
    assert not lowering.f32_exact(16, 1, 700)
    assert not lowering.f32_exact(12, 1, 700)  # 2048 > bf16's 256
    assert lowering.f32_exact(8, 1, 700)


def test_device_ops_carry_the_ff_and_recurrent_scopes():
    from repro.core.backend import batched_lane_init, batched_lane_window
    from repro.snn.train import _population_fwd

    net = _net(300)
    qparams, _ = quantize_params(net, init_float_params(jax.random.PRNGKey(3), net))
    x = _raster(300)
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), list(qparams))
    regs = jnp.zeros((2, len(net.layers)), jnp.int32)
    pop = _population_fwd.lower(net, stacked, regs, regs, x).compile().as_text()
    states = batched_lane_init(net, BATCH)
    lanes = batched_lane_window.lower(net, qparams, states, x, jnp.zeros(BATCH, bool))
    lanes = lanes.compile().as_text()
    for text in (pop, lanes):
        assert "neura.core.ff" in text and "neura.core.recurrent" in text
    ff_only = NetworkConfig((LayerConfig(300, 20), LayerConfig(20, 5)), T)
    q_ff, _ = quantize_params(ff_only, init_float_params(jax.random.PRNGKey(0), ff_only))
    fwd = jax.jit(lambda s: run_int(ff_only, q_ff, s).spike_counts)
    text = fwd.lower(x).compile().as_text()
    assert "neura.core.ff" in text and "neura.core.recurrent" not in text
