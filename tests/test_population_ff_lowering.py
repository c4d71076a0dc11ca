"""The population sweep's feed-forward products in exact int8 digit planes.

``lowering.population_currents`` takes weights of up to 16 bits into two int8
planes (``lowering.weight_planes``), multiplies each on the MXU's int8 path and recombines in int32; it
must equal XLA's int32 dot bit for bit, overflow included, wherever the
inputs fit int8.  ``eval_int_population`` tests a set's range once and cuts
it as int8 where it fits; ``lowering.population_feed`` reads the lowering
off the input's dtype.  A population that mixes 4- and 16-bit candidates
must score exactly as ``eval_int`` does per candidate, on binary and graded
rasters, over a first layer split across three fan-in cores.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend, lowering
from repro.core.fixed_point import int_max, int_min
from repro.core.network import NetworkConfig, init_float_params, quantize_params, run_int
from repro.core.snn_layer import LayerConfig, NeuronModel, ResetMode, Topology
from repro.data.snn_datasets import SpikeDataset
from repro.snn import train

W_BITS = (2, 4, 8, 9, 12, 16)
ROWS, N_OUT = 6, 24
T, N_SAMPLES, BATCH = 6, 20, 8  # three batches, the last one partial


def _weights(w_bits, fill, n_in, seed=0):
    if fill == "int_min":
        return np.full((n_in, N_OUT), int_min(w_bits), np.int32)
    if fill == "int_max":
        return np.full((n_in, N_OUT), int_max(w_bits), np.int32)
    rng = np.random.default_rng(seed)
    return rng.integers(int_min(w_bits), int_max(w_bits) + 1, (n_in, N_OUT)).astype(np.int32)


def _inputs(max_val, shape, seed=1):
    """uint8 spikes whose largest value is ``max_val`` (binary at 1)."""
    rng = np.random.default_rng(seed)
    x = rng.integers(0, max_val + 1, shape).astype(np.uint8)
    x.reshape(-1)[0] = max_val
    return x


def _plane_currents(x, w):
    """The int8-plane product of ``x`` and ``w``, whatever ``x``'s dtype."""
    return lowering.population_currents(jnp.asarray(x), lowering.weight_planes(jnp.asarray(w)))


def _int32_dot(x, w):
    return np.asarray(jnp.einsum("bi,io->bo", jnp.asarray(x, jnp.int32), jnp.asarray(w)))


# -- the product ---------------------------------------------------------------


@pytest.mark.parametrize("n_in", [256, 700])
@pytest.mark.parametrize("max_val", [1, 127])
@pytest.mark.parametrize("fill", ["int_min", "int_max", "random"])
@pytest.mark.parametrize("w_bits", W_BITS)
def test_plane_product_equals_the_int32_dot(w_bits, fill, max_val, n_in):
    x, w = _inputs(max_val, (ROWS, n_in)), _weights(w_bits, fill, n_in)
    assert bool(lowering.fits_int8(jnp.asarray(x)))
    got = _plane_currents(x, w)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), _int32_dot(x, w))


def test_plane_product_wraps_as_the_int32_dot_does():
    x = np.full((ROWS, 700), 127, np.uint8)
    w = np.full((700, N_OUT), int_min(16), np.int32)
    exact = 700 * 127 * int_min(16)
    assert exact < -(2**31)  # the sum overflows int32
    got = np.asarray(_plane_currents(x, w))
    np.testing.assert_array_equal(got, _int32_dot(x, w))
    assert got[0, 0] == np.int64(exact).astype(np.int32)


def test_the_planes_hold_every_candidate_width():
    """Two planes cover the widest weight any ``LayerConfig`` allows."""
    with pytest.raises(ValueError, match="w_bits"):
        LayerConfig(n_in=4, n_out=4, w_bits=17)
    w = np.asarray([[int_min(16), -1, 0, 255, 256, int_max(16)]], np.int32)
    hi, lo = w >> 8, (w & 0xFF) - 128
    assert hi.min() >= -128 and hi.max() <= 127 and lo.min() >= -128 and lo.max() <= 127
    np.testing.assert_array_equal(256 * hi + lo + 128, w)


# -- the feed decision ---------------------------------------------------------


@pytest.mark.parametrize(
    "max_val,want",
    [
        (1, lowering.INT8_PLANES),
        (127, lowering.INT8_PLANES),
        (128, lowering.XLA_INT32),
        (255, lowering.XLA_INT32),
    ],
)
@pytest.mark.parametrize("w_bits", W_BITS)
def test_feed_decision(w_bits, max_val, want):
    """Every width fits the two planes, so the inputs' range alone decides the feed."""
    x, w = _inputs(max_val, (ROWS, 256)), _weights(w_bits, "random", 256)
    fits = bool(lowering.fits_int8(jnp.asarray(x)))
    fed = jnp.asarray(x, jnp.int8 if fits else x.dtype)  # as eval_int_population cuts the set
    assert lowering.population_feed(fed.dtype) == want
    got = lowering.population_currents(fed, lowering.population_weights(jnp.asarray(w), fed.dtype))
    np.testing.assert_array_equal(np.asarray(got), _int32_dot(x, w))
    if want == lowering.XLA_INT32 and w_bits > 2:
        # the int8 cast wraps 128..255: this is why such a set takes the int32 dot
        planes = np.asarray(_plane_currents(x, w))
        assert not np.array_equal(planes, _int32_dot(x, w))


@pytest.mark.parametrize(
    "dtype,want",
    [
        (np.int8, lowering.INT8_PLANES),
        (np.bool_, lowering.XLA_INT32),
        (np.uint8, lowering.XLA_INT32),
        (np.int32, lowering.XLA_INT32),
        (np.float32, lowering.XLA_INT32),
    ],
)
def test_population_feed_of_a_dtype(dtype, want):
    assert lowering.population_feed(np.dtype(dtype)) == want
    w = lowering.population_weights(jnp.zeros((4, 3), jnp.int32), np.dtype(dtype))
    assert isinstance(w, lowering.WeightPlanes) == (want == lowering.INT8_PLANES)


@pytest.mark.parametrize(
    "dtype,max_val",
    [(np.int8, 1), (np.int8, 127), (np.uint8, 1), (np.uint8, 255), (np.int32, 255), (np.bool_, 1)],
)
def test_population_currents_equal_the_int32_dot(dtype, max_val):
    """Whichever lowering the dtype picks, the sweep's product is the int32 dot's."""
    x, w = _inputs(max_val, (ROWS, 700)), _weights(16, "random", 700)
    fed = jnp.asarray(x.astype(dtype))
    got = lowering.population_currents(fed, lowering.population_weights(jnp.asarray(w), fed.dtype))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), _int32_dot(x, w))


@pytest.mark.parametrize(
    "values,dtype,fits",
    [
        ([0, 127], np.uint8, True),
        ([0, 128], np.uint8, False),
        ([-128, 127], np.int32, True),
        ([-129, 0], np.int32, False),
        ([0, 255], np.int16, False),
        ([], np.int32, True),
        ([-200.0, 1.0], np.float32, False),
    ],
)
def test_fits_int8(values, dtype, fits):
    assert bool(lowering.fits_int8(jnp.asarray(np.asarray(values, dtype)))) == fits


# -- the population sweep ------------------------------------------------------


def _net(topology):
    """A Synaptic first layer of 700 inputs (three fan-in cores), then a LIF readout."""
    syn = dict(neuron=NeuronModel.SYNAPTIC, reset=ResetMode.ZERO, beta=0.9048, alpha=0.8187)
    return NetworkConfig(
        layers=(
            LayerConfig(n_in=700, n_out=16, topology=topology, w_bits=8, w_rec_bits=8, **syn),
            LayerConfig(n_in=16, n_out=5, neuron=NeuronModel.LIF, w_bits=8, beta=0.9),
        ),
        n_steps=T,
        name=f"planes-{topology.value}",
    )


def _population(topology, bits=((4, 8), (16, 16), (9, 4), (12, 12))):
    net = _net(topology)
    params = init_float_params(jax.random.PRNGKey(5), net)
    params = [p._replace(w_ff=4.0 * p.w_ff) for p in params]  # enough current to spike
    cands = [net.replace_precisions(w_bits=b, w_rec_bits=r) for b, r in bits]
    return net, cands, [quantize_params(c, params)[0] for c in cands]


def _dataset(max_val, seed=2):
    rng = np.random.default_rng(seed)
    spikes = (rng.random((N_SAMPLES, T, 700)) < 0.08).astype(np.uint8)
    if max_val > 1:
        spikes *= rng.integers(1, max_val + 1, spikes.shape).astype(np.uint8)
        spikes[0, 0, 0] = max_val
    labels = rng.integers(0, 5, N_SAMPLES).astype(np.int32)
    return SpikeDataset(spikes, labels, 5, "planes")


def _serial(cands, qps, ds):
    return [
        train.eval_int(c, qp, ds, batch_size=BATCH, return_stats=True) for c, qp in zip(cands, qps)
    ]


@pytest.mark.parametrize("max_val", [1, 127, 255])
@pytest.mark.parametrize("topology", [Topology.ATA_T, Topology.ATA_F])
def test_mixed_population_equals_eval_int(topology, max_val):
    net, cands, qps = _population(topology)
    ds = _dataset(max_val)
    fits = bool(train._fits_int8(jnp.asarray(ds.spikes)))
    assert fits == (max_val <= 127)
    windows = train._batch_windows(jnp.asarray(ds.spikes), BATCH, fits)[0]
    want = lowering.INT8_PLANES if fits else lowering.XLA_INT32
    assert [lowering.population_feed(b.dtype) for b in windows] == [want] * 3
    accs, stats = train.eval_int_population(
        net, cands, qps, ds, batch_size=BATCH, return_stats=True
    )
    for j, (acc, want) in enumerate(_serial(cands, qps, ds)):
        assert accs[j] == acc
        np.testing.assert_allclose(stats[j]["input_events_per_step"], want["input_events_per_step"])
        for got_l, want_l in zip(stats[j]["layer_events_per_step"], want["layer_events_per_step"]):
            np.testing.assert_allclose(got_l, want_l, rtol=0, atol=1e-6)
    hidden = [np.sum(s["layer_events_per_step"][0]) for s in stats]
    assert min(hidden) > 0, "every candidate must spike to test anything"


@pytest.mark.parametrize("dtype", [np.bool_, np.int8, np.uint8, np.int32])
def test_population_takes_either_feed_exactly(dtype):
    """Whatever the input's dtype, the sweep's counts and events equal the int32 dot's."""
    net, cands, qps = _population(Topology.ATA_T)
    x = jnp.asarray(_dataset(1).spikes[:BATCH].swapaxes(0, 1).astype(dtype))
    stacked, beta, alpha = backend.stack_population(cands, qps)
    got = backend.run_int_population(net, stacked, beta, alpha, x, return_events=True)
    for j, (c, qp) in enumerate(zip(cands, qps)):
        rec = run_int(c, qp, x.astype(jnp.int32))  # the reference backend's int32 dot
        np.testing.assert_array_equal(np.asarray(got[0][j]), np.asarray(rec.spike_counts))
        for l, want in enumerate(rec.layer_spikes):
            np.testing.assert_array_equal(np.asarray(got[1][j][:, l]), np.asarray(want))


def test_int4_control_still_differs():
    """Fed its weights at int4, the sweep no longer reads what ``eval_int`` does."""
    net, cands, qps = _population(Topology.ATA_T)
    ds = _dataset(1)
    control = [
        [p._replace(w_ff=p.w_ff >> (c.layers[i].w_bits - 4)) if c.layers[i].w_bits > 4 else p
         for i, p in enumerate(qp)]
        for c, qp in zip(cands, qps)
    ]  # fmt: skip
    _, stats = train.eval_int_population(
        net, cands, control, ds, batch_size=BATCH, return_stats=True
    )
    serial = [s["layer_events_per_step"][0] for _, s in _serial(cands, qps, ds)]
    differs = [
        not np.array_equal(stats[j]["layer_events_per_step"][0], serial[j])
        for j in range(len(cands))
        if cands[j].layers[0].w_bits > 4
    ]
    assert differs and all(differs)
