"""The plain reference against the program, and its int4 control against the reference.

The reference imports nothing of the program; here, on the CPU at small
sizes, it must agree with the program's own serial simulation bit for bit
on every neuron model, topology and reset the configurations can state, and
the int4 control must not.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lif_int
from perfharness import program, traffic, weights
from repro.core.network import run_int

SEED = 2**31 + 7


def _config(neuron="lif", topology="ff", reset="subtract", w_bits=6, leak_bits=8, hidden=24):
    base = {"neuron": neuron, "topology": "ff", "reset": reset, "w_bits": w_bits,
            "w_rec_bits": w_bits, "u_bits": 16, "i_bits": 16, "leak_bits": leak_bits,
            "beta": 0.95, "alpha": 0.9, "threshold": 1.0}  # fmt: skip
    return {
        "name": "tiny",
        "n_steps": 12,
        "init": {"ff_gain": 2.0, "ata_f_self_weight": 0.1},
        "layers": [
            dict(base, n_in=32, n_out=hidden, topology=topology),
            dict(base, n_in=hidden, n_out=5),
        ],
    }


def _both(config, raster):
    w = weights.make_weights(config, traffic.seed_key(SEED, 3))[0]
    rec = run_int(program.network(config), program.qparams(w), raster)
    ref = lif_int.simulate(config["layers"], w, raster)
    return w, rec, ref


VARIANTS = [
    dict(),
    dict(topology="ata_f"),
    dict(topology="ata_t"),
    dict(neuron="synaptic"),
    dict(neuron="if"),
    dict(reset="zero"),
    dict(leak_bits=3),
    dict(w_bits=12, topology="ata_f"),
]


def _id(variant):
    return ",".join(f"{k}={x}" for k, x in variant.items()) or "lif-ff"


@pytest.mark.parametrize("variant", VARIANTS, ids=_id)
def test_reference_equals_the_program(variant):
    config = _config(**variant)
    raster, _ = traffic.rasters({"family": "bernoulli", "density": 0.2}, 40, 12, 32, SEED)
    x = jnp.asarray(raster.transpose(1, 0, 2))
    _, rec, (counts, events) = _both(config, x)
    assert np.array_equal(np.asarray(rec.spike_counts), np.asarray(counts))
    for l in range(2):
        assert np.array_equal(np.asarray(rec.layer_spikes[l]), np.asarray(events[:, l]))
    assert np.asarray(events).sum() > 0  # the check is not of a silent network


@pytest.mark.parametrize("variant", [dict(), dict(topology="ata_f", w_bits=8)])
def test_int4_control_differs(variant):
    config = _config(**variant)
    raster, _ = traffic.rasters({"family": "bernoulli", "density": 0.2}, 40, 12, 32, SEED)
    x = jnp.asarray(raster.transpose(1, 0, 2))
    w, _, (counts, events) = _both(config, x)
    c4, e4 = lif_int.simulate(config["layers"], lif_int.control_weights(config["layers"], w), x)
    wrong = (np.asarray(c4) != np.asarray(counts)).any(axis=1) | (
        np.asarray(e4) != np.asarray(events)
    ).any(axis=(0, 1))
    assert wrong.mean() > 0.5


def test_decay_code_grid():
    assert lif_int.decay_code(0.95, 8) == (243, False)
    assert lif_int.decay_code(0.95, 3) == (0, True)  # rounds to 1 on a 32/256 grid: bypass
    assert lif_int.decay_code(0.5, 1) == (128, False)
    x = jnp.asarray([-7, -1, 0, 1, 255], jnp.int32)
    # k = 192 = 1/2 + 1/4: floor shifts, as the hardware's arithmetic shifts
    want = [-4 + -2, -1 + -1, 0, 0, 127 + 63]
    assert np.array_equal(np.asarray(lif_int._cg(x, 192, False)), want)
    jax.clear_caches()
