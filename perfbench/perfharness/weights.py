"""Integer weights from ``--seed``, made on the device in one jitted call.

The float draw follows the convention a user's untrained network starts
from (uniform within ``gain/sqrt(fan_in)``, a shared ATA-F self-weight of
0.1; the configuration's ``init`` sets the gain so that every core fires
at a rate a trained network would, rather than the output core staying
silent), and the quantization copies the deployment rule of the paper's
Flex-plorer: one scale per core, the tightest that fits the feed-forward
and recurrent weights in their widths and keeps the rescaled threshold at
half the membrane register; round half to even, clip onto the signed grid.
The program and the reference are both handed these integers, so neither
takes the other's weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def int_max(bits: int) -> int:
    return (1 << (bits - 1)) - 1


def _freeze(layers: list[dict]) -> tuple:
    return tuple(tuple(sorted(layer.items())) for layer in layers)


@functools.partial(jax.jit, static_argnames=("frozen", "widths", "gain", "self_weight"))
def _make(key, frozen: tuple, widths: tuple, gain: float, self_weight: float):
    layers = [dict(items) for items in frozen]
    floats = []
    for layer in layers:
        key, k_ff, k_rec = jax.random.split(key, 3)
        n_in, n_out = layer["n_in"], layer["n_out"]
        lim = gain / np.sqrt(n_in)
        w_ff = jax.random.uniform(k_ff, (n_in, n_out), jnp.float32, -lim, lim)
        if layer["topology"] == "ata_t":
            rlim = 1.0 / np.sqrt(n_out)
            w_rec = jax.random.uniform(k_rec, (n_out, n_out), jnp.float32, -rlim, rlim)
        elif layer["topology"] == "ata_f":
            w_rec = jnp.asarray(self_weight, jnp.float32)
        else:
            w_rec = jnp.zeros((0,), jnp.float32)
        floats.append((w_ff, w_rec))
    out = []
    for w_bits, rec_bits in widths:
        cand = []
        for layer, (w_ff, w_rec) in zip(layers, floats):
            theta = jnp.float32(layer["threshold"])
            scale = int_max(w_bits) / jnp.maximum(jnp.max(jnp.abs(w_ff)), 1e-12)
            if layer["topology"] != "ff":
                rec_max = jnp.maximum(jnp.max(jnp.abs(w_rec)), 1e-12)
                scale = jnp.minimum(scale, int_max(rec_bits) / rec_max)
            scale = jnp.minimum(scale, jnp.float32(0.5 * int_max(layer["u_bits"])) / theta)
            q_ff = jnp.clip(jnp.round(w_ff * scale), -int_max(w_bits) - 1, int_max(w_bits))
            q_rec = jnp.clip(jnp.round(w_rec * scale), -int_max(rec_bits) - 1, int_max(rec_bits))
            cand.append(
                {
                    "w_ff": q_ff.astype(jnp.int32),
                    "w_rec": q_rec.astype(jnp.int32),
                    "theta_q": jnp.round(theta * scale).astype(jnp.int32),
                }
            )
        out.append(cand)
    return out


def make_weights(config: dict, key, widths=None) -> list[list[dict]]:
    """Integer weights for each ``(w_bits, w_rec_bits)`` in ``widths``.

    ``widths=None`` gives the configuration's own widths (one entry, taken
    from its first layer; every layer of a configuration shares them). Each
    entry is a list over layers of ``{"w_ff", "w_rec", "theta_q"}`` int32
    device arrays, all quantized from one float draw.
    """
    layers = config["layers"]
    if widths is None:
        widths = [(layers[0]["w_bits"], layers[0]["w_rec_bits"])]
    init = config["init"]
    return _make(
        key,
        _freeze(layers),
        tuple(tuple(w) for w in widths),
        float(init["ff_gain"]),
        float(init["ata_f_self_weight"]),
    )
