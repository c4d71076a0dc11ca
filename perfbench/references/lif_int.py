"""Plain reference of the Flexi-NeurA integer core stack (arXiv 2602.18140, sec. 4.1).

Written from the paper's description, in ``jax.numpy`` int32, with no
kernels, lanes or batching tricks; it imports nothing of the program. One
time step, for each core in order:

Phase A (integration): the incoming spikes add their weight rows,
``acc = s_in @ W_ff``; recurrent cores add their own spikes of the previous
step (ATA-T: ``prev @ W_rec``; ATA-F: ``prev * w_self``). LIF/IF commit
``U = sat(U + acc)``, the Synaptic model ``I = sat(I + acc)``.

Phase B (leak and fire): ``u = sat(U + I)`` for Synaptic, else ``U``. A
neuron with ``u >= theta`` spikes and resets (to zero, or by subtracting
``theta``); the others leak, ``U = sat(CG(u))``. Synaptic cores then decay
``I = sat(CG_alpha(I))``. The spikes go to the next core at the same step.

The coefficient generator realises ``x * k / 256`` as the sum of the
arithmetic right shifts ``x >> s`` for each set bit of ``k`` (bit ``8 - s``),
with ``k`` the decay factor rounded to the grid that ``leak_bits`` taps
allow; a factor that rounds to 1 bypasses the generator. ``sat`` clamps
to the signed register width.

The feed-forward products are exact int32 dots. ``control_weights`` holds
the same weights at 4 significant bits: the int4 computation that the
comparison must refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def decay_code(factor: float, leak_bits: int) -> tuple[int, bool]:
    """``(k, bypass)``: the factor on the ``2**(8 - leak_bits) / 256`` grid."""
    step = 1 << (8 - leak_bits)
    k = int(round(factor * 256.0 / step)) * step
    return (0, True) if k >= 256 else (k, False)


def _cg(x, k: int, bypass: bool):
    if bypass:
        return x
    acc = jnp.zeros_like(x)
    for s in range(1, 9):
        if (k >> (8 - s)) & 1:
            acc = acc + (x >> s)
    return acc


def _sat(x, bits: int):
    return jnp.clip(x, -(1 << (bits - 1)), (1 << (bits - 1)) - 1)


_DYNAMICS = (
    "n_out", "neuron", "topology", "reset", "u_bits", "i_bits", "leak_bits", "beta", "alpha",
)  # fmt: skip


def _freeze(layers) -> tuple:
    """The static part of a core stack: what the dynamics read, and no width."""
    return tuple(tuple((k, layer[k]) for k in _DYNAMICS) for layer in layers)


@functools.partial(jax.jit, static_argnames=("frozen",))
def _simulate(frozen, weights, raster):
    layers = [dict(items) for items in frozen]
    B = raster.shape[1]
    state0 = [
        tuple(jnp.zeros((B, c["n_out"]), jnp.int32) for _ in range(3)) for c in layers
    ]  # (U, I, previous spikes) per core

    def step(states, s_t):
        x = s_t.astype(jnp.int32)
        new, emitted = [], []
        for c, w, (u, i, prev) in zip(layers, weights, states):
            acc = jnp.dot(x, w["w_ff"], preferred_element_type=jnp.int32)
            if c["topology"] == "ata_t":
                acc = acc + jnp.dot(prev, w["w_rec"], preferred_element_type=jnp.int32)
            elif c["topology"] == "ata_f":
                acc = acc + prev * w["w_rec"]
            synaptic = c["neuron"] == "synaptic"
            if synaptic:
                i = _sat(i + acc, c["i_bits"])
                v = _sat(u + i, c["u_bits"])
            else:
                u = _sat(u + acc, c["u_bits"])
                v = u
            theta = w["theta_q"]
            spk = (v >= theta).astype(jnp.int32)
            reset = jnp.zeros_like(v) if c["reset"] == "zero" else _sat(v - theta, c["u_bits"])
            beta = 1.0 if c["neuron"] == "if" else c["beta"]
            leak = _sat(_cg(v, *decay_code(beta, c["leak_bits"])), c["u_bits"])
            u = jnp.where(spk == 1, reset, leak)
            if synaptic:
                i = _sat(_cg(i, *decay_code(c["alpha"], c["leak_bits"])), c["i_bits"])
            new.append((u, i, spk))
            emitted.append(jnp.sum(spk, axis=-1))
            x = spk
        return new, (x, jnp.stack(emitted))

    _, (out, emitted) = jax.lax.scan(step, state0, raster)
    return jnp.sum(out, axis=0), emitted  # [B, n_classes], [T, L, B]


def simulate(layers: list[dict], weights: list[dict], raster):
    """Run the core stack over ``raster`` int ``[T, B, n_in]``.

    ``layers`` are the configuration file's layer entries, ``weights`` a
    list over layers of ``{"w_ff", "w_rec", "theta_q"}`` int32 arrays.
    Returns output spike counts ``[B, n_classes]`` and each core's emitted
    spikes per step ``[T, n_layers, B]``, as int32 device arrays.
    """
    return _simulate(_freeze(layers), weights, raster)


def control_weights(layers: list[dict], weights: list[dict]) -> list[dict]:
    """The same weights held at int4: the low ``w_bits - 4`` bits dropped."""
    out = []
    for c, w in zip(layers, weights):
        s_ff = max(0, c["w_bits"] - 4)
        s_rec = max(0, c["w_rec_bits"] - 4)
        out.append(
            {
                "w_ff": (w["w_ff"] >> s_ff) << s_ff,
                "w_rec": (w["w_rec"] >> s_rec) << s_rec,
                "theta_q": w["theta_q"],
            }
        )
    return out
