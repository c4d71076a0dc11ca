"""Pallas TPU kernel: fixed-capacity sparse event accumulation.

The event-driven integration phase of a Flexi-NeurA core is an AER
scatter: each input event (value, source channel) selects one quantized
weight row and adds ``value * row`` into the membrane-current accumulator.
Dynamic event counts don't trace, so the kernel consumes the *fixed-
capacity* formulation event-based accelerators use: every output row gets
``K`` event slots (K = the static, lane-rounded event budget), real events
compacted to the front, padding slots carrying value 0.

Grid is (E / be, N / bn): each program instance owns a [be, bn] output
tile plus its [be, K] event-list slice and the full weight table's [n_in,
bn] column block, zeroes its accumulator tile, then walks the ``be * K``
event slots scattering weight-row slices into it (``pl.when`` skips the
zero-valued padding slots, so per-tile work tracks real traffic).  The
event lists are read one scalar at a time at dynamic positions, so they
live in SMEM (a dynamic scalar read from VMEM must be lane-aligned, which
a slot index is not).  Exact int32 accumulation with the same wraparound
semantics as the dense matmul: int32 addition is order-independent, so
for any sufficient budget the result is bit-identical to ``spikes @ w_q``.
Event lists and weight tables that do not tile are zero-padded (value-0
slots, zero weight columns) and the result sliced back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(vals_ref, idx_ref, w_ref, o_ref, *, be, cap):
    o_ref[...] = jnp.zeros_like(o_ref)

    def body(e, carry):
        r = e // cap  # output row within this tile
        j = e % cap  # event slot within that row
        v = vals_ref[r, j]
        c = idx_ref[r, j]

        @pl.when(v != 0)
        def _scatter():
            o_ref[pl.ds(r, 1), :] += v * w_ref[pl.ds(c, 1), :]

        return carry

    jax.lax.fori_loop(0, be * cap, body, 0)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.jit, static_argnames=("be", "bn", "interpret"))
def sparse_accum(
    vals,  # int [E, K] per-slot event values (0 = padding)
    idx,  # int [E, K] per-slot source channels
    w_q,  # int [n_in, N] quantized weight table
    *,
    be: int = 256,
    bn: int = 128,
    interpret: bool = False,
):
    """Exact int32 ``sum_j vals[e, j] * w_q[idx[e, j]]``, any shape."""
    E, K = vals.shape
    n_in, N = w_q.shape
    be = min(be, _round_up(E, 8))
    bn = N if N <= bn else bn
    Ep, Np = _round_up(E, be), _round_up(N, bn)
    vals = jnp.pad(vals.astype(jnp.int32), ((0, Ep - E), (0, 0)))
    idx = jnp.pad(idx.astype(jnp.int32), ((0, Ep - E), (0, 0)))
    w = jnp.pad(w_q.astype(jnp.int32), ((0, 0), (0, Np - N)))
    events = pl.BlockSpec((be, K), lambda i, j: (i, 0), memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, be=be, cap=K),
        grid=(Ep // be, Np // bn),
        in_specs=[events, events, pl.BlockSpec((n_in, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((be, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Ep, Np), jnp.int32),
        interpret=interpret,
    )(vals, idx, w)
    return out[:E, :N]
