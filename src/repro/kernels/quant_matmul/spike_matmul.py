"""Pallas TPU kernel: exact int8 spike x quantized-weight matmul.

The spike-integration phase of a Flexi-NeurA core is a {0,1}-activation
matmul against the quantized weight table -- integer in, integer out, with
*exact* integer accumulation (the membrane register adds weight columns; no
float rounding is allowed if the simulator is to stay bit-faithful).  The
bf16-activation ``quant_matmul`` kernel next door trades exactness for MXU
throughput and is the right tool for the LM stack; this kernel is its
bit-exact sibling for the SNN fast path.

The MXU takes int8 x int8 -> int32 natively (Mosaic refuses an int32 x
int32 dot), so the kernel's operands are int8: exact for weights with
``w_bits <= 8`` and spike values up to 127.  Wider operands take XLA's
int32 dot; ``repro.core.lowering.mxu_feed`` makes that choice.

Tiling mirrors ``quant_matmul``: grid (M/bm, N/bn, K/bk) with an int32
accumulator tile in VMEM scratch across the K loop (K innermost, so each
(i, j) output tile sees its partials in order).  Operands that do not tile
are zero-padded to the tile and the result sliced back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lowering


def _kernel(s_ref, w_ref, o_ref, acc_ref, *, k_steps):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        s_ref[...], w_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...]


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _tile(n: int, block: int, align: int) -> tuple[int, int]:
    """(block, padded n): the full extent when it fits one block (rounded
    up to ``align``), else ``block``-sized tiles."""
    if n <= block:
        b = _round_up(n, align)
        return b, b
    return block, _round_up(n, block)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def spike_matmul(
    s,  # int8 [M, K] spike raster (rows = flattened time x batch)
    w_q,  # int8 [K, N] quantized weights
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 256,
    interpret: bool = False,
):
    """Exact int32 ``s @ w_q`` from int8 operands, any shape."""
    M, K = s.shape
    N = w_q.shape[1]
    # int8 tiles are (32, 128): pad M to 32-row multiples, K and N to lanes
    bm, Mp = _tile(M, bm, 32)
    bk, Kp = _tile(K, bk, 128)
    bn, Np = _tile(N, bn, 128)
    s = jnp.pad(s.astype(jnp.int8), ((0, Mp - M), (0, Kp - K)))
    w = jnp.pad(w_q.astype(jnp.int8), ((0, Kp - K), (0, Np - N)))
    k_steps = Kp // bk
    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps),
        grid=(Mp // bm, Np // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(s, w)
    return out[:M, :N]


def spike_integrate(
    spikes,  # int [T, B, K] input spike raster
    w_q,  # int32 [K, N] quantized weights
    *,
    w_bits: int = 16,
    max_val: int | None = None,
    use_pallas: bool = False,
    interpret: bool | None = None,
):
    """Window-level integration currents [T, B, N] = spikes @ w_q (exact).

    With ``use_pallas`` the lowering is ``lowering.mxu_feed(w_bits,
    max_val)``: the int8 kernel, XLA's int32 dot, or -- for a traced input
    of unknown magnitude -- both, picked per call from the raster's
    maximum.  Without it, XLA's int32 dot.  The kernel interprets exactly
    when off-TPU (``lowering.interpret``).
    """
    T, B, K = spikes.shape
    s2 = spikes.astype(jnp.int32).reshape(T * B, K)
    w32 = w_q.astype(jnp.int32)

    def int8():
        return spike_matmul(s2, w_q, interpret=lowering.interpret(interpret))

    def int32():
        return jnp.einsum("mk,kn->mn", s2, w32)

    feed = lowering.mxu_feed(w_bits, max_val) if use_pallas else lowering.XLA_INT32
    if feed == lowering.PALLAS_INT8:
        out = int8()
    elif feed == lowering.INT8_OR_INT32:
        out = jax.lax.cond(jnp.max(jnp.abs(s2)) <= 127, int8, int32)
    else:
        out = int32()
    return out.reshape(T, B, -1)
