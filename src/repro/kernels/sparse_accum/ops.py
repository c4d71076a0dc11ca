"""Public entry points for the fixed-capacity sparse event path.

``fixed_capacity_events`` is the jit-compatible AER encoder: it compacts a
spike raster into the static-budget event list the kernel consumes.
``sparse_accum_currents`` is the window-level integration op the event
backend and the serving lane window call.  On TPU it always runs the Pallas
kernel; elsewhere the identical numerics run through XLA (interpret-mode
Pallas is a debugging tool, not a fast path -- the parity suite in
``tests/test_sparse_accum.py`` holds the actual kernel to the bit-exact
contract on CPU via ``interpret=True``).

Off-TPU the lowering is chosen by an exactness certificate the *budget*
provides: every output row accumulates at most ``budget`` events, so where
``repro.core.lowering.f32_exact(w_bits, max_value, budget)`` holds the f32
BLAS matmul is bit-exact and 4-5x faster than XLA's integer loops on CPU --
this is what makes the jitted event strategy *faster* than the dense int
path even though XLA:CPU's gather/scatter lowerings lose to their own dense
matmul.  When the certificate fails, the exact int einsum carries the
numerics.

Budget semantics: the budget is a capacity contract -- callers size it at
or above the measured max per-row active-channel count (see
``EventBackend.static_budget`` / the serving admission rule).  For a
sufficient budget every lowering is bit-identical to the dense matmul.
For an *insufficient* budget the event-list paths (``fixed_capacity_events``
+ kernel/ref) deterministically keep each row's ``budget`` largest values
and drop the rest, while the dense lowerings have no list to clamp -- so
over-budget behavior is only defined at the event-list level.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import lowering
from repro.kernels.sparse_accum.sparse_accum import sparse_accum


def fixed_capacity_events(raster, budget: int):
    """Compact a spike raster into a fixed-capacity AER event list.

    ``raster`` int [..., n_in] with nonnegative spike values; ``budget`` is
    the static per-row slot count.  Returns ``(vals, idx)`` each
    [..., budget]: per row, the active (value, channel) pairs compacted to
    the front, remaining slots padded with value 0 (their channel is the
    tie-broken argmax of the zeros and is ignored by the accumulate).  When
    a row holds more than ``budget`` active channels, the ``budget``
    largest values are kept, ties broken toward lower channel indices
    (``top_k`` order) -- deterministic clamp semantics, exercised by the
    parity suite.
    """
    vals, idx = jax.lax.top_k(raster.astype(jnp.int32), budget)
    return vals, idx


def sparse_accum_currents(
    raster,  # int [T, B, n_in] spike raster (nonnegative values)
    w_q,  # int [n_in, N] quantized weight table
    budget: int,
    *,
    f32_exact: bool = True,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
):
    """Window FF currents [T, B, N] via the fixed-capacity event formulation.

    On TPU (or with ``use_pallas=True``) the raster is AER-encoded at the
    static ``budget`` and scattered through the Pallas kernel.  Elsewhere
    the identical int32 result comes from the f32 BLAS matmul when the
    caller certifies it (``f32_exact=True`` asserts
    ``lowering.f32_exact(w_bits, max_value, budget)``) and from the exact
    int einsum otherwise.  All paths share the dense matmul's wraparound
    semantics for any sufficient budget.
    """
    T, B, n_in = raster.shape
    budget = min(budget, n_in)
    flat = raster.astype(jnp.int32).reshape(T * B, n_in)
    if use_pallas is None:
        use_pallas = lowering.on_tpu()
    if use_pallas:
        vals, idx = fixed_capacity_events(flat, budget)
        out = sparse_accum(vals, idx, w_q, interpret=lowering.interpret(interpret))
    elif f32_exact:
        out = lowering.f32_currents(flat, w_q)
    else:
        out = jnp.einsum("ek,kn->en", flat, w_q.astype(jnp.int32))
    return out.reshape(T, B, -1)
