"""Which lowering each integer op takes, and which lowerings are exact.

Every feed-forward integration in the simulator is an integer product
``spikes @ w_q`` that must come out bit-identical to the int32 reference.
Four lowerings compute it, and this module is the one place that decides
between them and the one place that asks which platform the code runs on:

``"pallas-int8"``
    The ``spike_matmul`` Pallas kernel, feeding the MXU int8 x int8 ->
    int32.  Exact whenever both operands fit int8: weights with ``w_bits <=
    8`` and spike values <= 127 (binary spikes always qualify).
``"xla-int32"``
    XLA's integer dot.  Exact for every input; the fallback for wide
    weights and graded inputs.
``"pallas-sparse"``
    The ``sparse_accum`` Pallas kernel: an event list scattered into int32
    accumulators.  Exact for every input; the event strategy's TPU path.
``"f32"``
    An f32 matmul (:func:`f32_currents`), cast back to int32.  Exact only
    under :func:`f32_exact`'s bound: on CPU, every partial sum must stay
    below 2**24 (f32's exact-integer range); on TPU a default-precision f32
    matmul multiplies in one bf16 pass, which holds integers exactly only
    up to 256, so there both operands must also fit that range.

Pallas kernels compile for the chip on a TPU and run in interpret mode
everywhere else (:func:`interpret`); nothing runs a kernel in interpret mode
on a TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.fixed_point import int_min

__all__ = [
    "PALLAS_INT8",
    "XLA_INT32",
    "PALLAS_SPARSE",
    "F32",
    "INT8_OR_INT32",
    "on_tpu",
    "interpret",
    "mxu_feed",
    "f32_exact",
    "f32_max_input",
    "f32_currents",
]

PALLAS_INT8 = "pallas-int8"
XLA_INT32 = "xla-int32"
PALLAS_SPARSE = "pallas-sparse"
F32 = "f32"
#: ``mxu_feed`` for a traced input of unknown magnitude: the program holds
#: both lowerings and picks per call from the batch's maximum (``lax.cond``).
INT8_OR_INT32 = "pallas-int8|xla-int32"

_INT8_MAX = 127
_INT8_W_BITS = 8  # weights clip to [int_min, int_max] of w_bits: int8 holds w_bits <= 8
_F32_EXACT = 1 << 24  # integers below this are exact in f32
_BF16_EXACT = 256  # integers up to this are exact in bf16


def _w_mag(w_bits: int) -> int:
    """Largest weight magnitude at ``w_bits`` (quantization clips to int_min)."""
    return -int_min(w_bits)


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def interpret(requested: bool | None = None) -> bool:
    """Whether Pallas kernels run in interpret mode: exactly when off-TPU.

    ``requested`` is a caller's explicit setting; it may only restate the
    platform's answer (tests pass ``True`` on CPU).  Asking for interpret
    mode on a TPU raises.
    """
    if on_tpu():
        if requested:
            raise ValueError("Pallas interpret mode is for CPU runs; a TPU compiles its kernels")
        return False
    return True


def mxu_feed(w_bits: int, max_val: int | None) -> str:
    """How ``spike_matmul`` computes ``spikes @ w_q`` for these operands.

    ``max_val`` is the largest spike magnitude, or ``None`` when it is only
    known on the device (a traced input): then the program carries both
    the int8 kernel and the int32 dot and picks per call.
    """
    if w_bits > _INT8_W_BITS:
        return XLA_INT32
    if max_val is None:
        return INT8_OR_INT32
    return PALLAS_INT8 if max_val <= _INT8_MAX else XLA_INT32


def f32_exact(w_bits: int, max_val: int, rows: int) -> bool:
    """True when :func:`f32_currents` is bit-exact on this platform.

    ``rows`` bounds how many nonzero products one output sums (the layer's
    ``n_in``, or a smaller event budget); ``max_val`` bounds the spike
    values.  On a TPU the bf16 bound applies on top of the f32 one.
    """
    w = _w_mag(w_bits)
    if on_tpu() and (w > _BF16_EXACT or max_val > _BF16_EXACT):
        return False
    return w * max_val * rows < _F32_EXACT


def f32_max_input(w_bits: int, rows: int) -> int:
    """The largest spike value for which :func:`f32_exact` holds (0: none)."""
    top = (_F32_EXACT - 1) // (_w_mag(w_bits) * rows)
    if on_tpu():
        top = min(top, _BF16_EXACT)
    # the rule is monotone in max_val: top is the answer unless the rule
    # refuses these weights at any input
    return top if f32_exact(w_bits, top, rows) else 0


def f32_currents(x, w_q):
    """``x [..., n_in] @ w_q [n_in, N]`` through an f32 matmul, as int32.

    Exact only where the caller checked :func:`f32_exact`.  On CPU this
    runs the hot matmul through BLAS instead of XLA's integer loops; on TPU
    through one bf16 MXU pass.
    """
    lead = x.shape[:-1]
    flat = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    cur = flat @ w_q.astype(jnp.float32)
    return cur.astype(jnp.int32).reshape(*lead, -1)
