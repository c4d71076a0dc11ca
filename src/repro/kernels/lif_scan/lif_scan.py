"""Pallas TPU kernel: fused fixed-point LIF/IF scan with CG shift-add decay.

Hardware mapping of the paper's core (DESIGN.md section 2): the RTL keeps
membrane potentials in BRAM adjacent to a time-multiplexed datapath and
streams spike events through it; the TPU-native equivalent keeps a
[block_b, block_n] tile of membrane state resident in VMEM while the whole
inference window (T steps) streams through, so HBM traffic is exactly one
read of the input-current stream and one write of the spike raster --
state never round-trips.

Grid: (B / block_b, N / block_n); the time loop runs inside the kernel
(jax.lax.fori_loop) over a VMEM-resident current block [T, block_b, block_n].
The CG decay factor k is static, so the gated shift network unrolls into
straight-line adds exactly like the synthesized RTL (section 4.1.2).

Integer ops run on the VPU; there is no MXU work here by design -- the
upstream spike-weight integration matmul is a separate (quant_matmul) kernel,
mirroring the paper's split between integration and leak/fire phases.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fixed_point import int_max, int_min


def _kernel(cur_ref, spk_ref, u_final_ref, *, theta_q, decay_k, u_bits, reset_to_zero, t_steps):
    qmin, qmax = int_min(u_bits), int_max(u_bits)

    def step(t, u):
        i_t = cur_ref[t]  # [block_b, block_n] int32
        u = jnp.clip(u + i_t, qmin, qmax)
        spk = u >= theta_q
        if reset_to_zero:
            u_reset = jnp.zeros_like(u)
        else:
            u_reset = jnp.clip(u - theta_q, qmin, qmax)
        if decay_k >= 256:  # bypass path: IF model
            u_leak = u
        else:
            acc = jnp.zeros_like(u)
            for shift in range(1, 9):  # static k: unrolled like the RTL
                if (decay_k >> (8 - shift)) & 1:
                    acc = acc + (u >> shift)
            u_leak = jnp.clip(acc, qmin, qmax)
        u = jnp.where(spk, u_reset, u_leak)
        spk_ref[t] = spk.astype(jnp.int32)
        return u

    u = jnp.zeros(cur_ref.shape[1:], jnp.int32)
    u = jax.lax.fori_loop(0, t_steps, step, u)
    u_final_ref[...] = u


@functools.partial(
    jax.jit,
    static_argnames=("theta_q", "decay_k", "u_bits", "reset_to_zero", "block_b", "block_n", "interpret"),
)
def lif_scan(
    currents,  # int32 [T, B, N]
    *,
    theta_q: int,
    decay_k: int,
    u_bits: int = 16,
    reset_to_zero: bool = False,
    block_b: int = 8,
    block_n: int = 128,
    interpret: bool = False,
):
    """Fused LIF window scan. Returns (spikes [T, B, N], final_u [B, N]).

    A batch that does not tile by ``block_b`` is zero-padded and sliced
    back (lanes never interact); so is a layer wider than one ``block_n``
    tile that does not tile by it.  A layer narrower than ``block_n`` is
    one full-width tile.
    """
    T, B, N = currents.shape
    block_n = N if N <= block_n else block_n
    Bp, Np = -(-B // block_b) * block_b, -(-N // block_n) * block_n
    currents = jnp.pad(currents, ((0, 0), (0, Bp - B), (0, Np - N)))

    kernel = functools.partial(
        _kernel,
        theta_q=theta_q,
        decay_k=decay_k,
        u_bits=u_bits,
        reset_to_zero=reset_to_zero,
        t_steps=T,
    )
    spikes, u = pl.pallas_call(
        kernel,
        grid=(Bp // block_b, Np // block_n),
        in_specs=[
            pl.BlockSpec((T, block_b, block_n), lambda i, j: (0, i, j)),
        ],
        out_specs=[
            pl.BlockSpec((T, block_b, block_n), lambda i, j: (0, i, j)),
            pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, Bp, Np), jnp.int32),
            jax.ShapeDtypeStruct((Bp, Np), jnp.int32),
        ],
        interpret=interpret,
    )(currents)
    return spikes[:, :B, :N], u[:B, :N]
