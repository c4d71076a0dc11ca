"""Compute/communication overlap: ring all-gather matmul.

gemma2's training cells are bound by TP activation all-reduces and FSDP
weight gathers that XLA schedules *before* the consuming matmul.  The
classic fix is to decompose the gathered matmul into a ring: each of the g
steps multiplies the shard currently held while `ppermute` forwards it to
the ring neighbour, so the collective hides behind the MXU except for the
first hop:

    y = x @ W,  W sharded over axis `tp` on its first dim
      = sum_s x[:, shard_s] @ W_s      (shards arrive around the ring)

Exposed as a shard_map-compatible primitive; numerically identical to the
gathered matmul (property-tested).  On the dry-run meshes it trades the
all-gather's (g-1)/g·|W| wire for the same bytes on ppermute edges, but in
g-1 *overlappable* hops -- the win is schedule, not bytes, so it shows up
in wall-clock (TPU) rather than the wire-byte roofline term.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.compat import pcast_varying, shard_map

__all__ = ["ring_allgather_matmul", "ring_allgather_matmul_shardmap"]


def ring_allgather_matmul(x_local, w_shard, axis_name: str):
    """Inside shard_map: x_local [M, K], w_shard [K/g, N] (this rank's shard).

    Per ring step: multiply the resident shard against the matching K-slice
    of x while passing the shard on.  Returns [M, N] (full, replicated over
    the ring axis contribution-wise -- callers keep x replicated on the tp
    axis, as in a Megatron column-parallel layer's input).
    """
    g = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    k_shard = w_shard.shape[0]

    def body(step, carry):
        w_cur, acc = carry
        # shard currently held originated at rank (idx - step) mod g
        src = (idx - step) % g
        x_slice = jax.lax.dynamic_slice_in_dim(x_local, src * k_shard, k_shard, axis=1)
        acc = acc + jnp.einsum("mk,kn->mn", x_slice, w_cur)
        # forward the shard to the next rank (overlaps the next multiply)
        w_nxt = jax.lax.ppermute(
            w_cur, axis_name, perm=[(i, (i + 1) % g) for i in range(g)]
        )
        return (w_nxt, acc)

    acc0 = jnp.zeros((x_local.shape[0], w_shard.shape[1]), x_local.dtype)
    # partial sums vary per ring rank mid-loop; mark the carry as varying so
    # the fori_loop types agree under shard_map's varying-axis tracking
    acc0 = pcast_varying(acc0, axis_name)
    _, out = jax.lax.fori_loop(0, g, body, (w_shard, acc0))
    return out


def ring_allgather_matmul_shardmap(mesh: Mesh, axis_name: str = "model"):
    """jit-able [M, K] x [K, N] matmul with W gathered around the ring.

    W enters sharded P(axis, None); x replicated on ``axis``.
    """

    def fn(x, w):
        out = shard_map(
            functools.partial(ring_allgather_matmul, axis_name=axis_name),
            mesh=mesh,
            in_specs=(P(), P(axis_name, None)),
            out_specs=P(),
            # after g hops every rank holds the identical full sum (shards
            # arrive in rank-rotated order); the tracker can't infer that
            check_vma=False,
        )(x, w)
        return out

    return fn
