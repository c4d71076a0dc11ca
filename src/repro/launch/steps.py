"""Step builders: jit-compiled train / prefill / decode steps with shardings.

Shared by the dry-run (lower+compile against abstract inputs), the real
training loop (launch/train.py) and the serving path (launch/serve.py).
Donation is wired for the big recurring buffers (params/optimizer state in
training; KV caches in decode) so the compiled memory footprint is honest.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.precision import PrecisionPolicy, quantize_tree
from repro.models.registry import Arch, ShapeSpec
from repro.train import optimizer as opt_lib

__all__ = ["StepBundle", "build_train_step", "build_prefill_step", "build_decode_step"]


@dataclasses.dataclass
class StepBundle:
    """A jit-able step plus everything needed to lower it abstractly."""

    jitted: Any
    abstract_args: tuple
    name: str

    def lower(self):
        return self.jitted.lower(*self.abstract_args)


def _named(mesh, tree_of_pspecs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_of_pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


def build_train_step(
    arch: Arch,
    shape: ShapeSpec,
    mesh,
    cfg=None,
    *,
    lr: float = 3e-4,
    grad_clip: float = 1.0,
    optimizer=None,
    loss_fn=None,
    bf16_gather: bool = False,
) -> StepBundle:
    cfg = cfg or arch.config
    loss_fn = loss_fn or arch.loss_fn(cfg)
    optimizer = optimizer or opt_lib.adamw(lr)

    if bf16_gather:
        # single cast site at step start: the SPMD partitioner then converts
        # each FSDP shard to bf16 *before* the all-gather, halving gather
        # bytes on the wire (verified in the probe HLO -- section Perf).
        inner = loss_fn

        def loss_fn(params, batch):  # noqa: F811
            pc = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if jnp.issubdtype(p.dtype, jnp.floating) else p,
                params,
            )
            return inner(pc, batch)

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        grads, gnorm = opt_lib.clip_by_global_norm(grads, grad_clip)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = opt_lib.apply_updates(params, updates)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return params, opt_state, metrics

    abs_params = arch.abstract_params(cfg)
    abs_opt = jax.eval_shape(optimizer.init, abs_params)
    abs_batch = arch.input_template(shape, cfg)

    p_specs = arch.param_pspecs(mesh, cfg)
    o_specs = type(abs_opt)(step=P(), mu=p_specs, nu=p_specs)
    b_specs = arch.input_pspecs(mesh, shape, cfg)
    p_sh, o_sh, b_sh = _named(mesh, p_specs), _named(mesh, o_specs), _named(mesh, b_specs)

    jitted = jax.jit(
        train_step,
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1),
    )
    return StepBundle(jitted, (abs_params, abs_opt, abs_batch), f"train:{arch.name}:{shape.name}")


def _serve_params(arch, mesh, cfg, quant, serve_optimized: bool):
    """(abstract params, pspecs) for the serving side.

    Baseline: the training layout (f32, FSDP+TP) -- what a naive deployment
    inherits.  ``serve_optimized``: bf16 weights sharded TP-only (replicated
    over data) -- batch-sharded decode then needs *zero* parameter
    collectives per step, removing the all-gather wall the baseline dry-run
    measures.
    """
    abs_params = arch.abstract_params(cfg)
    p_specs = arch.param_pspecs(mesh, cfg)
    if serve_optimized:
        abs_params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
            if jnp.issubdtype(s.dtype, jnp.floating) else s,
            abs_params,
        )
        p_specs = jax.tree.map(
            lambda s: P(*(a if a == "model" else None for a in s)),
            p_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
    if quant is not None:
        abs_params = jax.eval_shape(lambda p: quantize_tree(p, quant), abs_params)
    return abs_params, _quant_pspecs(p_specs, abs_params)


def build_prefill_step(
    arch: Arch, shape: ShapeSpec, mesh, cfg=None, *,
    quant: PrecisionPolicy | None = None, serve_optimized: bool = False,
) -> StepBundle:
    cfg = cfg or arch.config
    prefill = arch.prefill_fn(cfg)

    abs_params, p_specs = _serve_params(arch, mesh, cfg, quant, serve_optimized)
    abs_batch = arch.input_template(shape, cfg)
    p_sh = _named(mesh, p_specs)
    b_sh = _named(mesh, arch.input_pspecs(mesh, shape, cfg))

    jitted = jax.jit(prefill, in_shardings=(p_sh, b_sh))
    return StepBundle(jitted, (abs_params, abs_batch), f"prefill:{arch.name}:{shape.name}")


def build_decode_step(
    arch: Arch,
    shape: ShapeSpec,
    mesh,
    cfg=None,
    *,
    quant: PrecisionPolicy | None = None,
    shard_cache_seq: bool = False,
    serve_optimized: bool = False,
) -> StepBundle:
    """serve_step: one new token against a seq_len-deep cache.

    ``shard_cache_seq`` shards the KV cache over the data axis on sequence --
    the long_500k (batch=1) configuration, where batch sharding is impossible
    and GSPMD turns the softmax reductions into the two-pass distributed
    softmax.
    """
    cfg = cfg or arch.config
    decode = arch.decode_fn(cfg)

    abs_params, p_specs = _serve_params(arch, mesh, cfg, quant, serve_optimized)
    abs_cache = arch.cache_abstract(shape, cfg)
    abs_batch = arch.input_template(shape, cfg)

    p_sh = _named(mesh, p_specs)
    c_sh = _named(mesh, arch.cache_pspecs(mesh, shape, cfg, shard_seq=shard_cache_seq))
    b_sh = _named(mesh, arch.input_pspecs(mesh, shape, cfg))

    jitted = jax.jit(
        decode,
        in_shardings=(p_sh, c_sh, b_sh),
        out_shardings=(None, c_sh),
        donate_argnums=(1,),
    )
    return StepBundle(jitted, (abs_params, abs_cache, abs_batch), f"decode:{arch.name}:{shape.name}")


def _quant_pspecs(p_specs, abs_params):
    """Align a param pspec tree with a (possibly quantized) abstract tree.

    QTensor leaves replace one array with (q, scale); q keeps the original
    weight's spec, the per-column scale inherits the spec's last axis.
    """
    from repro.core.precision import QTensor

    def align(spec, leaf):
        if isinstance(leaf, QTensor):
            last = spec[-1] if len(spec) else None
            lead = tuple(spec[:-1]) if len(spec) else ()
            return QTensor(
                q=P(*lead, last), scale=P(*((None,) * (leaf.scale.ndim - 1)), last), bits=leaf.bits, shape=leaf.shape
            )
        return spec

    return jax.tree.map(
        align,
        p_specs,
        abs_params,
        is_leaf=lambda x: isinstance(x, (P, QTensor)),
    )
