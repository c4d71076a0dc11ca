"""Batched LM serving engine: continuous batching over fixed cache slots.

The inference-side driver for the decode_* dry-run shapes, runnable at
reduced scale on CPU: a fixed pool of ``max_batch`` cache slots; incoming
requests are prefilled individually and copied into free slots; one fused
``decode_step`` advances every active slot each tick; finished sequences
free their slots immediately (continuous batching -- no head-of-line
blocking on long generations).

Weights can be served quantized through the paper's precision machinery
(``PrecisionPolicy``), which shrinks the weight bytes each decode step
reads.  The SNN-side counterpart -- the paper's actual
workload served the same continuous-batching way -- is
``repro.serve.snn_engine``; both engines are documented side by side in
``docs/SERVING.md``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import PrecisionPolicy, quantize_tree
from repro.models import transformer as tfm
from repro.models.registry import Arch

__all__ = ["Request", "ServeEngine"]


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # int32 [prompt_len]
    max_new_tokens: int = 16
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    def __init__(
        self,
        arch: Arch,
        params,
        *,
        max_batch: int = 8,
        max_len: int = 512,
        quant: PrecisionPolicy | None = None,
        greedy: bool = True,
    ):
        self.arch = arch
        self.cfg = arch.reduced_config
        self.params = quantize_tree(params, quant) if quant is not None else params
        self.max_batch = max_batch
        self.max_len = max_len
        self.greedy = greedy
        self.caches = tfm.cache_init(self.cfg, max_batch, max_len)
        self.cur_len = jnp.zeros((max_batch,), jnp.int32)
        self.slots: list[Request | None] = [None] * max_batch
        self._decode = jax.jit(
            lambda p, c, tok, ln: tfm.decode_step(self.cfg, p, c, tok, ln)
        )
        self.last_token = np.zeros((max_batch,), np.int32)

    # -- admission ---------------------------------------------------------
    def _free_slot(self) -> int | None:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def admit(self, req: Request) -> bool:
        """Prefill a request into a free slot (returns False when full).

        Prefill runs token-by-token through the shared decode step (a
        production engine prefills in one pass; token stepping keeps the
        smoke-scale engine simple and exercises the same cache paths), then
        every *other* slot's cache column and length are restored from a
        snapshot so admission never perturbs in-flight sequences.
        """
        slot = self._free_slot()
        if slot is None:
            return False
        snap_caches, snap_len = self.caches, self.cur_len
        self.cur_len = self.cur_len.at[slot].set(0)
        self.caches = jax.tree.map(
            lambda c: c.at[:, slot].set(jnp.zeros_like(c[:, slot])), self.caches
        )
        for t in req.prompt:
            tok = jnp.asarray(self.last_token)[:, None]
            tok = tok.at[slot, 0].set(int(t))
            logits, self.caches = self._decode(self.params, self.caches, tok, self.cur_len)
            self.cur_len = self.cur_len.at[slot].add(1)
        nxt = int(jnp.argmax(logits[slot, -1]))

        def restore(new, old):
            mask = jnp.zeros((new.shape[1],), bool).at[slot].set(True)
            shape = (1, new.shape[1]) + (1,) * (new.ndim - 2)
            return jnp.where(mask.reshape(shape), new, old)

        self.caches = jax.tree.map(restore, self.caches, snap_caches)
        self.cur_len = jnp.where(
            jnp.arange(self.max_batch) == slot, self.cur_len, snap_len
        )
        self.last_token[slot] = nxt
        req.generated.append(nxt)
        self.slots[slot] = req
        return True

    # -- decode tick ---------------------------------------------------------
    def tick(self) -> list[Request]:
        """One fused decode step for all active slots; returns finished."""
        if not any(s is not None for s in self.slots):
            return []
        tok = jnp.asarray(self.last_token)[:, None]
        logits, self.caches = self._decode(self.params, self.caches, tok, self.cur_len)
        self.cur_len = self.cur_len + jnp.asarray(
            [1 if s is not None else 0 for s in self.slots], jnp.int32
        )
        finished = []
        nxt = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            req.generated.append(int(nxt[i]))
            self.last_token[i] = int(nxt[i])
            if len(req.generated) >= req.max_new_tokens or int(self.cur_len[i]) >= self.max_len - 1:
                req.done = True
                finished.append(req)
                self.slots[i] = None
        return finished

    def run(self, requests: list[Request]) -> list[Request]:
        """Serve a request list to completion with continuous batching."""
        pending = list(requests)
        done: list[Request] = []
        while pending or any(s is not None for s in self.slots):
            while pending and self.admit(pending[0]):
                pending.pop(0)
            done.extend(self.tick())
        return done
