"""Multi-core Flexi-NeurA network: layer-to-core mapping and full simulation.

The paper maps each hidden/output layer to a dedicated processing core wired
through AER packets (Fig. 4); a layer wider than one core's 256 addresses or
neurons maps onto several (``LayerConfig.core_slices``, see
``repro.core.snn_layer``), so :attr:`NetworkConfig.n_cores` can exceed the
layer count.  Functionally the system is a layered SNN
unrolled over time; this module provides

* :func:`init_float_params` / :func:`quantize_params` -- the train->deploy path
  (float weights from BPTT, quantized to each core's fixed-point widths, with
  thresholds rescaled onto the same grid),
* :func:`run_float`  -- differentiable unrolled simulation (training / DSE),
* :func:`run_int`    -- bit-exact hardware-faithful simulation (deployment
  accuracy, the DSE's "hardware-aware accuracy"),

plus per-layer spike statistics that feed the latency/energy model in
``repro.core.hw_model``.

Both entry points take ``backend=`` -- a name registered with
``repro.core.backend`` (``"reference"`` step-major jnp semantics, ``"fused"``
layer-major Pallas kernel path, ``"event"`` sparse event-driven traversal)
or an ``InferenceBackend`` instance.  Every backend is held bit-exact to
``reference`` on its supported configs by ``tests/test_backend_parity.py``,
and every backend's :class:`SimRecord` carries the per-step event counts
that feed the latency/energy model in ``repro.core.hw_model``.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import InferenceBackend, SimRecord, get_backend
from repro.core.fixed_point import int_max
from repro.core.snn_layer import (
    FloatLayerParams,
    IntLayerParams,
    LayerConfig,
    Topology,
)

__all__ = [
    "NetworkConfig",
    "init_float_params",
    "layer_scale",
    "quantize_params",
    "run_float",
    "run_int",
    "SimRecord",
]


@dataclasses.dataclass(frozen=True)
class NetworkConfig:
    """A stack of cores plus the inference window length."""

    layers: tuple[LayerConfig, ...]
    n_steps: int
    name: str = "snn"

    def __post_init__(self):
        for prev, nxt in zip(self.layers[:-1], self.layers[1:]):
            if prev.n_out != nxt.n_in:
                raise ValueError(
                    f"layer size mismatch: {prev.n_out} -> {nxt.n_in} in {self.name}"
                )

    @property
    def n_in(self) -> int:
        return self.layers[0].n_in

    @property
    def n_classes(self) -> int:
        return self.layers[-1].n_out

    @property
    def n_cores(self) -> int:
        """Physical cores after wide layers are split across cores."""
        return sum(lc.n_cores for lc in self.layers)

    def replace_precisions(self, w_bits=None, w_rec_bits=None, leak_bits=None):
        """A new config with uniformly overridden DSE knobs (None = keep)."""
        new_layers = []
        for lc in self.layers:
            new_layers.append(
                dataclasses.replace(
                    lc,
                    w_bits=w_bits if w_bits is not None else lc.w_bits,
                    w_rec_bits=w_rec_bits if w_rec_bits is not None else lc.w_rec_bits,
                    leak_bits=leak_bits if leak_bits is not None else lc.leak_bits,
                )
            )
        return dataclasses.replace(self, layers=tuple(new_layers))


def init_float_params(key, net: NetworkConfig) -> list[FloatLayerParams]:
    params = []
    for cfg in net.layers:
        key, k_ff, k_rec = jax.random.split(key, 3)
        # SNN-Torch style: weights sized so a typical step's input current is
        # O(threshold); uniform(+-1/sqrt(fan_in)) as in torch.nn.Linear.
        lim = 1.0 / np.sqrt(cfg.n_in)
        w_ff = jax.random.uniform(k_ff, (cfg.n_in, cfg.n_out), jnp.float32, -lim, lim)
        if cfg.topology == Topology.ATA_T:
            rlim = 1.0 / np.sqrt(cfg.n_out)
            w_rec = jax.random.uniform(
                k_rec, (cfg.n_out, cfg.n_out), jnp.float32, -rlim, rlim
            )
        elif cfg.topology == Topology.ATA_F:
            w_rec = jnp.asarray(0.1, jnp.float32)  # shared self-weight register
        else:
            w_rec = jnp.zeros((0,), jnp.float32)
        params.append(
            FloatLayerParams(w_ff=w_ff, w_rec=w_rec, theta=jnp.asarray(cfg.threshold))
        )
    return params


def layer_scale(cfg, p: FloatLayerParams, w_max=None, rec_max=None) -> jax.Array:
    """The core's float->fixed-point quantization scale, as a traced f32 scalar.

    One scale per core: feed-forward and recurrent contributions accumulate
    into the same register, so they must share a scale; the scale is chosen
    as the tightest one that (a) fits both weight groups in their respective
    bit-widths and (b) keeps the rescaled threshold inside the *membrane
    register* with integration headroom -- the paper's automatic
    threshold/reset rescaling.  Without (b), a narrow u_bits register can
    place theta_q above the saturation point and the core goes silent.

    This is the single source of truth for the scale arithmetic: both
    :func:`quantize_params` (deployment) and the QAT straight-through
    forward (``repro.snn.qat``) call it, in float32 throughout, so the
    train-time fake-quant and the deploy-time quantization round identically
    bit for bit.  ``w_max`` / ``rec_max`` override the weight-grid maxima
    (``int_max(w_bits)`` / ``int_max(w_rec_bits)``) with traced values --
    the population-refinement path varies them per candidate under ``vmap``.
    """
    eps = jnp.float32(1e-12)
    if w_max is None:
        w_max = int_max(cfg.w_bits)
    if rec_max is None:
        rec_max = int_max(cfg.w_rec_bits)
    w_max = jnp.asarray(w_max, jnp.float32)
    rec_max = jnp.asarray(rec_max, jnp.float32)
    absmax_ff = jnp.max(jnp.abs(p.w_ff.astype(jnp.float32)))
    absmax_ff = jnp.where(absmax_ff == 0, eps, absmax_ff)
    scale = w_max / absmax_ff
    if cfg.topology == Topology.ATA_T and p.w_rec.size:
        absmax_rec = jnp.max(jnp.abs(p.w_rec.astype(jnp.float32)))
        scale = jnp.minimum(scale, rec_max / jnp.where(absmax_rec == 0, eps, absmax_rec))
    elif cfg.topology == Topology.ATA_F:
        absmax_rec = jnp.abs(p.w_rec.astype(jnp.float32))
        scale = jnp.minimum(scale, rec_max / jnp.where(absmax_rec == 0, eps, absmax_rec))
    # membrane-register constraint: theta_q at half the register leaves
    # 2x headroom for integration past threshold before saturation
    theta = p.theta.astype(jnp.float32) if hasattr(p.theta, "astype") else jnp.float32(p.theta)
    theta = jnp.where(theta == 0, eps, theta)
    return jnp.minimum(scale, jnp.float32(0.5 * int_max(cfg.u_bits)) / theta)


def quantize_params(
    net: NetworkConfig, params: Sequence[FloatLayerParams]
) -> tuple[list[IntLayerParams], list[float]]:
    """Quantize trained float weights onto each core's fixed-point grid.

    The per-core scale comes from :func:`layer_scale` (see there for the
    selection rule); rounding is round-half-to-even with clipping onto the
    signed grid.  A QAT-trained network (``repro.snn.qat``) deploys through
    this exact function -- the training-time fake-quant mirrors it bit for
    bit, so no separate QAT export path exists.
    """
    qparams, scales = [], []
    for cfg, p in zip(net.layers, params):
        scale = layer_scale(cfg, p)
        w_ff_q = jnp.clip(
            jnp.round(p.w_ff * scale), -int_max(cfg.w_bits) - 1, int_max(cfg.w_bits)
        ).astype(jnp.int32)
        if cfg.topology in (Topology.ATA_T, Topology.ATA_F):
            w_rec_q = jnp.clip(
                jnp.round(p.w_rec * scale),
                -int_max(cfg.w_rec_bits) - 1,
                int_max(cfg.w_rec_bits),
            ).astype(jnp.int32)
        else:
            w_rec_q = jnp.zeros((0,), jnp.int32)
        theta_q = jnp.round(p.theta * scale).astype(jnp.int32)
        qparams.append(IntLayerParams(w_ff=w_ff_q, w_rec=w_rec_q, theta_q=theta_q))
        scales.append(float(scale))
    return qparams, scales


def run_int(
    net: NetworkConfig,
    qparams: Sequence[IntLayerParams],
    spikes_in,
    backend: str | InferenceBackend = "reference",
) -> SimRecord:
    """Bit-exact deployment simulation. ``spikes_in``: int [T, batch, n_in]."""
    return get_backend(backend).run_int(net, list(qparams), spikes_in)


def run_float(
    net: NetworkConfig,
    params: Sequence[FloatLayerParams],
    spikes_in,
    spike_fn,
    backend: str | InferenceBackend = "reference",
) -> SimRecord:
    """Differentiable simulation. ``spikes_in``: float {0,1} [T, batch, n_in]."""
    return get_backend(backend).run_float(net, list(params), spikes_in, spike_fn)
