"""Per-core (= per-layer) SNN semantics: bit-exact integer and float variants.

One Flexi-NeurA core implements one layer.  The hardware processes a time
step in two phases (paper section 4.1.5):

  Phase A -- spike integration.  Each incoming ASPL event (a spike from the
  previous layer) adds the corresponding synaptic-weight column into the
  destination state: ``U`` for IF/LIF, ``I_syn`` for the Synaptic model.
  On EOTS, recurrent ASCL events (this layer's own spikes from the *previous*
  step) are integrated the same way (dense ``W_rec`` for ATA-T; a single
  shared self-weight register for ATA-F).

  Phase B -- leak / spike generation.  Neurons are swept sequentially by the
  time-multiplexed datapath; per neuron:
      Synaptic:  u_tmp = sat(U + I_syn)           (otherwise u_tmp = U)
      if u_tmp >= theta:  spike; U <- reset(u_tmp)   (reset-to-zero / by-subtract)
      else:               U <- CG_beta(u_tmp)        (no decay on the reset path)
      Synaptic:  I_syn <- CG_alpha(I_syn)            (decays every step)

The *vectorised* integer step below reproduces these numerics exactly
provided no intermediate event-by-event accumulation saturates (integration
is order-dependent only under saturation; ``repro.core.events`` provides the
strict per-event reference used by property tests to check this contract).

Core mapping: a core takes at most ``CORE_WIDTH`` (256) input addresses
and holds at most 256 neurons -- the 8-bit ASPL/ASCL addresses of
``repro.core.events``.  A wider layer maps onto
``ceil(n_in / 256) x ceil(n_out / 256)`` physical cores
(:meth:`LayerConfig.core_slices`): each takes one slice of the input
addresses for one slice of the neurons, and the cores that hold the same
neurons merge their int32 partial currents before phase A's one saturation.
int32 addition is associative and saturation is applied once per step
(``_integrate_acc``), so the split is bit-identical to the unsplit layer:
the vectorised paths keep computing a split layer as one product.  An ATA-T
layer's dense recurrence stays inside one core, so its neurons may not span
more than one.

Device ops carry the layer's parts in their op metadata: the feed-forward
product runs under ``jax.named_scope(FF_SCOPE)``, the ATA-T recurrent
product under ``jax.named_scope(RECURRENT_SCOPE)``.

Timing convention: a spike generated in phase B of step ``t`` is the input
that the next layer integrates at its step ``t`` (cores run pipelined, one
step apart in wall-clock but aligned in step index), and is this layer's own
recurrent input at step ``t + 1`` -- matching SNN-Torch's unrolling, which
the paper trains against.
"""

from __future__ import annotations

import dataclasses
import enum
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import coeff_gen
from repro.core.coeff_gen import DecayCode
from repro.core.fixed_point import saturate

__all__ = [
    "CORE_WIDTH",
    "FF_SCOPE",
    "RECURRENT_SCOPE",
    "NeuronModel",
    "ResetMode",
    "Topology",
    "LayerConfig",
    "IntLayerParams",
    "LayerState",
    "int_layer_init",
    "int_layer_step",
    "int_layer_step_dynamic",
    "int_phase_a",
    "int_phase_b",
    "int_layer_window",
    "int_layer_window_carry",
    "int_layer_window_from_currents",
    "fused_eligible",
    "float_layer_init",
    "float_layer_step",
]


#: Input addresses and neurons one physical core holds (8-bit AER addresses).
CORE_WIDTH = 256
#: ``jax.named_scope`` names of a layer's two products, read from the device trace.
FF_SCOPE = "neura.core.ff"
RECURRENT_SCOPE = "neura.core.recurrent"


class NeuronModel(str, enum.Enum):
    IF = "if"  # realised as LIF with the CG bypass path (no leak)
    LIF = "lif"
    SYNAPTIC = "synaptic"


class ResetMode(str, enum.Enum):
    ZERO = "zero"
    SUBTRACT = "subtract"


class Topology(str, enum.Enum):
    FF = "ff"  # feed-forward only
    ATA_F = "ata_f"  # self-feedback only (one shared weight register)
    ATA_T = "ata_t"  # dense intra-layer recurrence


@dataclasses.dataclass(frozen=True)
class LayerConfig:
    """Design-time parameters of one logical layer (pre-synthesis).

    A layer wider than one core maps onto several (:meth:`core_slices`).
    """

    n_in: int
    n_out: int
    neuron: NeuronModel = NeuronModel.LIF
    topology: Topology = Topology.FF
    reset: ResetMode = ResetMode.SUBTRACT
    # Fixed-point widths (the Flex-plorer DSE knobs).
    w_bits: int = 6
    w_rec_bits: int = 6
    u_bits: int = 16
    i_bits: int = 16
    leak_bits: int = 8
    # Float dynamics (trained / user-chosen); quantized on deployment.
    beta: float = 0.95  # membrane leak
    alpha: float = 0.90  # synaptic-current leak (Synaptic model only)
    threshold: float = 1.0

    def __post_init__(self):
        if self.n_in <= 0 or self.n_out <= 0:
            raise ValueError("layer sizes must be positive")
        if self.topology == Topology.ATA_T and self.n_out > CORE_WIDTH:
            raise ValueError(
                f"an ATA-T layer's dense recurrence must stay inside one core of "
                f"{CORE_WIDTH} neurons (got n_out={self.n_out}); a wider layer maps "
                f"onto several cores only with the FF or ATA-F topology"
            )
        for name in ("w_bits", "w_rec_bits"):
            b = getattr(self, name)
            if not 2 <= b <= 16:
                raise ValueError(f"{name} must be in [2, 16], got {b}")
        for name in ("u_bits", "i_bits"):
            b = getattr(self, name)
            if not 4 <= b <= 24:
                raise ValueError(f"{name} must be in [4, 24], got {b}")

    @property
    def fan_in_cores(self) -> int:
        """Cores that share this layer's input addresses, per slice of neurons."""
        return math.ceil(self.n_in / CORE_WIDTH)

    @property
    def neuron_cores(self) -> int:
        """Cores that share this layer's neurons."""
        return math.ceil(self.n_out / CORE_WIDTH)

    @property
    def n_cores(self) -> int:
        """Physical cores this layer maps onto."""
        return self.fan_in_cores * self.neuron_cores

    def core_slices(self) -> list[tuple[range, range, "LayerConfig"]]:
        """``(input addresses, neurons, core config)`` of each physical core.

        Neuron slice by neuron slice, then input slice by input slice.  The
        first core of each neuron slice (``addresses.start == 0``) is the
        state core: it holds the neurons' state, runs the recurrence and
        phase B.  The others are feed-forward only and send it their partial
        currents.  A layer that fits one core is its own single slice.
        """
        out = []
        for j in range(0, self.n_out, CORE_WIDTH):
            for i in range(0, self.n_in, CORE_WIDTH):
                rows = range(i, min(i + CORE_WIDTH, self.n_in))
                cols = range(j, min(j + CORE_WIDTH, self.n_out))
                core = dataclasses.replace(
                    self,
                    n_in=len(rows),
                    n_out=len(cols),
                    topology=self.topology if i == 0 else Topology.FF,
                )
                out.append((rows, cols, core))
        return out

    @property
    def is_recurrent(self) -> bool:
        return self.topology in (Topology.ATA_F, Topology.ATA_T)

    @property
    def effective_beta(self) -> float:
        # The IF model is the LIF datapath with the CG bypass engaged.
        return 1.0 if self.neuron == NeuronModel.IF else self.beta

    def beta_code(self) -> DecayCode:
        return coeff_gen.encode_decay(self.effective_beta, self.leak_bits)

    def alpha_code(self) -> DecayCode:
        return coeff_gen.encode_decay(self.alpha, self.leak_bits)


class IntLayerParams(NamedTuple):
    """Quantized runtime parameters (the SPI-loaded memories/registers)."""

    w_ff: jax.Array  # int32 [n_in, n_out]
    w_rec: jax.Array  # int32 [n_out, n_out] (ATA-T) | [] scalar (ATA-F) | [0] (FF)
    theta_q: jax.Array  # int32 scalar
    # Decay codes are static python (design/config-time), carried on LayerConfig.


class FloatLayerParams(NamedTuple):
    w_ff: jax.Array  # f32 [n_in, n_out]
    w_rec: jax.Array  # f32 [n_out, n_out] | scalar | [0]
    theta: jax.Array  # f32 scalar


class LayerState(NamedTuple):
    u: jax.Array  # membrane potential  [batch, n_out]
    i_syn: jax.Array  # synaptic current [batch, n_out] (zeros-shaped if unused)
    prev_spk: jax.Array  # this layer's spikes from the previous step [batch, n_out]


def _rec_weight_shape(cfg: LayerConfig):
    if cfg.topology == Topology.ATA_T:
        return (cfg.n_out, cfg.n_out)
    if cfg.topology == Topology.ATA_F:
        return ()  # single shared self-weight register (SPI ALL_TO_ALL_FALSE_WEIGHT)
    return (0,)


def int_layer_init(cfg: LayerConfig, batch: int) -> LayerState:
    # Three distinct buffers, not one shared zeros array: serving donates
    # the lane-carry state, and XLA rejects donating an aliased buffer twice.
    z = lambda: jnp.zeros((batch, cfg.n_out), jnp.int32)
    return LayerState(u=z(), i_syn=z(), prev_spk=z())


def float_layer_init(cfg: LayerConfig, batch: int) -> LayerState:
    z = lambda: jnp.zeros((batch, cfg.n_out), jnp.float32)
    return LayerState(u=z(), i_syn=z(), prev_spk=z())


def _integrate_acc(cfg: LayerConfig, params: IntLayerParams, state: LayerState, ff_acc):
    """Phase A given the step's feed-forward accumulation ``ff_acc``.

    Adds the recurrent contribution (the previous step's own spikes) and
    commits the total into the integration target register.  Saturation is
    applied once, after the full step's accumulation -- int32 addition is
    associative, so any exact method of computing ``ff_acc`` (dense matmul,
    Pallas kernel, sparse gather over active rows) yields identical state.
    """
    acc = ff_acc
    if cfg.topology == Topology.ATA_T:
        with jax.named_scope(RECURRENT_SCOPE):
            acc = acc + jnp.einsum("bi,io->bo", state.prev_spk, params.w_rec)
    elif cfg.topology == Topology.ATA_F:
        acc = acc + state.prev_spk * params.w_rec
    if cfg.neuron == NeuronModel.SYNAPTIC:
        return state.u, saturate(state.i_syn + acc, cfg.i_bits)
    return saturate(state.u + acc, cfg.u_bits), state.i_syn


def int_phase_a(cfg: LayerConfig, params: IntLayerParams, state: LayerState, s_in):
    """Phase A: accumulate weighted spikes into the integration target.

    Public because the QAT straight-through forward (``repro.snn.qat``) runs
    its exact forward values through this code path -- bit-for-bit the
    deployment arithmetic, per phase so the float mirror can attach at every
    intermediate.
    """
    with jax.named_scope(FF_SCOPE):
        ff_acc = jnp.einsum("bi,io->bo", s_in.astype(jnp.int32), params.w_ff)  # int32
    return _integrate_acc(cfg, params, state, ff_acc)


def int_phase_b(cfg: LayerConfig, params: IntLayerParams, u, i_syn, decay_u, decay_i):
    """Phase B (leak / spike / reset), shared by the static and traced steps.

    ``decay_u`` / ``decay_i`` are the CG applications -- the *only* place the
    static-register and traced-register datapaths differ, so this is the
    single copy of the spike/reset/leak numerics.
    """
    if cfg.neuron == NeuronModel.SYNAPTIC:
        u_tmp = saturate(u + i_syn, cfg.u_bits)
    else:
        u_tmp = u

    spk = (u_tmp >= params.theta_q).astype(jnp.int32)
    if cfg.reset == ResetMode.ZERO:
        u_reset = jnp.zeros_like(u_tmp)
    else:
        u_reset = saturate(u_tmp - params.theta_q, cfg.u_bits)
    u_leak = saturate(decay_u(u_tmp), cfg.u_bits)
    u_new = jnp.where(spk == 1, u_reset, u_leak)

    if cfg.neuron == NeuronModel.SYNAPTIC:
        i_new = saturate(decay_i(i_syn), cfg.i_bits)
    else:
        i_new = i_syn

    return LayerState(u=u_new, i_syn=i_new, prev_spk=spk), spk


def int_layer_step(
    cfg: LayerConfig, params: IntLayerParams, state: LayerState, s_in
) -> tuple[LayerState, jax.Array]:
    """One bit-exact hardware time step. Returns (new_state, spikes int32)."""
    beta_code = cfg.beta_code()
    u, i_syn = int_phase_a(cfg, params, state, s_in)
    return int_phase_b(
        cfg,
        params,
        u,
        i_syn,
        lambda x: coeff_gen.apply_decay(x, beta_code),
        lambda x: coeff_gen.apply_decay(x, cfg.alpha_code()),
    )


def int_layer_step_dynamic(
    cfg: LayerConfig,
    params: IntLayerParams,
    state: LayerState,
    s_in,
    beta_register,
    alpha_register,
) -> tuple[LayerState, jax.Array]:
    """Bit-exact step with *traced* DecayRate registers (population DSE path).

    Identical numerics to :func:`int_layer_step`, but the CG registers are jax
    values, so a vmap over candidates (whose ``leak_bits`` differ) compiles to
    one program.  ``beta_register`` / ``alpha_register`` are packed 9-bit
    ``DecayCode.decay_rate_register`` values.
    """
    u, i_syn = int_phase_a(cfg, params, state, s_in)
    return int_phase_b(
        cfg,
        params,
        u,
        i_syn,
        lambda x: coeff_gen.apply_decay_traced(x, beta_register),
        lambda x: coeff_gen.apply_decay_traced(x, alpha_register),
    )


def fused_eligible(cfg: LayerConfig) -> bool:
    """True when a layer's window can run through the fused kernel path.

    The fused path (int spike-weight matmul feeding the ``lif_scan`` Pallas
    kernel) covers the IF/LIF datapath with either reset mode on purely
    feed-forward cores.  Recurrent topologies (the next step's input depends
    on this step's spikes) and the Synaptic model (a second state register)
    stay on the step-major reference semantics.
    """
    return cfg.topology == Topology.FF and cfg.neuron in (
        NeuronModel.IF,
        NeuronModel.LIF,
    )


def int_layer_window(cfg: LayerConfig, params: IntLayerParams, raster) -> jax.Array:
    """Run one layer over a whole window. ``raster``: int [T, batch, n_in].

    Returns the output spike raster int32 [T, batch, n_out].  This is the
    layer-major traversal used by backends that process the network
    core-by-core instead of step-by-step; numerics are exactly
    ``int_layer_step`` iterated over the window.
    """
    state0 = int_layer_init(cfg, raster.shape[1])

    def step(state, s_t):
        state, spk = int_layer_step(cfg, params, state, s_t)
        return state, spk

    _, spikes = jax.lax.scan(step, state0, raster.astype(jnp.int32))
    return spikes


def int_layer_window_carry(
    cfg: LayerConfig, params: IntLayerParams, state: LayerState, ff_currents, live=None
) -> tuple[LayerState, jax.Array]:
    """Carried-state form of :func:`int_layer_window_from_currents`.

    Starts from ``state`` (instead of a fresh init) and returns the state
    after the window alongside the spikes -- the seam for callers that
    advance a layer chunk-by-chunk (the serving engine's lane pool): running
    two consecutive chunks through this function is bit-identical to one
    longer window, which is bit-identical to iterated
    :func:`int_layer_step`.

    ``live`` (optional bool [T, batch]) freezes a batch element's carry once
    its liveness goes False: the step still computes, but the committed state
    is the pre-step state, so the returned carry is *exactly* the state after
    that element's last live step.  This is the chunk-quantisation seam for
    persistent streams: a caller may pad a lane's chunk past its real data
    and still read back a bit-exact carry at the data boundary (padding
    steps would otherwise decay the membrane / advance ``prev_spk``).
    Spikes emitted on dead steps are garbage-but-harmless: downstream
    layers' states are frozen on the same mask, and window callers mask
    recorded outputs.
    """
    beta_code = cfg.beta_code()
    alpha_code = cfg.alpha_code()

    def step(state, inp):
        c_t = inp if live is None else inp[0]
        u, i_syn = _integrate_acc(cfg, params, state, c_t)
        new_state, spk = int_phase_b(
            cfg,
            params,
            u,
            i_syn,
            lambda x: coeff_gen.apply_decay(x, beta_code),
            lambda x: coeff_gen.apply_decay(x, alpha_code),
        )
        if live is not None:
            live_t = inp[1][:, None]  # [batch, 1]
            new_state = jax.tree.map(
                lambda n, o: jnp.where(live_t, n, o), new_state, state
            )
        return new_state, spk

    xs = ff_currents.astype(jnp.int32)
    if live is not None:
        xs = (xs, live)
    return jax.lax.scan(step, state, xs)


def int_layer_window_from_currents(
    cfg: LayerConfig, params: IntLayerParams, ff_currents
) -> jax.Array:
    """Run one layer over a window of *precomputed* FF integration currents.

    ``ff_currents``: int32 [T, batch, n_out], the per-step feed-forward
    accumulation ``s_t @ w_ff`` (however it was computed -- this is the seam
    the event-driven backend uses to feed sparse-gathered currents into the
    exact step dynamics).  The scan adds recurrent contributions and runs
    phase B per step, so *every* neuron model / topology / reset mode is
    covered with numerics identical to :func:`int_layer_step`.
    """
    state0 = int_layer_init(cfg, ff_currents.shape[1])
    _, spikes = int_layer_window_carry(cfg, params, state0, ff_currents)
    return spikes


def _integrate_float(cfg: LayerConfig, params: FloatLayerParams, state: LayerState, s_in):
    acc = jnp.einsum("bi,io->bo", s_in.astype(jnp.float32), params.w_ff)
    if cfg.topology == Topology.ATA_T:
        acc = acc + jnp.einsum("bi,io->bo", state.prev_spk, params.w_rec)
    elif cfg.topology == Topology.ATA_F:
        acc = acc + state.prev_spk * params.w_rec
    if cfg.neuron == NeuronModel.SYNAPTIC:
        return state.u, state.i_syn + acc
    return state.u + acc, state.i_syn


def float_layer_step(
    cfg: LayerConfig,
    params: FloatLayerParams,
    state: LayerState,
    s_in,
    spike_fn,
) -> tuple[LayerState, jax.Array]:
    """Differentiable step with the *same phase ordering* as the hardware.

    ``spike_fn(u - theta)`` must return {0,1} forward with a surrogate
    gradient (see repro.snn.surrogate).  Keeping the hardware's
    decay-or-reset ordering at train time removes the train/deploy semantic
    gap that a vanilla SNN-Torch unrolling would leave.
    """
    beta = cfg.effective_beta
    u, i_syn = _integrate_float(cfg, params, state, s_in)
    u_tmp = u + i_syn if cfg.neuron == NeuronModel.SYNAPTIC else u

    spk = spike_fn(u_tmp - params.theta)
    if cfg.reset == ResetMode.ZERO:
        u_reset = jnp.zeros_like(u_tmp)
    else:
        u_reset = u_tmp - params.theta
    # jax.lax.stop_gradient on the branch selector is implicit: spk already
    # carries the surrogate gradient; mixing via arithmetic keeps it flowing.
    u_new = spk * u_reset + (1.0 - spk) * (beta * u_tmp)

    if cfg.neuron == NeuronModel.SYNAPTIC:
        i_new = cfg.alpha * i_syn
    else:
        i_new = i_syn
    return LayerState(u=u_new, i_syn=i_new, prev_spk=spk), spk
