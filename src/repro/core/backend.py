"""Pluggable inference backends for the Flexi-NeurA simulator.

The simulator exposes one seam -- :class:`InferenceBackend` -- through which
every consumer (training eval, the Flex-plorer DSE, serving, benchmarks)
runs a network.  Three backends ship here:

``reference``
    The paper-faithful step-major simulation: one ``jax.lax.scan`` over time
    steps, each step walking every core via ``int_layer_step`` /
    ``float_layer_step``.  This is the numerics contract.

``fused``
    Layer-major traversal that wires the Pallas kernels into the simulator:
    each eligible core's whole window runs as an exact int spike-weight
    matmul (``repro.kernels.quant_matmul.spike_matmul``) feeding the fused
    membrane scan (``repro.kernels.lif_scan``).  Bit-identical to
    ``reference`` by construction (both reduce to ``int_layer_step``'s
    arithmetic); the parity suite in ``tests/test_backend_parity.py`` holds
    it to that.

``event``
    Layer-major *event-driven* traversal: per layer, only the active
    pre-synaptic rows are gathered and summed (a masked-gather / segment-sum
    over a static event budget sized from the measured spike raster), so
    integration work scales with spike counts, not dense layer size --
    the execution model that underpins the paper's latency/energy story.
    Bit-exact to ``reference`` on every config (int32 accumulation is
    order-independent and the step dynamics are shared); transparently falls
    back to the dense window when a layer's traffic is too dense for the
    sparse path to win.  Three strategies: ``"csr"`` (host scipy, the eager
    CPU champion), ``"gather"`` (jnp masked gather), and ``"pallas"`` (the
    jit-compatible fixed-capacity event path through
    ``repro.kernels.sparse_accum`` -- the one that composes with
    ``shard_map`` and the serving engine's jitted lane tick).

Fused-path coverage matrix (per layer; ineligible layers transparently run
the reference step scan inside the fused traversal, so mixed networks work):

    neuron     topology   reset              fused kernel path?
    ---------  ---------  -----------------  ----------------------------
    IF / LIF   FF         zero / subtract    yes (matmul + lif_scan)
    IF / LIF   ATA_F/T    any                no  (recurrence couples steps)
    SYNAPTIC   any        any                no  (second state register)

The event path instead covers *every* row of that matrix sparsely: the
sparse gather computes only the feed-forward accumulation, and the shared
step scan (``int_layer_window_from_currents``) layers recurrent integration
and phase B on top, so recurrent and Synaptic cores stay on the sparse path.

Layer-major traversal is legal because inter-core traffic is strictly
feed-forward and step-aligned (a spike emitted at step t is consumed by the
next core at its step t); only *intra*-layer recurrence couples consecutive
steps, and those layers stay on the step scan.

Adding a backend: subclass :class:`InferenceBackend`, implement ``run_int``
(and optionally ``run_float``), then ``register_backend("name", Factory)``.
Everything above ``network.run_int`` selects backends by name, so new
execution strategies (multi-core mapping, event-driven, remote) plug in
without touching callers.  A backend that sizes buffers from concrete data
(like ``event``'s csr/gather strategies) sets ``jit_compatible = False``;
callers that would wrap ``run_int`` in their own ``jax.jit`` (e.g.
``eval_int``) then let the backend manage compilation itself, and sharding
callers may ask for a jit-compatible stand-in via ``jit_surrogate`` before
abandoning a mesh.

This module also hosts the population-batched integer simulation used by
the Flex-plorer's population DSE mode: a whole batch of precision
candidates -- same static network structure, different quantized weights,
thresholds and CG decay registers -- runs through one jitted, vmapped
program (``run_int_population``), eliminating the per-candidate
recompile-and-run that dominates serial DSE wall-clock.

The same one-compiled-program-many-lanes idea, batched over *samples*
instead of candidates, is exposed as the serving seam: ``batched_lane_init``
/ ``batched_lane_window`` advance a fixed pool of independent sample lanes
by a chunk of time steps per jitted call (what ``repro.serve.snn_engine``
drives for continuous batching), and ``run_int_batched`` runs a whole
ragged batch of variable-length samples through one jitted scan.  Each lane's
trajectory is bit-exact with a serial single-sample ``run_int``: the step
dynamics are elementwise/matmul over the batch axis, so batching lanes is
semantically a ``jax.vmap`` of the single-sample step.

Both batching axes (samples here, candidates in the population sweep) are
*independent* work, which is what lets ``repro.core.shard`` spread them
across devices bit-exactly -- see that module for the multi-device
execution layer built on these entry points.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lowering
from repro.core.snn_layer import (
    FF_SCOPE,
    IntLayerParams,
    ResetMode,
    fused_eligible,
    float_layer_init,
    float_layer_step,
    int_layer_init,
    int_layer_step,
    int_layer_step_dynamic,
    int_layer_window,
    int_layer_window_carry,
    int_layer_window_from_currents,
)
from repro.kernels.lif_scan.lif_scan import lif_scan
from repro.kernels.lif_scan.ref import lif_scan_ref
from repro.kernels.quant_matmul.spike_matmul import spike_integrate
from repro.kernels.sparse_accum.ops import sparse_accum_currents

__all__ = [
    "SimRecord",
    "InferenceBackend",
    "ReferenceBackend",
    "FusedBackend",
    "EventBackend",
    "register_backend",
    "get_backend",
    "available_backends",
    "check_population_structure",
    "stack_population",
    "run_int_population",
    "batched_lane_init",
    "batched_lane_window",
    "batched_lane_tick",
    "run_int_batched",
]


@dataclasses.dataclass
class SimRecord:
    """Outputs of a full-window simulation.

    spike_counts -- [batch, n_classes] output-layer spike totals (rate code)
    layer_spikes -- list over layers of [T, batch] per-step spike totals
                    (ASPL events *emitted* by that layer; layer l's entry is
                    what layer l+1 integrates at its step t)
    input_events -- [T, batch] per-step ASPL counts into layer 0 (the input
                    raster's active channels; what core 0 integrates)
    lowerings    -- per layer, the name of the lowering the backend ran it
                    through (see ``repro.core.lowering``); ``None`` when the
                    producer does not report it

    Every backend populates the first three fields, so any record can drive
    the event-count-calibrated latency/energy model in
    ``repro.core.hw_model`` (see ``EventTraffic.from_record``).
    """

    spike_counts: jax.Array
    layer_spikes: list[jax.Array]
    input_events: jax.Array | None = None
    lowerings: list[str] | None = None

    def predictions(self):
        return jnp.argmax(self.spike_counts, axis=-1)

    def event_stats(self) -> dict:
        """Batch-mean event traffic: the latency/energy model's inputs.

        Returns ``{"input_events_per_step": [T], "layer_events_per_step":
        list over layers of [T]}`` as numpy arrays (mean over the batch) --
        the same shape ``eval_int(..., return_stats=True)`` aggregates over
        a whole dataset.
        """
        if self.input_events is None:
            raise ValueError("record carries no input_events (legacy record?)")
        return {
            "input_events_per_step": np.asarray(jnp.mean(self.input_events, axis=1)),
            "layer_events_per_step": [
                np.asarray(jnp.mean(s, axis=1)) for s in self.layer_spikes
            ],
        }

    def total_events_per_image(self) -> float:
        """Mean events per sample over the whole window (input + emitted)."""
        if self.input_events is None:
            raise ValueError("record carries no input_events (legacy record?)")
        total = jnp.sum(jnp.mean(self.input_events, axis=1))
        for s in self.layer_spikes:
            total = total + jnp.sum(jnp.mean(s, axis=1))
        return float(total)


_STEP_SCAN = "step-scan"  # the reference per-step walk (int_layer_step)


def _run_step_major(net, params, spikes_in, init_fn, step_fn) -> SimRecord:
    """Step-major simulation: scan over time, walk the cores inside."""
    batch = spikes_in.shape[1]
    states = [init_fn(cfg, batch) for cfg in net.layers]

    def one_step(states, s_t):
        new_states = []
        x = s_t
        emitted = []
        for cfg, p, st in zip(net.layers, params, states):
            st, x = step_fn(cfg, p, st, x)
            new_states.append(st)
            emitted.append(jnp.sum(x, axis=-1))  # events per sample this step
        return new_states, (x, jnp.stack(emitted, axis=0))

    states, (out_spikes, emitted) = jax.lax.scan(one_step, states, spikes_in)
    counts = jnp.sum(out_spikes, axis=0)
    layer_spikes = [emitted[:, i, :] for i in range(len(net.layers))]
    input_events = jnp.sum(spikes_in != 0, axis=-1)
    return SimRecord(
        spike_counts=counts,
        layer_spikes=layer_spikes,
        input_events=input_events,
        lowerings=[_STEP_SCAN] * len(net.layers),
    )


class InferenceBackend:
    """One execution strategy for a full-window network simulation."""

    name = "base"
    #: True when ``run_int`` may be traced under a caller's ``jax.jit``.
    #: Backends that size buffers from concrete data (event-driven) set this
    #: False and manage jit compilation internally; callers like ``eval_int``
    #: check it before wrapping.
    jit_compatible = True

    def run_int(self, net, qparams: Sequence[IntLayerParams], spikes_in) -> SimRecord:
        raise NotImplementedError

    def run_float(self, net, params, spikes_in, spike_fn) -> SimRecord:
        raise NotImplementedError

    def jit_surrogate(self, net, spikes_in) -> "InferenceBackend | None":
        """A jit-compatible stand-in carrying this backend's numerics, or None.

        Sharding callers (``run_int_sharded``) ask for one before abandoning
        a multi-device mesh on a ``jit_compatible = False`` backend; returning
        ``None`` means the backend is irreplaceably host-side and the caller
        should fall back to the serial path.
        """
        return None


class ReferenceBackend(InferenceBackend):
    """Step-major jnp semantics -- the numerics contract for every backend."""

    name = "reference"

    # The reference backend has no configuration knobs, so any two
    # instances are interchangeable: compare (and hash) by value so callers
    # passing an explicit ReferenceBackend() are recognised as the default
    # (the population-mode warning in explore_snn keys off this).
    def __eq__(self, other) -> bool:
        return type(other) is type(self)

    def __hash__(self) -> int:
        return hash((type(self).__module__, type(self).__qualname__))

    def run_int(self, net, qparams, spikes_in) -> SimRecord:
        return _run_step_major(
            net, list(qparams), spikes_in.astype(jnp.int32), int_layer_init, int_layer_step
        )

    def run_float(self, net, params, spikes_in, spike_fn) -> SimRecord:
        def step(cfg, p, st, x):
            return float_layer_step(cfg, p, st, x, spike_fn)

        return _run_step_major(
            net, list(params), spikes_in.astype(jnp.float32), float_layer_init, step
        )


class FusedBackend(InferenceBackend):
    """Layer-major traversal through the fused integration + membrane kernels.

    ``use_pallas`` selects the Pallas kernels (default: only on TPU; the
    pure-jnp window oracle carries the identical numerics elsewhere, which
    keeps CPU/GPU runs fast -- interpret-mode Pallas is a debugging tool,
    not a fast path).  The parity suite uses ``use_pallas=True`` to hold
    the *actual kernels* to the bit-exact contract on CPU, where they run
    in interpret mode (``interpret`` may only restate that; see
    ``repro.core.lowering.interpret``).

    Each eligible layer's integration takes ``lowering.mxu_feed(w_bits,
    max_val)``: layer 0's ``max_val`` is measured from a concrete raster
    (and picked on the device from a traced one), deeper layers integrate
    {0,1} phase-B spikes.  The membrane scan is the ``lif_scan`` kernel,
    except under traced weights (a ``vmap`` over candidates): the kernel
    needs a static threshold, so those layers run the jnp oracle
    ``lif_scan_ref`` -- named in the record's ``lowerings``.
    """

    name = "fused"

    def __init__(
        self,
        use_pallas: bool | None = None,
        interpret: bool | None = None,
        block_b: int = 8,
        block_n: int = 128,
    ):
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.block_b = block_b
        self.block_n = block_n

    def _pallas_enabled(self) -> bool:
        if self.use_pallas is None:
            return lowering.on_tpu()
        return self.use_pallas

    def _fused_layer_window(self, cfg, p: IntLayerParams, raster, max_val):
        """Whole-window spikes for one FF IF/LIF core via the kernel pair.

        Returns ``(spikes, lowering name)``."""
        use_pallas = self._pallas_enabled()
        interpret = lowering.interpret(self.interpret) if use_pallas else False
        feed = lowering.mxu_feed(cfg.w_bits, max_val) if use_pallas else lowering.XLA_INT32
        with jax.named_scope(FF_SCOPE):
            currents = spike_integrate(
                raster,
                p.w_ff,
                w_bits=cfg.w_bits,
                max_val=max_val,
                use_pallas=use_pallas,
                interpret=interpret,
            )
        code = cfg.beta_code()
        decay_k = 256 if code.bypass else code.k
        reset_to_zero = cfg.reset == ResetMode.ZERO
        try:
            theta_q = int(p.theta_q)  # static for the Pallas kernel
        except (
            jax.errors.TracerIntegerConversionError,
            jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError,
        ):
            theta_q = None  # traced weights (e.g. under vmap): oracle only
        if theta_q is None or not use_pallas:
            theta = p.theta_q if theta_q is None else theta_q
            spikes, _ = lif_scan_ref(currents, theta, decay_k, cfg.u_bits, reset_to_zero)
            return spikes, f"{feed}+lif_scan_ref"
        spikes, _ = lif_scan(
            currents,
            theta_q=theta_q,
            decay_k=decay_k,
            u_bits=cfg.u_bits,
            reset_to_zero=reset_to_zero,
            block_b=self.block_b,
            block_n=self.block_n,
            interpret=interpret,
        )
        return spikes, f"{feed}+lif_scan"

    def run_int(self, net, qparams, spikes_in) -> SimRecord:
        x = spikes_in.astype(jnp.int32)
        input_events = jnp.sum(x != 0, axis=-1)
        # layer 0's largest input, where the host can see it; phase B emits {0,1}
        max_val = (
            None
            if isinstance(x, jax.core.Tracer) or not self._pallas_enabled()
            else int(jnp.max(jnp.abs(x)))
        )
        emitted, names = [], []
        for cfg, p in zip(net.layers, qparams):
            if fused_eligible(cfg):
                x, name = self._fused_layer_window(cfg, p, x, max_val)
            else:
                x, name = int_layer_window(cfg, p, x), _STEP_SCAN
            emitted.append(jnp.sum(x, axis=-1))  # [T, batch]
            names.append(name)
            max_val = 1
        counts = jnp.sum(x, axis=0)
        return SimRecord(
            spike_counts=counts,
            layer_spikes=emitted,
            input_events=input_events,
            lowerings=names,
        )

    def run_float(self, net, params, spikes_in, spike_fn) -> SimRecord:
        # The fused kernels are integer-only; float (training) simulation
        # keeps the differentiable reference semantics.
        return ReferenceBackend().run_float(net, params, spikes_in, spike_fn)


# ---------------------------------------------------------------------------
# Event-driven backend: work scales with spike counts, not dense layer size
# ---------------------------------------------------------------------------

try:  # the host CSR strategy wants scipy's C sparse kernels; optional
    import scipy.sparse as _scipy_sparse
except ImportError:  # pragma: no cover - scipy ships with jax, but stay safe
    _scipy_sparse = None


def _round_capacity(k: int, multiple: int = 16) -> int:
    """Round an event budget up to a lane-aligned multiple (bounds the
    number of distinct compiled programs and keeps the gather shapes
    vector-unit/Pallas friendly)."""
    return max(multiple, ((k + multiple - 1) // multiple) * multiple)


def _gather_currents(raster, w_ff, k_active: int):
    """Sparse FF integration: sum only the active pre-synaptic weight rows.

    ``raster`` int32 [T, B, n_in]; ``k_active`` is a static per-window event
    budget >= the max active-channel count of any (t, b).  ``top_k`` on the
    spike vector compacts the active source addresses to the front (the
    returned values double as the per-lane spike values, so over-budget
    lanes contribute exact zeros), then the masked gather-and-sum computes
    ``s_t @ w_ff`` touching k_active rows instead of n_in.  int32 addition
    is order-independent, so the result is bit-identical to the dense
    einsum for any sufficient budget.
    """
    T, B, n_in = raster.shape
    flat = raster.reshape(T * B, n_in).astype(jnp.int32)
    vals, idx = jax.lax.top_k(flat, k_active)  # per-lane values: 0 = padding
    rows = w_ff[idx]  # [T*B, k_active, n_out] gather of active rows
    currents = jnp.einsum("ek,eko->eo", vals, rows.astype(jnp.int32))
    return currents.reshape(T, B, -1)


def _csr_currents(
    raster: np.ndarray,
    w_ff: np.ndarray,
    active: np.ndarray,
    row_counts: np.ndarray,
) -> np.ndarray:
    """Host-side sparse FF integration through scipy's C CSR kernel.

    ``np.flatnonzero`` on the (caller-precomputed) activity mask *is* the
    CSR column structure (row-major order) and the per-row event counts
    *are* the indptr, so assembly is one C pass plus O(nnz) address
    arithmetic; the CSR x dense product then costs O(nnz * n_out) -- true
    event-count-proportional work.  Exact int32, same wraparound semantics
    as the dense einsum.
    """
    T, B, n_in = raster.shape
    rows = T * B
    nz = np.flatnonzero(active)
    c = (nz % n_in).astype(np.int32)
    data = np.ascontiguousarray(raster).reshape(-1)[nz].astype(np.int32, copy=False)
    indptr = np.zeros(rows + 1, np.int64)
    np.cumsum(row_counts.reshape(-1), out=indptr[1:])
    mat = _scipy_sparse.csr_matrix((data, c, indptr), shape=(rows, n_in))
    currents = np.asarray(mat @ w_ff.astype(np.int32, copy=False), np.int32)
    return currents.reshape(T, B, -1)


@functools.partial(jax.jit, static_argnames=("cfg", "k_active"))
def _event_layer_window(cfg, params: IntLayerParams, raster, k_active: int):
    with jax.named_scope(FF_SCOPE):
        currents = _gather_currents(raster, params.w_ff, k_active)
    return int_layer_window_from_currents(cfg, params, currents)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _phase_b_window(cfg, params: IntLayerParams, currents):
    return int_layer_window_from_currents(cfg, params, currents)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _dense_layer_window(cfg, params: IntLayerParams, raster):
    """Density fallback: whole-window flat dense integration (one einsum
    over [T*B, n_in], the fused backend's shape) feeding the same step scan
    -- so even the fallback beats the step-major reference on wall-clock."""
    with jax.named_scope(FF_SCOPE):
        currents = spike_integrate(raster, params.w_ff)
    return int_layer_window_from_currents(cfg, params, currents)


@functools.partial(jax.jit, static_argnames=("cfg", "budget", "how", "interpret"))
def _fixed_layer_window(cfg, params: IntLayerParams, raster, budget, how, interpret):
    """One layer's window for the pallas strategy.

    ``how`` (static) is the layer's lowering: ``lowering.PALLAS_SPARSE``
    scatters the fixed-capacity event list at ``budget`` through the Pallas
    kernel; ``lowering.F32`` / ``lowering.XLA_INT32`` integrate densely
    (the caller certified ``F32`` against the budget, or against ``n_in``
    on the density fallback).  Traceable end to end -- this is the layer
    window the pallas strategy runs under an outer ``jax.jit`` /
    ``shard_map``.
    """
    with jax.named_scope(FF_SCOPE):
        if how == lowering.PALLAS_SPARSE:
            currents = sparse_accum_currents(
                raster, params.w_ff, budget, use_pallas=True, interpret=interpret
            )
        elif how == lowering.F32:
            currents = lowering.f32_currents(raster, params.w_ff)
        else:
            currents = spike_integrate(raster, params.w_ff)
    return int_layer_window_from_currents(cfg, params, currents)


class EventBackend(InferenceBackend):
    """Event-driven layer-major traversal: integrate active rows, skip silence.

    Per layer, only the active pre-synaptic rows contribute to the window's
    feed-forward integration; the shared step scan
    (``int_layer_window_from_currents``) then applies recurrent integration
    and phase B.  Work and memory traffic scale with spike counts -- the
    same contract the hardware's AER pipeline (and the latency model in
    ``hw_model``) obeys.  Two sparse strategies carry identical numerics:

    ``"gather"``
        The jnp masked-gather formulation: ``top_k`` compacts active source
        addresses into a static event budget sized from the *measured* max
        per-step event count (lane-rounded, see ``_round_capacity``), then a
        masked gather-and-sum touches budget rows instead of n_in.  Fully
        jit-compiled; the shape XLA:TPU / a Pallas kernel wants.

    ``"csr"``
        Host-side CSR x dense product through scipy's C kernel: O(nnz *
        n_out) work.  On CPU, XLA's gather/scatter lower to code that loses
        to its own dense matmul even at 5% density, so this is the strategy
        that actually realises the event-driven win there.  Host-side by
        construction: ``jit_compatible = False``, raises under tracing.

    ``"pallas"``
        The jit-compatible fixed-capacity event path
        (``repro.kernels.sparse_accum``): the raster is AER-encoded into a
        static, lane-rounded event budget and scattered through the Pallas
        kernel on TPU; off-TPU the identical int32 numerics run through the
        budget-certified exact-f32 BLAS lowering (or the int einsum when
        the certificate fails), so the strategy stays *faster than the
        dense int path* while remaining a single traceable program.  This
        is the strategy that survives ``jax.jit`` / ``shard_map`` / the
        serving engine's jitted lane tick: ``jit_compatible = True``.

    ``"auto"`` (default) picks ``gather`` on TPU and ``csr`` elsewhere when
    scipy is available -- the eager champions -- and promotes to
    ``"pallas"`` whenever ``run_int`` is invoked under tracing, so
    ``backend="event"`` composes with outer ``jax.jit`` / ``vmap`` without
    losing sparsity.

    ``event_budget`` (optional static int) pins the layer-0 event budget for
    the traced pallas path, where there are no concrete spike counts to
    measure; unset, tracing uses full capacity for safety and eager runs
    measure per layer.  It is a *capacity contract*: callers guarantee no
    (step, sample) row carries more active channels than the budget (the
    serving engine enforces this at admission; ``jit_surrogate`` measures it
    from the concrete rasters).  ``input_max_val`` (static int, default 1 =
    binary spike rasters, the repo-wide raster contract) bounds input values
    for the same traced path: together with the budget it certifies the
    exact-f32 lowering off-TPU (``lowering.f32_exact(w_bits,
    input_max_val, budget)``); where it cannot certify, the exact int
    einsum runs instead.  Deeper layers need no declaration -- phase-B spikes
    are {0,1}, which certifies every supported core size.

    Bit-exact to ``reference`` on every neuron model x topology x reset mode
    (asserted by the parity suite): both strategies compute the identical
    int32 feed-forward sum -- int32 addition is order-independent, and
    saturation only applies after the full step's accumulation -- and the
    dynamics reuse the reference step numerics.  Two transparent fallbacks
    keep the contract without a perf cliff:

    * density: a layer whose event budget exceeds ``dense_threshold * n_in``
      runs the dense window instead (sparse indirection loses to the dense
      matmul well below 100% density);
    * tracing: under an outer ``jax.jit`` / ``vmap`` the csr and gather
      strategies have no concrete spike counts to size buffers from, so
      ``auto`` (and ``gather``) promote to the fixed-capacity pallas path
      -- still bit-exact, still one compiled program.  An *explicitly*
      selected ``csr`` raises instead: host-side scipy cannot trace.
    """

    name = "event"
    jit_compatible = False  # class default; pallas instances override below

    def __init__(
        self,
        strategy: str = "auto",
        dense_threshold: float = 0.34,
        capacity_multiple: int = 16,
        event_budget: int | None = None,
        input_max_val: int = 1,
        use_pallas: bool | None = None,
        interpret: bool | None = None,
    ):
        if strategy not in ("auto", "gather", "csr", "pallas"):
            raise ValueError(f"unknown event strategy {strategy!r}")
        if strategy == "csr" and _scipy_sparse is None:
            raise ValueError("event strategy 'csr' needs scipy installed")
        if not 0.0 < dense_threshold <= 1.0:
            raise ValueError(f"dense_threshold must be in (0, 1], got {dense_threshold}")
        if not isinstance(capacity_multiple, int) or capacity_multiple < 1:
            raise ValueError(f"capacity_multiple must be a positive int, got {capacity_multiple}")
        if event_budget is not None and (not isinstance(event_budget, int) or event_budget < 1):
            raise ValueError(f"event_budget must be a positive int or None, got {event_budget}")
        if not isinstance(input_max_val, int) or input_max_val < 1:
            raise ValueError(f"input_max_val must be a positive int, got {input_max_val}")
        self.strategy = strategy
        self.dense_threshold = dense_threshold
        self.capacity_multiple = capacity_multiple
        self.event_budget = event_budget
        self.input_max_val = input_max_val
        self.use_pallas = use_pallas
        self.interpret = interpret
        # The fixed-capacity path is one traceable program; the measured
        # eager strategies are not.
        self.jit_compatible = strategy == "pallas"

    # Value identity: backend instances ride through ``jax.jit`` static
    # arguments (shard_map, the sharded eval path), so equal configurations
    # must hash equal or every fresh instance would recompile the world.
    def _static_key(self):
        return (
            self.strategy,
            self.dense_threshold,
            self.capacity_multiple,
            self.event_budget,
            self.input_max_val,
            self.use_pallas,
            self.interpret,
        )

    def __eq__(self, other):
        return isinstance(other, EventBackend) and self._static_key() == other._static_key()

    def __hash__(self):
        return hash(self._static_key())

    def resolved_strategy(self, traced: bool = False) -> str:
        if self.strategy != "auto":
            return self.strategy
        if traced:
            return "pallas"
        if lowering.on_tpu() or _scipy_sparse is None:
            return "gather"
        return "csr"

    def _budget(self, x_counts_max: int, cfg) -> int:
        return min(cfg.n_in, _round_capacity(x_counts_max, self.capacity_multiple))

    def static_budget(self, n_in: int, k_max: int | None = None) -> int:
        """The static lane-rounded event budget for a layer of width ``n_in``.

        Priority: the configured ``event_budget`` (lane-rounded, capped at
        ``n_in``), else the measured ``k_max``, else full capacity (the safe
        traced default: every lowering stays exact, sparsity is just not
        exploited until a budget is declared or measured).
        """
        if self.event_budget is not None:
            k = self.event_budget
        elif k_max is not None:
            k = k_max
        else:
            return n_in
        return min(n_in, _round_capacity(k, self.capacity_multiple))

    def serve_budget(self, n_in: int, admission_threshold: float) -> int:
        """The event budget a serving engine compiles its sparse lane program at.

        The configured ``event_budget`` wins; otherwise 2x the admission
        density (lane-rounded) -- room for a request's max *step* to run
        twice as hot as its admission-checked *mean* without re-routing.
        """
        if self.event_budget is not None:
            return self.static_budget(n_in)
        k = max(1, int(2 * admission_threshold * n_in))
        return min(n_in, _round_capacity(k, self.capacity_multiple))

    def _fixed_lowering(self, cfg, budget: int | None, max_val: int) -> str:
        """The pallas strategy's lowering for one layer: the sparse kernel
        where it runs (on TPU, or forced by ``use_pallas``), else the f32
        matmul where ``lowering.f32_exact`` certifies it against the budget
        (against ``n_in`` on the density fallback), else the int32 dot."""
        kernel = lowering.on_tpu() if self.use_pallas is None else self.use_pallas
        if budget is not None and kernel:
            return lowering.PALLAS_SPARSE
        rows = cfg.n_in if budget is None else min(budget, cfg.n_in)
        if lowering.f32_exact(cfg.w_bits, max_val, rows):
            return lowering.F32
        return lowering.XLA_INT32

    def run_int(self, net, qparams, spikes_in) -> SimRecord:
        x = jnp.asarray(spikes_in)
        traced = isinstance(x, jax.core.Tracer)
        strategy = self.resolved_strategy(traced=traced)
        if traced and strategy == "csr":
            raise ValueError(
                "event strategy 'csr' is host-side (scipy) and cannot run under "
                "jit/vmap tracing; use strategy='pallas' (the jit-compatible "
                "fixed-capacity path) or call it eagerly"
            )
        x = x.astype(jnp.int32)
        if strategy == "csr":
            return self._run_int_csr(net, qparams, np.asarray(x))
        if strategy == "pallas" or traced:
            return self._run_int_fixed(net, qparams, x, traced)
        input_events = jnp.sum(x != 0, axis=-1)
        emitted, names = [], []
        for cfg, p in zip(net.layers, qparams):
            k_max = int(jnp.max(jnp.sum(x != 0, axis=-1)))  # concrete: host value
            k = self._budget(k_max, cfg)
            if k > self.dense_threshold * cfg.n_in:
                x = _dense_layer_window(cfg, p, x)
                names.append(lowering.XLA_INT32)
            else:
                x = _event_layer_window(cfg, p, x, k)
                names.append(f"gather@{k}")
            emitted.append(jnp.sum(x, axis=-1))  # [T, batch]
        counts = jnp.sum(x, axis=0)
        return SimRecord(
            spike_counts=counts,
            layer_spikes=emitted,
            input_events=input_events,
            lowerings=names,
        )

    def _run_int_fixed(self, net, qparams, x, traced: bool) -> SimRecord:
        """The fixed-capacity (pallas-strategy) traversal.

        Eager runs measure per-layer budgets and input magnitude exactly as
        the gather strategy does; traced runs take the static budget
        (``static_budget``) and the declared ``input_max_val`` for layer 0,
        full capacity for deeper layers (phase-B spikes are {0,1}, so the
        f32 certificate holds at any supported size).  Either way every
        layer is one traceable ``_fixed_layer_window`` call -- the whole run
        composes with an outer ``jax.jit`` / ``shard_map``.
        """
        input_events = jnp.sum(x != 0, axis=-1)
        emitted, names = [], []
        max_val = self.input_max_val if traced else max(1, int(jnp.max(x)))
        for i, (cfg, p) in enumerate(zip(net.layers, qparams)):
            if traced:
                budget = self.static_budget(cfg.n_in) if i == 0 else cfg.n_in
            else:
                k_max = int(jnp.max(jnp.sum(x != 0, axis=-1)))
                budget = self.static_budget(cfg.n_in, k_max=k_max)
            if budget > self.dense_threshold * cfg.n_in:
                budget = None  # density fallback: dense lowering, same numerics
            how = self._fixed_lowering(cfg, budget, max_val)
            sparse = how == lowering.PALLAS_SPARSE
            interpret = lowering.interpret(self.interpret) if sparse else False
            x = _fixed_layer_window(cfg, p, x, budget, how, interpret)
            emitted.append(jnp.sum(x, axis=-1))  # [T, batch]
            names.append(f"{how}@{budget}" if sparse else how)
            max_val = 1  # phase B emits {0,1}
        counts = jnp.sum(x, axis=0)
        return SimRecord(
            spike_counts=counts,
            layer_spikes=emitted,
            input_events=input_events,
            lowerings=names,
        )

    def jit_surrogate(self, net, spikes_in) -> "EventBackend | None":
        """A pallas-strategy twin for sharding callers, or None for csr.

        ``auto``/``gather``/``pallas`` all carry identical numerics through
        the fixed-capacity path, so a mesh partition need not be abandoned:
        the surrogate pins the layer-0 budget (configured, else measured
        from the concrete rasters -- lane-rounding bounds the number of
        distinct compiled programs) and the measured input magnitude.  An
        *explicit* ``csr`` selection is an opt-in to the host-side path and
        returns None: the caller warns and runs serially.
        """
        if self.strategy == "csr":
            return None
        budget = self.event_budget
        input_max_val = self.input_max_val
        x = jnp.asarray(spikes_in)
        if not isinstance(x, jax.core.Tracer):
            if budget is None:
                budget = max(1, int(jnp.max(jnp.sum(x != 0, axis=-1))))
            input_max_val = max(input_max_val, int(jnp.max(x)))
        return EventBackend(
            strategy="pallas",
            dense_threshold=self.dense_threshold,
            capacity_multiple=self.capacity_multiple,
            event_budget=budget,
            input_max_val=input_max_val,
            use_pallas=self.use_pallas,
            interpret=self.interpret,
        )

    def _run_int_csr(self, net, qparams, x: np.ndarray) -> SimRecord:
        """Host-driven traversal: numpy event bookkeeping, scipy CSR
        integration, jitted phase-B scans.  On the CPU jax backend the
        host/device handoffs are zero-copy, so the only real work is the
        activity pass (the AER encoder's job), the O(nnz * n_out) sparse
        product, and the phase-B scan."""
        active = x != 0  # [T, batch, n_in] byte mask, reused by the CSR build
        counts = active.sum(axis=-1)  # [T, batch]
        input_events = counts
        emitted, names = [], []
        for cfg, p in zip(net.layers, qparams):
            k = self._budget(int(counts.max(initial=0)), cfg)
            if k > self.dense_threshold * cfg.n_in:
                x = np.asarray(_dense_layer_window(cfg, p, jnp.asarray(x)))
                active = x != 0
                counts = active.sum(axis=-1)
                names.append(lowering.XLA_INT32)
            else:
                names.append("csr")
                currents = _csr_currents(x, np.asarray(p.w_ff), active, counts)
                x = np.asarray(_phase_b_window(cfg, p, jnp.asarray(currents)))
                # phase B emits {0,1}: the spike raster is its own mask and
                # its sum doubles as the next layer's event count
                active = x
                counts = x.sum(axis=-1)
            emitted.append(counts)
        return SimRecord(
            spike_counts=jnp.asarray(x.sum(axis=0)),
            layer_spikes=[jnp.asarray(e) for e in emitted],
            input_events=jnp.asarray(input_events),
            lowerings=names,
        )

    def run_float(self, net, params, spikes_in, spike_fn) -> SimRecord:
        # Float (training) simulation keeps the differentiable reference
        # semantics; sparsity games don't pay off under surrogate gradients.
        return ReferenceBackend().run_float(net, params, spikes_in, spike_fn)


_REGISTRY: dict[str, Callable[[], InferenceBackend]] = {}


def register_backend(name: str, factory: Callable[[], InferenceBackend]) -> None:
    """Register a backend factory under ``name`` (later wins, like a config)."""
    _REGISTRY[name] = factory


def get_backend(backend: str | InferenceBackend) -> InferenceBackend:
    """Resolve a backend selector: a registered name or an instance."""
    if isinstance(backend, InferenceBackend):
        return backend
    try:
        return _REGISTRY[backend]()
    except KeyError:
        raise ValueError(
            f"unknown inference backend {backend!r}; available: {available_backends()}"
        ) from None


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


register_backend("reference", ReferenceBackend)
register_backend("fused", FusedBackend)
register_backend("event", EventBackend)


# ---------------------------------------------------------------------------
# Population-batched integer simulation (the Flex-plorer DSE hot path)
# ---------------------------------------------------------------------------


# Layer fields a population sweep may vary per candidate: they only reach the
# traced program through quantized values / decay registers.  Everything else
# is static (baked into the one compiled program) and must match the base net.
_POPULATION_KNOBS = ("w_bits", "w_rec_bits", "leak_bits", "beta", "alpha")


def check_population_structure(base, nets) -> None:
    """Raise unless every candidate shares ``base``'s static structure."""
    base_sig = [
        {f.name: getattr(lc, f.name) for f in dataclasses.fields(lc) if f.name not in _POPULATION_KNOBS}
        for lc in base.layers
    ]
    for net in nets:
        if len(net.layers) != len(base.layers):
            raise ValueError(
                f"population candidate {net.name!r} has {len(net.layers)} layers, base has {len(base.layers)}"
            )
        for i, lc in enumerate(net.layers):
            for name, want in base_sig[i].items():
                got = getattr(lc, name)
                if got != want:
                    raise ValueError(
                        f"population candidate {net.name!r} layer {i} differs from the "
                        f"base net in static field {name!r} ({got!r} != {want!r}); only "
                        f"{_POPULATION_KNOBS} may vary across a population sweep"
                    )


def _population_registers(nets) -> np.ndarray:
    """Host int32 ``[P, 2, n_layers]``: each candidate's packed beta and
    alpha DecayRate registers (pure config arithmetic, no device work)."""
    return np.asarray(
        [
            [
                [cfg.beta_code().decay_rate_register for cfg in net.layers],
                [cfg.alpha_code().decay_rate_register for cfg in net.layers],
            ]
            for net in nets
        ],
        np.int32,
    )


@functools.partial(jax.jit, static_argnames=("pad", "flat"))
def _stack_population_jit(qparams_list, regs, pad: int = 0, flat: bool = False):
    """The whole population build as one program.

    Stacks every leaf of ``qparams_list`` (P per-candidate parameter lists)
    along a new leading candidate axis and splits ``regs`` (see
    :func:`_population_registers`) into the beta and alpha registers.
    ``pad`` repeats the last candidate that many times (a mesh's shard
    remainder); ``flat`` packs every output into one int32 ``[P + pad, F]``
    buffer, so a mesh placement moves the population in one transfer.
    """
    out = (jax.tree.map(lambda *xs: jnp.stack(xs), *qparams_list), regs[:, 0], regs[:, 1])
    if pad:
        out = jax.tree.map(lambda a: jnp.concatenate([a, jnp.repeat(a[-1:], pad, axis=0)]), out)
    if flat:
        return jnp.concatenate([a.reshape(a.shape[0], -1) for a in jax.tree.leaves(out)], axis=1)
    return out


def stack_population(nets, qparams_list):
    """Stack per-candidate quantized parameters for a vmapped evaluation.

    ``nets`` are per-candidate :class:`NetworkConfig`s sharing one static
    structure (layer count/shapes/neuron/topology/reset/register widths --
    exactly what the DSE holds fixed while varying ``w_bits`` /
    ``w_rec_bits`` / ``leak_bits``); ``qparams_list`` the matching
    ``quantize_params`` outputs.  Returns ``(stacked_qparams, beta_regs,
    alpha_regs)`` where each stacked leaf gains a leading candidate axis and
    the decay registers are int32 ``[P, n_layers]`` packed DecayRate values.

    The population is built by one program (``_stack_population_jit``),
    with the decay registers computed on the host and carried in with it,
    on the default device; ``repro.core.shard.stack_population_sharded``
    runs the same build and places it on a mesh once.
    """
    return _stack_population_jit([list(qp) for qp in qparams_list], _population_registers(nets))


def _run_int_dynamic(net, qparams, beta_regs, alpha_regs, spikes_in):
    """One candidate's bit-exact run with traced decay registers.

    Numerically identical to ``ReferenceBackend.run_int`` (the dynamic step
    gates the same shift taps arithmetically); exists so the decay registers
    can differ across vmapped candidates.  Returns ``(spike_counts [batch,
    n_classes], emitted [T, n_layers, batch])`` -- the emitted per-step event
    totals feed the event-aware DSE cost model.
    """
    batch = spikes_in.shape[1]
    states = [int_layer_init(cfg, batch) for cfg in net.layers]

    def one_step(states, s_t):
        new_states = []
        x = s_t
        emitted = []
        for i, (cfg, p, st) in enumerate(zip(net.layers, qparams, states)):
            st, x = int_layer_step_dynamic(cfg, p, st, x, beta_regs[i], alpha_regs[i])
            new_states.append(st)
            emitted.append(jnp.sum(x, axis=-1))
        return new_states, (x, jnp.stack(emitted, axis=0))

    _, (out_spikes, emitted) = jax.lax.scan(one_step, states, spikes_in)
    return jnp.sum(out_spikes, axis=0), emitted  # [batch, n_classes], [T, L, batch]


def run_int_population(
    net, stacked_qparams, beta_regs, alpha_regs, spikes_in, return_events: bool = False
):
    """Score P precision candidates in one vmapped sweep.

    ``spikes_in`` int [T, batch, n_in] is shared by all candidates (the DSE
    evaluates every candidate on the same held-out batch).  Returns int32
    spike counts [P, batch, n_classes]; with ``return_events``, also the
    per-candidate emitted event totals [P, T, n_layers, batch] (each
    candidate quantizes differently, so its event traffic -- and therefore
    its modeled latency/energy -- differs too).
    """
    spikes_in = spikes_in.astype(jnp.int32)

    def one(qp, beta, alpha):
        return _run_int_dynamic(net, qp, beta, alpha, spikes_in)

    counts, emitted = jax.vmap(one, in_axes=(0, 0, 0))(
        stacked_qparams, beta_regs, alpha_regs
    )
    if return_events:
        return counts, emitted
    return counts


# ---------------------------------------------------------------------------
# Batched lane stepping (the SNN serving engine's hot path)
# ---------------------------------------------------------------------------


def batched_lane_init(net, n_lanes: int) -> list:
    """Fresh per-layer states for a pool of ``n_lanes`` independent lanes.

    A *lane* holds one in-flight sample; lanes never interact (every step
    operation is elementwise or a matmul over the batch axis), so a pool of
    lanes at different local time steps evolves each lane exactly as a
    serial single-sample run would.
    """
    return [int_layer_init(cfg, n_lanes) for cfg in net.layers]


def lane_state_take(states, lane: int) -> list:
    """Snapshot one lane's per-layer carry out of a pool (host copy).

    The preemption seam: ``states`` is the pool from
    :func:`batched_lane_init` / :func:`batched_lane_window`; the returned
    per-layer :class:`LayerState` slices (numpy, detached from the pool's
    donated buffers) hold everything lane ``lane``'s trajectory needs to
    resume later -- membrane, synaptic current, previous spikes.  Restoring
    them with :func:`lane_state_put` and continuing the window from the
    same local step is bit-exact with an uninterrupted run (lanes never
    interact, so a lane's carry *is* its full simulation state).
    """
    return jax.tree.map(lambda a: np.asarray(a[lane]), states)


def lane_state_put(states, lane: int, carry) -> list:
    """Write a :func:`lane_state_take` snapshot back into a pool at
    ``lane`` (any slot -- the carry is placement-independent).  Returns the
    new pool states; other lanes are untouched."""
    return jax.tree.map(
        lambda a, v: a.at[lane].set(jnp.asarray(v, a.dtype)), states, carry
    )


@functools.partial(jax.jit, static_argnames=("net", "ff_mode", "event_budget"))
def batched_lane_window(
    net,
    qparams,
    states,
    x_chunk,
    reset_mask,
    valid_steps=None,
    ff_mode="int32",
    event_budget=None,
):
    """Advance every lane by ``k`` time steps through the whole core stack.

    ``states``   -- list over layers of per-lane :class:`LayerState` (from
                    :func:`batched_lane_init`);
    ``x_chunk``  -- int [k, n_lanes, n_in], each active lane's raster
                    slice starting at its *own* local step (inactive lanes
                    and steps past a lane's window: zeros);
    ``reset_mask`` -- bool [n_lanes], lanes newly admitted since the last
                    call; their state is zeroed (== ``int_layer_init``)
                    before stepping, so admission never perturbs a lane's
                    bit-exact trajectory and freed lanes can be reused
                    immediately (continuous batching);
    ``valid_steps`` -- optional int [n_lanes]: per lane, how many of the
                    chunk's steps fall inside its own window.  Recorded
                    outputs are masked past a lane's validity (residual
                    membrane charge could otherwise keep firing on
                    zero-input padding steps), and the lane's *carry* is
                    frozen at the validity boundary (padding steps would
                    otherwise decay the membrane and advance ``prev_spk``),
                    so a lane may *complete mid-chunk* bit-exactly and its
                    post-chunk state is exactly the state after its last
                    valid step -- the seam streaming sessions snapshot and
                    resume from.  ``None`` records every step.

    Returns ``(states, out_spikes [k, n_lanes, n_classes], emitted
    [k, n_layers, n_lanes])`` -- the final layer's per-step spikes plus
    every layer's per-step per-lane emitted-event count (what per-request
    ``event_stats`` accumulates from).

    One jitted call advances all lanes ``k`` steps: per-call dispatch
    overhead -- not the tiny per-step arithmetic -- dominates a CPU/edge
    serving loop, so the engine amortises it over a chunk.  The program
    specialises on ``k``; callers bound compilation count by quantising
    ``k`` (the serving engine uses powers of two, with ``valid_steps``
    absorbing the overshoot past the earliest lane completion).

    The traversal is layer-major *within* the chunk (legal for the same
    reason the fused/event backends are: inter-core traffic is strictly
    feed-forward and step-aligned): each layer integrates its whole chunk
    in one feed-forward matmul and carries its state through the shared
    step scan (``int_layer_window_carry``), which layers recurrence and
    phase B on top -- so every neuron model / topology / reset mode is
    covered bit-exactly while the hot matmul runs at [k * n_lanes, n_in]
    instead of k separate [n_lanes, n_in] slivers.

    ``ff_mode`` (static) selects how the feed-forward matmul is computed:
    ``"int32"`` (exact by construction) or ``"f32_exact"``, which routes it
    through ``lowering.f32_currents`` -- still bit-exact *provided the
    caller has checked* ``lowering.f32_exact(w_bits, max_spike_value,
    n_in)`` for every layer (the serving engine checks this per network and
    per request; deeper layers integrate {0,1} phase-B spikes).

    ``event_budget`` (static) routes *layer 0* through the fixed-capacity
    sparse event path (``repro.kernels.sparse_accum``) at that budget: the
    Pallas AER scatter on TPU, the budget-certified exact-f32 lowering
    elsewhere.  The caller guarantees the capacity contract -- every active
    lane's chunk rows carry at most ``event_budget`` active channels -- and,
    off-TPU, ``lowering.f32_exact(l0.w_bits, max_spike_value,
    event_budget)`` (the serving engine enforces both at admission, see the
    ``"event-pallas"`` route).  Deeper layers follow ``ff_mode`` as usual.
    """
    states = jax.tree.map(
        lambda a: jnp.where(reset_mask[:, None], jnp.zeros_like(a), a), states
    )
    k = x_chunk.shape[0]
    x = x_chunk.astype(jnp.int32)
    live = None
    if valid_steps is not None:
        live = jnp.arange(k)[:, None] < valid_steps[None, :]  # [k, n_lanes]
    new_states, emitted = [], []
    for li, (cfg, p, st) in enumerate(zip(net.layers, qparams, states)):
        with jax.named_scope(FF_SCOPE):
            if li == 0 and event_budget is not None:
                currents = sparse_accum_currents(x, p.w_ff, min(event_budget, cfg.n_in))
            elif ff_mode == "f32_exact":
                currents = lowering.f32_currents(x, p.w_ff)
            else:
                currents = spike_integrate(x, p.w_ff)
        st, x = int_layer_window_carry(cfg, p, st, currents, live=live)
        new_states.append(st)
        emitted.append(jnp.sum(x, axis=-1))  # [k, n_lanes]
    out_spikes = x
    emitted = jnp.stack(emitted, axis=1)  # [k, n_layers, n_lanes]
    if live is not None:
        live_i = live.astype(jnp.int32)
        out_spikes = out_spikes * live_i[:, :, None]
        emitted = emitted * live_i[:, None, :]
    return new_states, out_spikes, emitted


def batched_lane_tick(net, qparams, states, x_t, reset_mask, event_budget=None):
    """Single-step convenience form of :func:`batched_lane_window`.

    Returns ``(states, out_spikes [n_lanes, n_classes], emitted
    [n_layers, n_lanes])`` for one tick.  ``event_budget`` routes layer 0
    through the fixed-capacity sparse path, same contract as the window form.
    """
    states, out, emitted = batched_lane_window(
        net, qparams, states, x_t[None], reset_mask, event_budget=event_budget
    )
    return states, out[0], emitted[0]


@functools.partial(jax.jit, static_argnames=("net",))
def _run_int_batched_jit(net, qparams, rasters, lengths):
    T, B, _ = rasters.shape
    states = [int_layer_init(cfg, B) for cfg in net.layers]

    def one_step(states, inp):
        s_t, t = inp
        live = (t < lengths).astype(jnp.int32)  # [B]
        new_states, emitted = [], []
        x = s_t
        for cfg, p, st in zip(net.layers, qparams, states):
            st, x = int_layer_step(cfg, p, st, x)
            new_states.append(st)
            emitted.append(jnp.sum(x, axis=-1) * live)
        return new_states, (x * live[:, None], jnp.stack(emitted, axis=0))

    ts = jnp.arange(T)
    _, (out_spikes, emitted) = jax.lax.scan(one_step, states, (rasters, ts))
    counts = jnp.sum(out_spikes, axis=0)
    live = ts[:, None] < lengths[None, :]  # [T, B]
    input_events = jnp.sum(rasters != 0, axis=-1) * live
    return counts, emitted, input_events


def run_int_batched(net, qparams, rasters, lengths=None, mesh=None) -> SimRecord:
    """One vmap-batched run over a ragged batch of variable-length samples.

    ``rasters`` int [T_max, B, n_in], each sample zero-padded to the longest
    window; ``lengths`` int [B] gives each sample's true window (``None`` =
    all full length).  One jitted scan of :func:`batched_lane_tick`'s step
    advances every sample in lockstep; a sample's contributions (output
    spikes, emitted events, input events) are masked out past its own
    length, so every per-sample slice of the returned :class:`SimRecord` is
    bit-exact with a serial single-sample ``run_int`` over that sample's
    unpadded window (zero-input padding steps could otherwise still fire
    from residual membrane charge).

    This is the whole-window form of the serving seam: the population sweep
    batches *candidates* with one compiled program, this batches *samples*.
    Per-sample record views: ``spike_counts[b]``, ``layer_spikes[l][:Tb, b]``,
    ``input_events[:Tb, b]``.

    ``mesh`` (``None`` | ``"auto"`` | int | ``repro.core.shard.DeviceMesh``)
    spreads the sample axis across devices via ``shard_map`` -- still
    bit-exact per sample (lanes are independent); see ``repro.core.shard``.
    """
    if mesh is not None:
        from repro.core import shard as shard_lib  # deferred: shard imports us

        return shard_lib.run_int_batched_sharded(net, qparams, rasters, lengths, mesh)
    rasters = jnp.asarray(rasters).astype(jnp.int32)
    T, B, _ = rasters.shape
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    else:
        lengths = jnp.asarray(lengths, jnp.int32)
        if lengths.shape != (B,):
            raise ValueError(f"lengths must be [B]={B}, got {lengths.shape}")
    counts, emitted, input_events = _run_int_batched_jit(
        net, list(qparams), rasters, lengths
    )
    return SimRecord(
        spike_counts=counts,
        layer_spikes=[emitted[:, i, :] for i in range(len(net.layers))],
        input_events=input_events,
    )
