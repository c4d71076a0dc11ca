"""NeurA-Serve: continuous-batching inference service for quantized SNNs.

The SNN-side counterpart of :mod:`repro.serve.engine` (the LM decode
engine), driving the paper's actual workload -- bit-exact quantized SNN
inference over the backend registry -- as a *service* instead of one batch
at a time through ``run_int``:

* A fixed pool of ``max_batch`` **lanes** holds in-flight samples.  Each
  tick, one jitted program (``repro.core.backend.batched_lane_window``)
  advances every active lane by a chunk of time steps at its *own* local
  step index; lanes never interact, so each lane's trajectory is bit-exact
  with a serial single-sample ``run_int``.
* Requests may carry different window lengths; a finished sample frees its
  lane **immediately** and the next queued request is admitted on the
  following tick (continuous batching -- no head-of-line blocking on long
  windows).
* Serving with ``backend="event"`` adds a density-based **admission
  policy**: with an eager strategy (scipy CSR on CPU, masked gather on
  TPU) a request whose input density is at or below
  ``sparse_admission_threshold`` is routed straight through the event
  backend's sparse path one sample at a time, while dense requests go to
  the batched lane pool.  With the jit-compatible ``strategy="pallas"``
  there is no out-of-jit detour: sparse requests stay *in* the lane pool
  (route ``"event-pallas"``) and the jitted chunk advance itself takes the
  fixed-capacity sparse path for layer 0 whenever every active lane fits
  the static event budget.  All routes are bit-exact, so routing is a
  latency knob, not an accuracy knob.
* Every completed request reports wall-clock latency (arrival ->
  completion, queueing included) plus the modeled hardware operating point
  at its *measured* event traffic: the per-request ``SimRecord``-shaped
  event stats feed ``hw_model.design_point`` exactly as a batch run's
  ``event_stats()`` would.

* ``data_parallel=N`` partitions the lane pool into per-device **shards**
  (``repro.core.shard.wrap_lane_window``): lane state stays resident on
  its device across ticks, one jitted tick advances every shard, and
  admission stays a global host-side decision -- the lane index *is* the
  placement.  Numerics never move (lanes are independent), so sharding is
  purely a throughput knob for per-tick compute large enough to cover the
  extra dispatch.

The **front-line control plane** (``repro.serve.scheduler`` +
``repro.serve.metrics``) turns the lane pool into a QoS-aware service:

* Requests carry a :class:`~repro.serve.scheduler.Priority` class, a
  ``tenant``, and an optional ``deadline_s``; admission runs the
  scheduler's class-credit deficit-round-robin over per-tenant
  weighted-fair queues (prioritised but starvation-free).
* A request whose deadline cannot survive the queue is **degraded** to a
  coarser registered :class:`~repro.serve.scheduler.PrecisionTier` --
  served immediately through one ragged ``run_int_batched`` express call
  at the tier's re-quantized network (the paper's accuracy-vs-resource
  dial, applied online) -- or **rejected** up front when no tier can make
  the deadline either.
* A queued ``CRITICAL`` request may **preempt** a running lower-priority
  lane: the victim's carry state is snapshotted through the lane seams
  (``lane_state_take``/``lane_state_put``), the request re-enters the
  front of its class queue, and its eventual resume is bit-exact with an
  uninterrupted serial ``run_int``.
* ``engine.metrics`` is a rolling-window StatLogger (p50/p99 latency per
  class, queue depth, lane occupancy, event-route hit rate, preemption /
  degradation / rejection counters) that the HTTP front-end
  (``repro.serve.http``) exposes at ``/metrics`` and ``/healthz``.

``SNNServeEngine.run`` replays an offered-load schedule (open loop:
requests become visible at ``arrival_s`` offsets); ``submit``/``tick``
expose the loop for callers that drive it themselves; and
:class:`AsyncSNNServer` is an asyncio facade whose ``submit`` resolves a
future on completion.  Throughput and p95 latency on the chip are measured
by the ``*-serve-open`` benchmark cells (``PERF.md``); the serving story is
documented in ``docs/SERVING.md``.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import time
import warnings
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hw_model, lowering
from repro.core import shard as shard_lib
from repro.core.fixed_point import int_max, int_min
from repro.core.backend import (
    EventBackend,
    InferenceBackend,
    batched_lane_init,
    batched_lane_window,
    get_backend,
    lane_state_put,
    lane_state_take,
    run_int_batched,
)
from repro.core.network import NetworkConfig, run_int
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduler import PrecisionTier, Priority, SchedPolicy, Scheduler

__all__ = [
    "SNNRequest",
    "SNNServeEngine",
    "AsyncSNNServer",
    "EngineStalledError",
]


class EngineStalledError(RuntimeError):
    """``poll()``/``drain()`` made no progress for ``max_idle_ticks``
    consecutive rounds while requests were still queued.

    Carries the scheduler's queue snapshot and the lane table at the time
    of the stall, so the spin is diagnosable instead of silent:
    ``err.queue_snapshot`` / ``err.lane_states``.
    """

    def __init__(self, msg: str, queue_snapshot: dict, lane_states: list):
        super().__init__(msg)
        self.queue_snapshot = queue_snapshot
        self.lane_states = lane_states


@dataclasses.dataclass
class SNNRequest:
    """One inference request: a single sample's spike raster.

    ``raster`` is int [T, n_in] -- the sample's own window length T may
    differ per request.  ``arrival_s`` is the request's offset from the
    start of ``SNNServeEngine.run`` (offered-load replay); 0 means already
    queued.

    QoS fields: ``priority`` (a :class:`~repro.serve.scheduler.Priority`
    class), ``tenant`` (weighted-fair sharing key within a class), and
    ``deadline_s`` -- a latency SLO in seconds from arrival; when the
    engine's service estimate says the deadline will be missed the request
    is degraded to a registered precision tier or rejected instead of
    queueing past it.  ``on_complete`` is invoked with the request at any
    terminal state (completed / degraded / rejected); a raising callback is
    counted (``callback_failures``) and never takes the engine down.

    The engine fills the result fields at the terminal state: ``status`` is
    ``"completed"`` | ``"degraded"`` | ``"rejected"``, ``tier`` names the
    precision served (``"full"`` or a registered tier name), and
    ``preemptions`` / ``admitted_seq`` record scheduling history.
    """

    uid: int
    raster: np.ndarray
    arrival_s: float = 0.0
    priority: Priority | int = Priority.STANDARD
    tenant: str = "default"
    deadline_s: float | None = None
    on_complete: "Callable[[SNNRequest], None] | None" = dataclasses.field(
        default=None, repr=False
    )
    # -- filled by the engine at the terminal state --------------------------
    spike_counts: np.ndarray | None = None  # [n_classes] output spike totals
    prediction: int | None = None
    route: str | None = None  # "lanes" | "event-*" | "degraded"
    latency_s: float | None = None  # terminal - arrival (queueing included)
    service_s: float | None = None  # terminal - admission
    status: str | None = None  # "completed" | "degraded" | "rejected"
    tier: str | None = None  # "full" | registered tier name (None if rejected)
    preemptions: int = 0
    restarts: int = 0  # quarantine / crash-recovery re-admissions
    admitted_seq: int | None = None  # first-admission order (FIFO property)
    _arrival_wall: float | None = dataclasses.field(default=None, repr=False)
    _net: "NetworkConfig | None" = dataclasses.field(default=None, repr=False)
    _stats_src: tuple | None = dataclasses.field(default=None, repr=False)
    _stats: dict | None = dataclasses.field(default=None, repr=False)
    _design: hw_model.DesignPoint | None = dataclasses.field(default=None, repr=False)
    _max_val: int = dataclasses.field(default=0, repr=False)
    _max_step_events: int = dataclasses.field(default=0, repr=False)
    _sched_seq: int | None = dataclasses.field(default=None, repr=False)
    _suspended: tuple | None = dataclasses.field(default=None, repr=False)
    _finalized: bool = dataclasses.field(default=False, repr=False)
    # -- streaming-session seam (repro.serve.streaming) ----------------------
    # A chunk request continues a persistent stream: ``_carry_in`` is a
    # lane_state_take snapshot restored at admission instead of zeroing the
    # lane, ``_want_carry`` asks for the post-window carry back on
    # ``carry_out``, and ``_record_steps`` keeps the final layer's per-step
    # spike vectors on ``step_outputs`` (the sliding-window readout input).
    _carry_in: list | None = dataclasses.field(default=None, repr=False)
    _want_carry: bool = dataclasses.field(default=False, repr=False)
    _record_steps: bool = dataclasses.field(default=False, repr=False)
    carry_out: list | None = dataclasses.field(default=None, repr=False)
    step_outputs: np.ndarray | None = dataclasses.field(default=None, repr=False)

    def __post_init__(self):
        self.priority = Priority(self.priority)  # raises on unknown classes
        if self.deadline_s is not None:
            self.deadline_s = float(self.deadline_s)
        self.raster = np.asarray(self.raster)
        if self.raster.ndim != 2:
            raise ValueError(
                f"request {self.uid}: raster must be [T, n_in], got shape "
                f"{self.raster.shape}"
            )
        if self.raster.shape[0] < 1:
            raise ValueError(f"request {self.uid}: empty window")
        # spike values are tiny non-negative ints; a uint8 raster quarters the
        # bytes every serving tick streams across the host->device boundary
        if self.raster.size:
            lo, hi = int(self.raster.min()), int(self.raster.max())
            self._max_val = max(abs(lo), abs(hi))
            if self.raster.dtype != np.uint8:
                self.raster = self.raster.astype(
                    np.uint8 if 0 <= lo and hi <= 255 else np.int32
                )
        # cached: the raster is immutable once submitted, and the admission
        # policy re-reads density on every dispatch round
        self._density = float(np.count_nonzero(self.raster)) / max(1, self.raster.size)
        # max active channels in any single step: the sparse lane route's
        # capacity check (the event budget bounds a *step*, not the mean)
        self._max_step_events = int(np.count_nonzero(self.raster, axis=-1).max(initial=0))

    @property
    def n_steps(self) -> int:
        return self.raster.shape[0]

    @property
    def density(self) -> float:
        """Fraction of nonzero raster entries (the admission-policy signal)."""
        return self._density

    @property
    def done(self) -> bool:
        return self.spike_counts is not None

    @property
    def finished(self) -> bool:
        """Terminal: completed, degraded, or rejected (exactly once)."""
        return self.status is not None

    @property
    def event_stats(self) -> dict | None:
        """This request's measured event traffic, ``SimRecord.event_stats``
        shaped: ``{"input_events_per_step": [T], "layer_events_per_step":
        [[T], ...]}``.  Assembled lazily (off the serving hot path) from
        whatever the engine recorded -- the per-tick emitted counts of the
        lane route, the single-sample ``SimRecord`` of the event route, or
        this sample's slice of a degraded express batch.
        """
        if self._stats is None and self._stats_src is not None:
            kind, payload = self._stats_src
            if kind == "record":
                self._stats = payload.event_stats()
            elif kind == "batch":  # (SimRecord, sample index, true window)
                rec, b, Tb = payload
                self._stats = {
                    "input_events_per_step": np.asarray(rec.input_events)[
                        :Tb, b
                    ].astype(np.float64),
                    "layer_events_per_step": [
                        np.asarray(s)[:Tb, b].astype(np.float64)
                        for s in rec.layer_spikes
                    ],
                }
            else:  # per-lane chunks: list of [k_i, n_layers] emitted counts
                per_step = np.concatenate(payload, axis=0).astype(np.float64)
                self._stats = {
                    "input_events_per_step": np.count_nonzero(
                        self.raster, axis=-1
                    ).astype(np.float64)[: per_step.shape[0]],
                    "layer_events_per_step": [
                        per_step[:, l] for l in range(per_step.shape[1])
                    ],
                }
        return self._stats

    @property
    def design(self) -> hw_model.DesignPoint | None:
        """Modeled hardware operating point at this request's measured traffic.

        Derived lazily from ``event_stats`` (off the serving hot path):
        latency/power/energy from ``hw_model.design_point``, exactly what a
        batch run's ``SimRecord.event_stats()`` would feed it.  A degraded
        request's point is modeled at its *tier's* network -- the coarser
        deployment the paper's explorer would have picked.
        """
        if self._design is None and self._net is not None and self.event_stats is not None:
            self._design = hw_model.design_point(
                self._net, hw_model.EventTraffic.from_stats(self.event_stats)
            )
        return self._design


@functools.partial(
    jax.jit,
    static_argnames=("net", "ff_mode", "dmesh", "event_budget"),
    donate_argnums=(2,),
)
def _lane_window_packed(
    net, qparams, states, x_chunk, lane_meta, ff_mode, dmesh=None, event_budget=None
):
    """``batched_lane_window`` with packed aux input and packed output.

    Serving throughput on CPU/edge hosts is bounded by host<->device
    boundary crossings, not arithmetic: ``lane_meta`` int32 [2, n_lanes]
    carries ``(reset_flags, valid_steps)`` in one transfer, and the
    final-layer spikes + per-layer emitted counts come back as one
    [k, n_lanes, n_classes + n_layers] array -- two crossings per tick
    instead of four.

    The lane-carry ``states`` buffers are donated: the pool's previous
    state is dead the moment a tick returns (the engine rebinds it), so XLA
    reuses those buffers for the new state instead of allocating a fresh
    pool every tick.

    ``dmesh`` (static) partitions the lane axis across a device mesh: each
    device owns ``n_lanes / n_shards`` resident lanes and one dispatch
    advances every shard (see ``repro.core.shard.wrap_lane_window``).
    ``None`` keeps the single-device program.

    ``event_budget`` (static) routes layer 0 through the fixed-capacity
    sparse event path at that budget (see ``batched_lane_window``); the
    engine only passes it on ticks where every active lane satisfies the
    capacity + exactness contract, so the sparse program is bit-exact with
    the dense one.  It composes with ``dmesh``: the budget is a python
    static inside the shard-mapped body.
    """

    def body(qp, st, x, meta):
        st, out, emitted = batched_lane_window(
            net,
            qp,
            st,
            x,
            meta[0] != 0,
            valid_steps=meta[1],
            ff_mode=ff_mode,
            event_budget=event_budget,
        )
        packed = jnp.concatenate([out, jnp.transpose(emitted, (0, 2, 1))], axis=-1)
        return st, packed

    if dmesh is not None and dmesh.n_shards > 1:
        body = shard_lib.wrap_lane_window(body, dmesh)
    return body(qparams, states, x_chunk, lane_meta)


@dataclasses.dataclass
class _Lane:
    """Host-side bookkeeping for one occupied lane."""

    req: SNNRequest
    admitted_wall: float
    t: int = 0  # next local step to feed
    fresh: bool = True  # device state must be zeroed on the next tick
    counts: np.ndarray | None = None  # [n_classes] running output spikes
    layer_events: list = dataclasses.field(default_factory=list)  # per tick [L]
    step_out: list | None = None  # per tick [valid, n_classes] (streaming readout)
    carry0: list | None = None  # chunk-start carry snapshot (quarantine restart)


class SNNServeEngine:
    """Continuous-batching SNN inference over a fixed lane pool.

    ``backend`` selects the serving strategy by registry name or instance:
    the lane pool always advances through the shared batched lane window
    (reference numerics -- every registered backend is held bit-exact to
    those, so the choice never moves outputs), and an
    :class:`~repro.core.backend.EventBackend` additionally enables the
    density-based admission policy.  An eager strategy (csr / gather)
    serves sparse requests through its host/eager sparse path one sample at
    a time; the jit-compatible ``strategy="pallas"`` instead keeps sparse
    requests in the lane pool (route ``"event-pallas"``) and lets the
    jitted chunk advance take the fixed-capacity sparse path whenever the
    whole active cohort fits the engine's static event budget
    (``EventBackend.serve_budget``) -- event x serve as one compiled
    program.

    ``tick_stride`` caps how many time steps one jitted call advances the
    lane pool: per-call dispatch overhead dominates the tiny per-step
    arithmetic on CPU/edge hosts, so each tick runs ``k`` steps where ``k``
    is the power of two that just covers the earliest remaining lane window
    (capped by ``tick_stride``), with per-lane ``valid_steps`` masking
    absorbing the overshoot.  Lanes therefore complete -- and free -- at
    the tick that covers their window (continuous batching at chunk
    granularity), while only the few power-of-two chunk programs ever
    compile.  ``tick_stride=1`` recovers strict per-step ticking;
    ``tick_stride=None`` leaves the chunk uncapped.

    ``scheduler`` (a :class:`~repro.serve.scheduler.SchedPolicy` or a
    prebuilt :class:`~repro.serve.scheduler.Scheduler`) configures the
    front-line control plane: class-credit priority admission, per-tenant
    weighted fairness, preemption, and deadline verdicts.  The default
    policy with default-class requests degenerates to the plain FIFO the
    engine always had.  ``precision_tiers`` registers the coarser
    deployments that deadline degradation may serve (ordered finest ->
    coarsest; the first tier that makes the deadline wins).

    ``max_idle_ticks`` is the liveness guard: if ``poll()`` completes
    nothing, admits nothing, and has no active lanes for that many
    consecutive rounds while requests are still queued, it raises
    :class:`EngineStalledError` carrying the queue snapshot and lane table
    instead of spinning forever (``None`` disables the guard).

    ``report_design_point=False`` skips attaching per-request event stats
    (and therefore the lazily derived ``req.design`` hardware operating
    point) for pure-throughput deployments.

    ``data_parallel`` partitions the lane pool into per-device shards:
    lanes ``[i * max_batch/n, (i+1) * max_batch/n)`` are resident on device
    ``i``, one jitted tick advances every shard, and admission stays a
    global host-side decision (a request lands on whichever lane is free;
    the lane index *is* the placement).  ``max_batch`` must divide evenly.
    Requests for more devices than exist clamp down with a
    ``RuntimeWarning`` -- on a single-device host this degrades to the
    unsharded engine, bit-exactly.  Routing and
    numerics are unchanged: lanes never interact, so the sharded pool's
    trajectories are identical to the serial pool's (asserted by the serve
    parity tests).
    """

    def __init__(
        self,
        net: NetworkConfig,
        qparams: Sequence,
        *,
        max_batch: int = 8,
        backend: str | InferenceBackend = "reference",
        sparse_admission_threshold: float = 0.10,
        tick_stride: int | None = 32,
        report_design_point: bool = True,
        data_parallel: int | None = None,
        scheduler: "SchedPolicy | Scheduler | None" = None,
        precision_tiers: Sequence[PrecisionTier] = (),
        max_idle_ticks: int | None = 1000,
        metrics_window_s: float = 60.0,
        journal=None,
        faults=None,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if data_parallel is not None and data_parallel < 1:
            raise ValueError(f"data_parallel must be >= 1 or None, got {data_parallel}")
        if tick_stride is not None and tick_stride < 1:
            raise ValueError(f"tick_stride must be >= 1 or None, got {tick_stride}")
        if not 0.0 <= sparse_admission_threshold <= 1.0:
            raise ValueError(
                "sparse_admission_threshold must be in [0, 1], got "
                f"{sparse_admission_threshold}"
            )
        if max_idle_ticks is not None and max_idle_ticks < 1:
            raise ValueError(
                f"max_idle_ticks must be >= 1 or None, got {max_idle_ticks}"
            )
        self.net = net
        self.qparams = list(qparams)
        self.max_batch = max_batch
        resolved = get_backend(backend)
        self.backend_name = resolved.name
        self.event_backend = resolved if isinstance(resolved, EventBackend) else None
        self.sparse_admission_threshold = sparse_admission_threshold
        self.tick_stride = tick_stride
        self.report_design_point = report_design_point
        self.sched = scheduler if isinstance(scheduler, Scheduler) else Scheduler(scheduler)
        for tier in precision_tiers:
            if tier.net.n_in != net.n_in or tier.net.n_classes != net.n_classes:
                raise ValueError(
                    f"precision tier {tier.name!r} does not match the serving "
                    f"network topology ({tier.net.n_in}ch/{tier.net.n_classes}cls "
                    f"vs {net.n_in}ch/{net.n_classes}cls)"
                )
        self.tiers: tuple[PrecisionTier, ...] = tuple(precision_tiers)
        self.max_idle_ticks = max_idle_ticks
        self.metrics = ServeMetrics(metrics_window_s)
        # -- NeurA-Guard durability / chaos seams ----------------------------
        # ``journal`` (repro.serve.journal.Journal) records admissions and
        # terminal states for crash recovery; ``faults`` (repro.serve.faults.
        # FaultInjector) threads the chaos injector's tick/carry sites
        # through the serve loop.  Both default off and cost nothing when
        # absent.
        self.journal = journal
        self.faults = faults
        self.stop_admission = False  # graceful drain: refuse new submits
        # Slots the supervisor's validity sweep condemned: they hold no
        # lane, never admit, and only an engine restart reclaims them.
        self._quarantined: set[int] = set()

        self._dmesh = None
        if data_parallel is not None and data_parallel > 1:
            n_avail = len(jax.devices())
            if data_parallel <= n_avail and max_batch % data_parallel:
                # the requested count exists but cannot split the pool: that
                # is a config error, not something to silently reshape
                raise ValueError(
                    f"data_parallel={data_parallel} must divide max_batch="
                    f"{max_batch} (lanes are split evenly across devices)"
                )
            # over-asks clamp down -- to the device count if it divides, else
            # to the largest usable shard count below it
            n = min(data_parallel, n_avail)
            while max_batch % n:
                n -= 1
            if n < data_parallel:
                warnings.warn(
                    f"data_parallel={data_parallel} clamped to {n}: {n_avail} "
                    f"device(s) visible, max_batch={max_batch}",
                    RuntimeWarning,
                    stacklevel=2,
                )
            if n > 1:
                self._dmesh = shard_lib.make_mesh(n)
        self.data_parallel = self._dmesh.n_shards if self._dmesh is not None else 1

        self._states = batched_lane_init(net, max_batch)
        self._lanes: list[_Lane | None] = [None] * max_batch
        self.n_ticks = 0  # jitted chunk dispatches
        self.n_served = 0
        self._admit_seq = 0  # first-admission counter (FIFO-order evidence)
        self._idle_rounds = 0  # consecutive no-progress polls (liveness guard)
        # Largest layer-0 input spike value for which the f32 feed-forward
        # lowering stays exact (``lowering.f32_exact``); deeper layers always
        # integrate {0,1} phase-B spikes, so they only need it at value 1.
        l0 = net.layers[0]
        self._deep_f32_ok = all(
            lowering.f32_exact(c.w_bits, 1, c.n_in) for c in net.layers[1:]
        )
        self._f32_input_max: int = 0
        if self._deep_f32_ok:
            self._f32_input_max = lowering.f32_max_input(l0.w_bits, l0.n_in)
        # The jitted sparse lane route: with an event backend resolving to the
        # pallas strategy, sparse requests stay in the lane pool and the
        # chunk advance takes the fixed-capacity path for layer 0.  A request
        # admits to the sparse route only when its max per-step
        # active-channel count fits the budget AND its values stay under
        # _sparse_val_max: on TPU the Pallas kernel accumulates int32 for any
        # value; elsewhere the budget certifies the f32 lowering.
        self._event_budget: int | None = None
        self._sparse_val_max: int = 0
        if self.event_backend is not None and self.event_backend.resolved_strategy() == "pallas":
            self._event_budget = self.event_backend.serve_budget(
                l0.n_in, sparse_admission_threshold
            )
            self._sparse_val_max = (
                np.iinfo(np.int32).max
                if lowering.on_tpu()
                else lowering.f32_max_input(l0.w_bits, self._event_budget)
            )

    # -- introspection ------------------------------------------------------
    @property
    def queue(self):
        """The scheduler, quacking like the FIFO deque it replaced
        (``len`` / truthiness / indexing / scheduling-order iteration)."""
        return self.sched

    @property
    def active_lanes(self) -> int:
        return sum(l is not None for l in self._lanes)

    @property
    def free_lanes(self) -> int:
        return self.max_batch - self.active_lanes - len(self._quarantined)

    @property
    def capacity(self) -> int:
        """Lanes not condemned by quarantine (active or free)."""
        return self.max_batch - len(self._quarantined)

    @property
    def quarantined(self) -> frozenset:
        return frozenset(self._quarantined)

    @property
    def in_flight(self) -> bool:
        return bool(self.sched) or self.active_lanes > 0

    def route_lowerings(self) -> dict[str, list[str]]:
        """Per lane-pool program, the lowering each layer's feed-forward
        takes for binary traffic (``repro.core.lowering`` names; inputs
        above ``_f32_input_max`` switch the dense program to the int32 dot).
        ``"event-pallas"`` appears when the sparse lane program exists."""
        deep = lowering.F32 if self._deep_f32_ok else lowering.XLA_INT32
        l0 = lowering.F32 if self._f32_input_max >= 1 else lowering.XLA_INT32
        routes = {"lanes": [l0] + [deep] * (len(self.net.layers) - 1)}
        if self._event_budget is not None:
            sparse = lowering.PALLAS_SPARSE if lowering.on_tpu() else lowering.F32
            routes["event-pallas"] = [f"{sparse}@{self._event_budget}"] + routes["lanes"][1:]
        return routes

    # -- admission ----------------------------------------------------------
    def submit(self, req: SNNRequest) -> None:
        """Queue a request (arrival stamped now unless ``run`` set it)."""
        if self.stop_admission:
            raise RuntimeError(
                f"request {req.uid}: engine is draining, admission is stopped"
            )
        if req.raster.shape[1] != self.net.n_in:
            raise ValueError(
                f"request {req.uid}: raster has {req.raster.shape[1]} channels, "
                f"network expects {self.net.n_in}"
            )
        if req._arrival_wall is None:
            req._arrival_wall = time.perf_counter()
        # WAL: the admission must survive a crash.  Streaming chunk requests
        # are *not* journaled here -- the session manager journals the feed
        # itself (recovery rebuilds chunks from the session's carry seam, so
        # engine-level chunk records would double-count the stream).
        if self.journal is not None and not req._want_carry:
            self.journal.append(
                "submit",
                arrays={"raster": req.raster},
                uid=req.uid,
                priority=int(req.priority),
                tenant=req.tenant,
                deadline_s=req.deadline_s,
            )
        self.metrics.inc("submitted")
        self.sched.add(req)

    def _routes_to_event(self, req: SNNRequest) -> bool:
        """Direct (out-of-jit) sparse route: eager csr/gather strategies only.

        Streaming chunk requests never take it -- the direct route runs a
        fresh-state single-sample ``run_int``, which cannot restore or
        return a lane carry; they stay in the lane pool (where the jitted
        ``"event-pallas"`` sparse route still applies per tick).
        """
        return (
            self.event_backend is not None
            and self._event_budget is None
            and req.density <= self.sparse_admission_threshold
            and req._carry_in is None
            and not req._want_carry
        )

    def _sparse_lane_eligible(self, req: SNNRequest) -> bool:
        """Admission rule for the jitted ``"event-pallas"`` lane route:
        sparse enough to be worth tagging, every step fits the static event
        budget (the capacity contract), and values stay inside the budget's
        f32 exactness certificate."""
        return (
            self._event_budget is not None
            and req.density <= self.sparse_admission_threshold
            and req._max_step_events <= self._event_budget
            and req._max_val <= self._sparse_val_max
        )

    def _serve_event(self, req: SNNRequest) -> SNNRequest:
        """Direct sparse route: one single-sample event-backend run."""
        t0 = time.perf_counter()
        rec = run_int(
            self.net,
            self.qparams,
            jnp.asarray(req.raster[:, None, :], jnp.int32),
            backend=self.event_backend,
        )
        req.spike_counts = np.asarray(rec.spike_counts)[0]
        req.route = f"event-{self.event_backend.resolved_strategy()}"
        self.metrics.direct_s += time.perf_counter() - t0
        self._finish(req, time.perf_counter(), stats_src=("record", rec))
        return req

    def _free_lane(self) -> int | None:
        for i, lane in enumerate(self._lanes):
            if lane is None and i not in self._quarantined:
                return i
        return None

    # -- the control plane: one dispatch round ------------------------------
    def _dispatch(self, now: float) -> list[SNNRequest]:
        """One scheduling round over the queue, in QoS order:

        1. **direct sparse serves** -- event-routable requests are served
           wherever they sit (their route needs no lane, so a full pool
           must never head-of-line block them behind a dense request);
        2. **deadline sweep** -- every queued deadlined request gets a
           keep / degrade / reject verdict against the engine's measured
           service estimate; degraded requests are served *now* through
           the tier express batch, rejects terminate immediately;
        3. **preemption** -- queued CRITICALs may evict running
           lower-priority lanes (longest remaining window first) when the
           pool is full;
        4. **admission** -- free lanes fill by class-credit DRR + tenant
           WFQ (strict FIFO under the default policy).

        The round is the profiler span ``neura.serve.dispatch``, with the
        queue depth on entry (``queued``) and the lanes it filled
        (``admitted``).
        """
        with jax.profiler.TraceAnnotation(
            "neura.serve.dispatch", queued=len(self.sched)
        ) as span:
            done, admitted = self._dispatch_round(now)
            span.set_metadata(admitted=admitted)
        return done

    def _dispatch_round(self, now: float) -> tuple[list[SNNRequest], int]:
        t0 = time.perf_counter()
        admitted = 0
        served_s = 0.0  # compute spent serving, excluded from dispatch_s
        done: list[SNNRequest] = []

        if self.event_backend is not None and self._event_budget is None and self.sched:
            for req in [r for r in self.sched if self._routes_to_event(r)]:
                self.sched.remove(req)
                s0 = time.perf_counter()
                done.append(self._serve_event(req))
                served_s += time.perf_counter() - s0

        degrade: list[tuple[SNNRequest, PrecisionTier]] = []
        if self.sched:
            deadlined = [r for r in self.sched if r.deadline_s is not None]
            if deadlined:
                step_s = self.metrics.est_step_s
                lane_backlog = sum(
                    l.req.n_steps - l.t for l in self._lanes if l is not None
                )
                queue_backlog = sum(r.n_steps for r in self.sched)
                for req in deadlined:
                    if step_s is None:
                        wait = 0.0
                    elif (
                        Priority(req.priority) is Priority.CRITICAL
                        and self.sched.policy.preempt
                    ):
                        wait = 0.0  # it would preempt its way in
                    else:
                        wait = (
                            (lane_backlog + queue_backlog - req.n_steps)
                            * step_s
                            / self.max_batch
                        )
                    action, tier = self.sched.deadline_action(
                        req, now, est_step_s=step_s, est_wait_s=wait, tiers=self.tiers
                    )
                    if action == "degrade":
                        self.sched.remove(req)
                        degrade.append((req, tier))
                    elif action == "reject":
                        self.sched.remove(req)
                        done.append(self._reject(req, now))
        if degrade:
            s0 = time.perf_counter()
            done.extend(self._serve_degraded(degrade, now))
            dt = time.perf_counter() - s0
            served_s += dt
            self.metrics.degrade_s += dt

        pol = self.sched.policy
        while (
            pol.preempt
            and self.sched.has_class(Priority.CRITICAL)
            and self._free_lane() is None
        ):
            victim = self._pick_victim()
            if victim is None:
                break
            req = self.sched.pop_class(Priority.CRITICAL)
            if req is None:
                break
            self._preempt(victim)
            self._admit(req, victim, now)
            admitted += 1

        while self.sched:
            slot = self._free_lane()
            if slot is None:
                break
            req = self.sched.pop()
            if req is None:
                break  # queue non-empty but nothing admissible: idle round
            self._admit(req, slot, now)
            admitted += 1

        self.metrics.dispatch_s += time.perf_counter() - t0 - served_s
        return done, admitted

    def _admit(self, req: SNNRequest, slot: int, now: float) -> None:
        """Place a request on a free lane -- restoring its snapshotted carry
        if it was preempted (the resume is then bit-exact with an
        uninterrupted run), otherwise starting a fresh lane."""
        if req._suspended is not None:
            lane, carry = req._suspended
            req._suspended = None
            self._states = lane_state_put(self._states, slot, carry)
            self._lanes[slot] = lane
            self.metrics.inc("resumed")
            return
        if req.admitted_seq is None:
            req.admitted_seq = self._admit_seq
            self._admit_seq += 1
        req.route = "event-pallas" if self._sparse_lane_eligible(req) else "lanes"
        lane = _Lane(
            req=req,
            admitted_wall=now,
            counts=np.zeros(self.net.n_classes, np.int64),
        )
        if req._record_steps:
            lane.step_out = []
        if req._carry_in is not None:
            # a streaming chunk resumes its stream's persistent carry: write
            # the snapshot over whatever the slot last held instead of
            # zeroing (fresh=False keeps the reset flag off).  carry0 keeps
            # the chunk-start snapshot on the host so a quarantine can
            # restart this chunk from its own seam, not from stream zero.
            self._states = lane_state_put(self._states, slot, req._carry_in)
            lane.fresh = False
            lane.carry0 = req._carry_in
            req._carry_in = None
        self._lanes[slot] = lane

    def _pick_victim(self) -> int | None:
        """Preemption victim: the non-critical lane with the most window
        left (evicting near-finished work wastes the most sunk compute),
        respecting the policy's per-request eviction cap."""
        pol = self.sched.policy
        best, best_rem = None, -1
        for i, lane in enumerate(self._lanes):
            if lane is None:
                continue
            r = lane.req
            if Priority(r.priority) is Priority.CRITICAL:
                continue
            rem = r.n_steps - lane.t
            if rem < pol.preempt_min_remaining_steps or r.preemptions >= pol.max_preemptions:
                continue
            if rem > best_rem:
                best, best_rem = i, rem
        return best

    def _preempt(self, slot: int) -> None:
        """Evict a running lane: snapshot its carry through the lane seams
        and re-enqueue the request at the front of its class queue."""
        lane = self._lanes[slot]
        self._lanes[slot] = None
        req = lane.req
        req.preemptions += 1
        req._suspended = (lane, lane_state_take(self._states, slot))
        self.sched.requeue_front(req)
        self.metrics.inc("preempted")

    def _serve_degraded(
        self, batch: list[tuple[SNNRequest, PrecisionTier]], now: float
    ) -> list[SNNRequest]:
        """Express service for deadline-degraded requests: group by tier
        and run each group through one immediate ragged ``run_int_batched``
        at the tier's re-quantized (net, qparams), skipping the lane queue
        entirely.  Batch and window pad to powers of two (per-sample
        lengths masking keeps each sample bit-exact with a serial
        ``run_int`` at the same tier), so only a handful of express
        programs ever compile."""
        done: list[SNNRequest] = []
        groups: dict[str, tuple[PrecisionTier, list[SNNRequest]]] = {}
        for req, tier in batch:
            groups.setdefault(tier.name, (tier, []))[1].append(req)
        cap = 1 << max(0, (self.max_batch - 1)).bit_length()
        for tier, reqs in groups.values():
            for lo in range(0, len(reqs), cap):
                chunk = reqs[lo : lo + cap]
                steps = [tier.steps(r.n_steps) for r in chunk]
                T_pad = 1 << max(0, (max(steps) - 1)).bit_length()
                B_pad = min(cap, 1 << max(0, (len(chunk) - 1)).bit_length())
                x = np.zeros((T_pad, B_pad, self.net.n_in), np.int32)
                lengths = np.zeros((B_pad,), np.int32)
                for b, (r, Tb) in enumerate(zip(chunk, steps)):
                    x[:Tb, b] = r.raster[:Tb]
                    lengths[b] = Tb
                rec = run_int_batched(tier.net, tier.qparams, x, lengths)
                counts = np.asarray(rec.spike_counts)
                end = time.perf_counter()
                for b, (r, Tb) in enumerate(zip(chunk, steps)):
                    r.spike_counts = counts[b]
                    r.status = "degraded"
                    r.tier = tier.name
                    r.route = "degraded"
                    r.service_s = end - now
                    self._finish(r, end, stats_src=("batch", (rec, b, Tb)), net=tier.net)
                    done.append(r)
        return done

    # -- the tick loop ------------------------------------------------------
    def _chunk_cap(self) -> int:
        if self.tick_stride is None:
            return 1 << 30  # effectively uncapped
        return 1 << (self.tick_stride.bit_length() - 1)

    def _chunk_len(self, active: list[int]) -> int:
        """Power-of-two step count that just covers the earliest lane
        completion (capped by ``tick_stride``): only O(log T) distinct chunk
        programs ever compile, and per-lane ``valid_steps`` masking absorbs
        the overshoot so the finishing lane still completes bit-exactly."""
        k = min(self._lanes[i].req.n_steps - self._lanes[i].t for i in active)
        k = 1 << max(0, (k - 1)).bit_length()  # next power of two >= k
        return min(k, self._chunk_cap())

    def tick(self) -> list[SNNRequest]:
        """One chunked advance for every active lane; returns finished.

        Each lane is fed its own raster slice starting at its own local
        step, so lanes admitted at different times (and with different
        window lengths) advance together through one jitted call.

        A tick that runs lanes is the profiler span ``neura.serve.tick``
        (arguments ``k``, ``active``, ``route``, ``ff_mode``), holding
        ``pack``, ``launch``, ``readback`` and ``complete`` in that order.
        """
        active = [i for i, lane in enumerate(self._lanes) if lane is not None]
        if not active:
            return []
        with jax.profiler.TraceAnnotation("neura.serve.tick", active=len(active)) as span:
            if self.faults is not None:
                self.faults.on_tick()  # chaos: may stall, raise, or "kill"
            with jax.profiler.TraceAnnotation("neura.serve.pack"):
                k, x, meta, budget, ff_mode = self._pack(active)
            span.set_metadata(
                k=k, route="dense" if budget is None else "sparse", ff_mode=ff_mode
            )
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("neura.serve.launch"):
                self._states, packed = _lane_window_packed(
                    self.net, self.qparams, self._states, x, meta, ff_mode, self._dmesh,
                    budget,
                )
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("neura.serve.readback"):
                packed = np.asarray(packed)  # [k, n_lanes, n_classes + n_layers]
            t2 = time.perf_counter()
            with jax.profiler.TraceAnnotation("neura.serve.complete") as done:
                finished = self._complete(active, k, meta, packed, t2 - t0, t1 - t0)
                done.set_metadata(finished=len(finished))
        return finished

    def _pack(self, active: list[int]):
        """The tick's inputs: chunk length ``k``, the lanes' raster slices
        ``x``, their (reset, valid steps) ``meta``, and the program to run
        (sparse event ``budget`` or None, feed-forward lowering)."""
        k = self._chunk_len(active)
        dtype = (
            np.uint8
            if all(self._lanes[i].req.raster.dtype == np.uint8 for i in active)
            else np.int32
        )
        x = np.zeros((k, self.max_batch, self.net.n_in), dtype)
        meta = np.zeros((2, self.max_batch), np.int32)  # (reset flags, valid steps)
        for i in active:
            lane = self._lanes[i]
            valid = min(k, lane.req.n_steps - lane.t)
            x[:valid, i] = lane.req.raster[lane.t : lane.t + valid]
            meta[1, i] = valid
            if lane.fresh:
                meta[0, i] = 1
                lane.fresh = False
        # The sparse chunk program runs when every active lane honors the
        # budget's capacity + exactness contract (checked per lane, not per
        # route tag: a "lanes"-routed dense request that happens to fit the
        # budget doesn't block the cohort).  Mixed cohorts with an
        # over-budget lane fall back to the dense program -- still bit-exact.
        budget = (
            self._event_budget
            if self._event_budget is not None
            and all(
                self._lanes[i].req._max_step_events <= self._event_budget
                and self._lanes[i].req._max_val <= self._sparse_val_max
                for i in active
            )
            else None
        )
        if budget is not None:
            # layer 0 goes through the sparse path; deeper layers integrate
            # {0,1} phase-B spikes, needing only the static per-layer bound
            ff_mode = "f32_exact" if self._deep_f32_ok else "int32"
        else:
            ff_mode = (
                "f32_exact"
                if self._f32_input_max >= 1
                and all(self._lanes[i].req._max_val <= self._f32_input_max for i in active)
                else "int32"
            )
        return k, x, meta, budget, ff_mode

    def _complete(
        self, active: list[int], k: int, meta: np.ndarray, packed: np.ndarray,
        tick_wall: float, launch_s: float,
    ) -> list[SNNRequest]:
        """Book the tick's read-back outputs into its lanes; returns finished."""
        n_classes = self.net.n_classes
        self.n_ticks += 1
        finished = []
        now = time.perf_counter()
        self.metrics.record_tick(
            k, tick_wall, len(self.sched), len(active), self.max_batch, now, launch_s
        )
        for i in active:
            lane = self._lanes[i]
            valid = int(meta[1, i])
            lane.counts += packed[:, i, :n_classes].sum(axis=0)  # masked past valid
            lane.layer_events.append(packed[:valid, i, n_classes:])  # [valid, L]
            if lane.step_out is not None:
                lane.step_out.append(packed[:valid, i, :n_classes].copy())
            lane.t += valid
            if lane.t >= lane.req.n_steps:
                finished.append(self._complete_lane(i, now))
        if self.faults is not None:
            # chaos: corrupt a still-active lane's carry *after* the tick's
            # saturate ran (so the corruption survives until the validity
            # sweep, exactly like a mid-window bit flip on real hardware)
            still = [i for i in active if self._lanes[i] is not None]
            self._states, _ = self.faults.poison_carry(self._states, still)
        return finished

    def _complete_lane(self, slot: int, now: float) -> SNNRequest:
        lane = self._lanes[slot]
        self._lanes[slot] = None  # freed immediately: next dispatch may reuse it
        req = lane.req
        if req._want_carry:
            # the freeze in batched_lane_window pinned the slot's state at
            # this lane's validity boundary, so the snapshot is exactly the
            # carry after the request's last real step -- even when the
            # pow2 chunk overshot the window
            req.carry_out = lane_state_take(self._states, slot)
        if lane.step_out is not None:
            req.step_outputs = (
                np.concatenate(lane.step_out, axis=0)
                if lane.step_out
                else np.zeros((0, self.net.n_classes), np.int64)
            )
        req.spike_counts = lane.counts
        req.service_s = now - lane.admitted_wall
        self._finish(req, now, stats_src=("chunks", lane.layer_events))
        return req

    def _finish(
        self, req: SNNRequest, now: float, stats_src: tuple, net=None
    ) -> None:
        if req._finalized:
            raise RuntimeError(f"request {req.uid} reached a terminal state twice")
        req._finalized = True
        req._suspended = None
        if req.status is None:
            req.status = "completed"
            req.tier = "full"
        req.prediction = int(np.argmax(req.spike_counts))
        if req._arrival_wall is not None:
            req.latency_s = now - req._arrival_wall
        if req.service_s is None:
            req.service_s = req.latency_s
        if self.report_design_point:
            # req.event_stats / req.design assemble lazily from these
            req._stats_src = stats_src
            req._net = net if net is not None else self.net
        self.n_served += 1
        self.metrics.record_finish(req, now)
        self._finalize(req)

    def _reject(self, req: SNNRequest, now: float) -> SNNRequest:
        """Terminal reject: the client learns now, not after a doomed wait."""
        if req._finalized:
            raise RuntimeError(f"request {req.uid} reached a terminal state twice")
        req._finalized = True
        req._suspended = None
        req.status = "rejected"
        if req._arrival_wall is not None:
            req.latency_s = now - req._arrival_wall
        self.metrics.record_reject(req, now)
        self._finalize(req)
        return req

    def _finalize(self, req: SNNRequest) -> None:
        """Invoke the completion callback; a raising callback is counted
        and contained -- it must never take the serving loop down."""
        # WAL: the terminal state lands before the callback runs, so a
        # crash inside a callback still replays as "served" (streaming
        # chunks are the manager's to journal, not ours)
        if self.journal is not None and not req._want_carry:
            self.journal.append("done", uid=req.uid, status=req.status)
        if req.on_complete is not None:
            try:
                req.on_complete(req)
            except Exception:
                self.metrics.inc("callback_failures")

    # -- NeurA-Guard: carry validity + lane quarantine -----------------------
    def sweep_carries(self) -> list[int]:
        """Validity sweep over the active lanes' device carries.

        A healthy carry is bounded by construction: the jitted tick
        saturates ``u`` into the layer's ``u_bits`` range and ``i_syn``
        into ``i_bits``, and ``prev_spk`` is binary.  Anything outside
        those bounds (or non-finite, for float-typed leaves) can only be
        corruption -- a bit flip, a bad DMA, an injected fault -- and the
        lane's trajectory is no longer trustworthy.  Returns the slots
        that fail; the supervisor quarantines them.
        """
        bad: list[int] = []
        for slot, lane in enumerate(self._lanes):
            if lane is None:
                continue
            carry = lane_state_take(self._states, slot)
            for st, cfg in zip(carry, self.net.layers):
                u = np.asarray(st.u)
                i_syn = np.asarray(st.i_syn)
                spk = np.asarray(st.prev_spk)
                ok = (
                    np.all(np.isfinite(u.astype(np.float64)))
                    and np.all(np.isfinite(i_syn.astype(np.float64)))
                    and int(u.min(initial=0)) >= int_min(cfg.u_bits)
                    and int(u.max(initial=0)) <= int_max(cfg.u_bits)
                    and int(i_syn.min(initial=0)) >= int_min(cfg.i_bits)
                    and int(i_syn.max(initial=0)) <= int_max(cfg.i_bits)
                    and int(spk.min(initial=0)) >= 0
                    and int(spk.max(initial=0)) <= 1
                )
                if not ok:
                    bad.append(slot)
                    break
        return bad

    def quarantine_lane(self, slot: int) -> SNNRequest | None:
        """Condemn a lane slot and salvage its request.

        The slot never admits again (only an engine restart reclaims it).
        The resident request restarts from its last trustworthy seam: a
        streaming chunk re-enters the queue carrying its chunk-start carry
        snapshot (``carry0``), anything else restarts from admission --
        both bit-exact, because everything computed *on* the corrupt lane
        is discarded.  Returns the requeued request (``None`` for an
        already-empty slot).
        """
        if not 0 <= slot < self.max_batch:
            raise ValueError(f"no lane slot {slot}")
        self._quarantined.add(slot)
        lane = self._lanes[slot]
        self._lanes[slot] = None
        if lane is None:
            return None
        req = lane.req
        req.restarts += 1
        req._suspended = None
        req._carry_in = lane.carry0  # chunk-start seam (None = fresh restart)
        self.sched.requeue_front(req)
        self.metrics.inc("quarantined_lanes")
        self.metrics.inc("quarantine_restarts")
        return req

    def warmup(self, n_steps: int | None = None, include_int32: bool = False) -> None:
        """Precompile the chunk programs a typical workload will hit.

        Compiles the power-of-two lane-window programs up to the chunk that
        covers ``n_steps`` (default: the network's nominal window) by
        running zero-input, zero-validity chunks through the pool, plus the
        event backend's sparse route when one is enabled: the eager (csr /
        gather) direct route gets a zero-raster single-sample run, and the
        jitted pallas route gets the sparse lane program precompiled *at
        each power-of-two chunk*, so the first sparse admission never pays
        compile latency mid-traffic.  Registered precision tiers get their
        express (degraded-serve) programs compiled at every power-of-two
        batch width up to the pool.  Call once before measuring or serving
        latency-sensitive traffic; without it the first cohorts pay jit
        compilation inside their reported latency.

        The default covers binary/uint8 spike streams (the common case).
        Pass ``include_int32=True`` when the workload also carries graded
        or large-valued inputs, so the int32 fallback programs (both the
        int32 input dtype and ``ff_mode="int32"``) compile up front too.

        An entry point that called
        ``repro.distributed.compat.enable_compilation_cache`` keeps these
        compiles in JAX's persistent cache, so an engine restarted with the
        same network skips them in the next process.

        Warmup traffic leaves no trace: ``n_served`` and the metrics layer
        are reset on the way out.
        """
        if self.in_flight:
            raise RuntimeError("warmup() requires an idle engine")
        T = self.net.n_steps if n_steps is None else n_steps
        cap = self._chunk_cap()
        combos = [(np.uint8, "f32_exact" if self._f32_input_max >= 1 else "int32", None)]
        if self._event_budget is not None:
            combos.append(
                (
                    np.uint8,
                    "f32_exact" if self._deep_f32_ok else "int32",
                    self._event_budget,
                )
            )
        if include_int32:
            combos += [(np.uint8, "int32", None), (np.int32, "int32", None)]
        for dtype, ff_mode, budget in dict.fromkeys(combos):
            k = 1
            while True:
                kk = min(k, cap)
                x = np.zeros((kk, self.max_batch, self.net.n_in), dtype)
                meta = np.zeros((2, self.max_batch), np.int32)
                self._states, packed = _lane_window_packed(
                    self.net, self.qparams, self._states, x, meta, ff_mode,
                    self._dmesh, budget,
                )
                np.asarray(packed)
                if kk == cap or k >= T:
                    break
                k <<= 1
        # zero-validity chunks record nothing, but they did advance the pool
        # states; reset so the next admission starts from a clean pool
        self._states = batched_lane_init(self.net, self.max_batch)
        if self.event_backend is not None and self._event_budget is None:
            req = SNNRequest(uid=-1, raster=np.zeros((T, self.net.n_in), np.uint8))
            self._serve_event(req)
        for tier in self.tiers:
            T_pad = 1 << max(0, (tier.steps(T) - 1)).bit_length()
            full = 1 << max(0, (self.max_batch - 1)).bit_length()
            for B_pad in [1 << i for i in range(full.bit_length())]:
                np.asarray(
                    run_int_batched(
                        tier.net,
                        tier.qparams,
                        np.zeros((T_pad, B_pad, self.net.n_in), np.int32),
                        np.zeros((B_pad,), np.int32),
                    ).spike_counts
                )
        self.n_served = 0
        self.metrics = ServeMetrics(self.metrics.window_s)

    # -- serve loops --------------------------------------------------------
    def poll(self) -> list[SNNRequest]:
        """One service round: a dispatch round, then one tick.

        The liveness guard lives here: a round that completes nothing,
        admits nothing, and runs no lanes while requests still queue is an
        *idle* round, and ``max_idle_ticks`` consecutive idle rounds raise
        :class:`EngineStalledError` with the queue snapshot and lane table
        (instead of ``drain()`` spinning forever on a wedged scheduler).
        """
        done = self._dispatch(time.perf_counter())
        done.extend(self.tick())
        if done or self.active_lanes > 0 or not self.sched:
            self._idle_rounds = 0
        else:
            self._idle_rounds += 1
            if self.max_idle_ticks is not None and self._idle_rounds >= self.max_idle_ticks:
                snap = self.sched.snapshot()
                lanes = [
                    None
                    if lane is None
                    else {"uid": lane.req.uid, "t": lane.t, "n_steps": lane.req.n_steps}
                    for lane in self._lanes
                ]
                raise EngineStalledError(
                    f"no progress for {self._idle_rounds} consecutive rounds "
                    f"with {len(self.sched)} queued request(s) and no active "
                    f"lanes; queue snapshot: {snap}; lanes: {lanes}",
                    snap,
                    lanes,
                )
        return done

    def drain(self) -> list[SNNRequest]:
        """Serve everything already submitted to completion."""
        done = []
        while self.in_flight:
            done.extend(self.poll())
        return done

    def run(self, requests: Sequence[SNNRequest]) -> list[SNNRequest]:
        """Open-loop offered-load replay of a request schedule.

        Requests become visible when the wall clock passes their
        ``arrival_s`` offset from the call's start (an arrival process, not
        a closed loop): per-request ``latency_s`` therefore includes
        queueing delay, which is what the open-loop serve benchmark
        reports its p95 over.  When the engine
        is idle and the next arrival is in the future it sleeps until then.
        """
        pending = sorted(requests, key=lambda r: r.arrival_s)
        t0 = time.perf_counter()
        for req in pending:
            req._arrival_wall = t0 + req.arrival_s
        done: list[SNNRequest] = []
        i = 0
        while i < len(pending) or self.in_flight:
            now = time.perf_counter()
            while i < len(pending) and pending[i]._arrival_wall <= now:
                self.submit(pending[i])
                i += 1
            if self.in_flight:
                done.extend(self.poll())
            elif i < len(pending):
                time.sleep(max(0.0, pending[i]._arrival_wall - now))
        return done


class AsyncSNNServer:
    """asyncio facade over :class:`SNNServeEngine`.

    ``submit`` returns a future resolved with the request at *any* terminal
    state -- completed, degraded, or rejected (distinguish via
    ``req.status``); a single background task drives the engine's poll loop
    while anything is in flight (yielding to the event loop between ticks)
    and exits when the engine goes idle.  A cancelled future never wedges
    the drive loop (its request still serves; the resolution is simply
    dropped), and if the engine raises mid-drive (e.g.
    :class:`EngineStalledError`) every pending future receives the
    exception instead of hanging forever -- the error is also kept on
    ``server.error``.
    """

    def __init__(self, engine: SNNServeEngine):
        self.engine = engine
        self._futures: dict[int, asyncio.Future] = {}
        self._task: asyncio.Task | None = None
        self.error: BaseException | None = None

    def submit(self, req: SNNRequest) -> "asyncio.Future[SNNRequest]":
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._futures[id(req)] = fut
        try:
            self.engine.submit(req)
        except Exception:
            self._futures.pop(id(req), None)
            raise
        if self._task is None or self._task.done():
            self._task = loop.create_task(self._drive())
        return fut

    async def serve(self, requests: Sequence[SNNRequest]) -> list[SNNRequest]:
        return list(await asyncio.gather(*[self.submit(r) for r in requests]))

    async def _drive(self) -> None:
        try:
            while self.engine.in_flight:
                for req in self.engine.poll():
                    fut = self._futures.pop(id(req), None)
                    if fut is not None and not fut.done():
                        fut.set_result(req)
                await asyncio.sleep(0)
        except Exception as e:
            # deliver the failure to every waiter rather than hanging them
            self.error = e
            pending, self._futures = self._futures, {}
            for fut in pending.values():
                if not fut.done():
                    fut.set_exception(e)
