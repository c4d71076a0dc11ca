"""Sharded multi-device execution layer for the Flexi-NeurA simulator.

The paper scales Flexi-NeurA by mapping the network across multiple
processing cores; the simulator's analogue is spreading *independent* work
items across JAX devices.  Two axes are independent by construction and
therefore shard bit-exactly:

* the **sample axis** -- every step operation is elementwise or a matmul
  over the batch dimension, so samples never interact
  (:func:`run_int_sharded`, :func:`run_float_sharded`,
  :func:`run_int_batched_sharded`, and the per-device lane shards the
  serving engine drives through :func:`wrap_lane_window`);
* the **candidate axis** of a population DSE sweep -- candidates share one
  static structure and differ only in quantized values / decay registers
  (:func:`run_int_population_sharded`).

Every entry point goes through ``repro.distributed.compat.shard_map`` (the
version shim) with the parameters replicated and the work axis partitioned;
no collectives are ever emitted, so a shard's trajectory is the exact
int32 arithmetic the serial path runs on that slice.  Bit-exactness per
shard + order-independent reassembly (concatenation along the work axis)
gives whole-result bit-exactness, which ``tests/test_shard.py`` asserts
against the serial paths -- including ragged remainders and the
single-device fallback.

Remainders and fallback rules:

* a work axis that does not divide by the shard count is **zero-padded**
  (samples) or **edge-repeated** (candidates) up to the next multiple, and
  the outputs are sliced back -- padding never leaks into results because
  lanes are independent;
* a mesh of one device (or ``mesh=None``) falls back to the serial code
  path *verbatim* -- not a 1-way shard_map -- so single-device deployments
  pay zero overhead and stay trivially bit-exact.

``resolve_mesh`` accepts the user-facing spellings every threaded ``mesh=``
keyword takes: ``None`` (serial), an ``int`` device count, ``"auto"`` (all
local devices), a :class:`DeviceMesh`, or a raw 1-D ``jax.sharding.Mesh``.

Four-chip scaling of the population sweep is measured by the
``dvs-dse-sweep-4chip`` benchmark cell (``PERF.md``); the design rules
(axis choices, donation, fallback) are documented in
``docs/ARCHITECTURE.md``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.backend import (
    InferenceBackend,
    SimRecord,
    _population_registers,
    _run_int_batched_jit,
    _stack_population_jit,
    get_backend,
    run_int_batched,
    run_int_population,
    stack_population,
)
from repro.distributed import compat

__all__ = [
    "DeviceMesh",
    "make_mesh",
    "resolve_mesh",
    "pad_to_shards",
    "host_bounds",
    "allgather_hosts",
    "run_int_sharded",
    "run_float_sharded",
    "stack_population_sharded",
    "place_replicated",
    "run_int_population_sharded",
    "run_int_batched_sharded",
    "wrap_lane_window",
]

#: Default mesh axis name for the sharded work dimension.
SHARD_AXIS = "shard"


@dataclasses.dataclass(frozen=True)
class DeviceMesh:
    """A 1-D device mesh over the sharded work axis (samples/candidates/lanes).

    ``mesh is None`` encodes the single-device fallback: callers holding a
    ``DeviceMesh`` with ``n_shards == 1`` run the serial code path verbatim.
    Frozen (and therefore hashable), so it can ride through ``jax.jit``
    static arguments without retriggering compilation across calls.
    """

    mesh: Mesh | None
    axis: str = SHARD_AXIS

    @property
    def n_shards(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.devices.size)

    def pad(self, n: int) -> int:
        """How many pad entries bring ``n`` up to a multiple of the shards."""
        return -n % self.n_shards


def make_mesh(
    data_parallel: int | None = None,
    *,
    devices=None,
    axis: str = SHARD_AXIS,
) -> DeviceMesh:
    """Build a 1-D :class:`DeviceMesh` over the first ``data_parallel`` devices.

    ``data_parallel=None`` uses every local device.  One device (requested
    or available) yields the fallback mesh (``mesh=None``): the sharded
    entry points then run their serial paths.  Asking for more devices than
    exist is an error -- callers that want best-effort clamp first (the
    serving engine does).
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices) if data_parallel is None else int(data_parallel)
    if n < 1:
        raise ValueError(f"data_parallel must be >= 1, got {data_parallel}")
    if n > len(devices):
        raise ValueError(
            f"data_parallel={n} exceeds the {len(devices)} available devices; "
            "force more host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N or clamp"
        )
    if n == 1:
        return DeviceMesh(mesh=None, axis=axis)
    return DeviceMesh(mesh=Mesh(np.asarray(devices[:n]), (axis,)), axis=axis)


def resolve_mesh(mesh) -> DeviceMesh | None:
    """Normalise a user-facing ``mesh=`` value.

    ``None`` -> ``None`` (serial; the caller keeps its untouched code path),
    ``"auto"`` -> all local devices, an ``int`` -> that many devices, a 1-D
    ``jax.sharding.Mesh`` or :class:`DeviceMesh` -> as given.
    """
    if mesh is None:
        return None
    if isinstance(mesh, DeviceMesh):
        return mesh
    if isinstance(mesh, Mesh):
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"sharded execution wants a 1-D mesh, got axes {mesh.axis_names}"
            )
        return DeviceMesh(mesh=mesh, axis=mesh.axis_names[0])
    if mesh == "auto":
        return make_mesh()
    if isinstance(mesh, int):
        return make_mesh(mesh)
    raise ValueError(
        f"cannot interpret mesh={mesh!r}; pass None, 'auto', an int device "
        "count, a DeviceMesh, or a 1-D jax.sharding.Mesh"
    )


def pad_to_shards(x, dmesh: DeviceMesh, axis: int, mode: str = "zero"):
    """Pad ``x`` along ``axis`` to a shard-divisible extent.

    ``mode="zero"`` appends zeros (samples: padded lanes are discarded after
    the run, and lane independence keeps them from perturbing real lanes);
    ``mode="edge"`` repeats the trailing entry (candidates: every lane must
    hold structurally valid parameters).
    """
    pad = dmesh.pad(x.shape[axis])
    if pad == 0:
        return x
    if mode == "zero":
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths)
    tail = jnp.take(x, jnp.full((pad,), x.shape[axis] - 1), axis=axis)
    return jnp.concatenate([x, tail], axis=axis)


# ---------------------------------------------------------------------------
# Sample-axis sharding: full-window simulation
# ---------------------------------------------------------------------------


def _record_parts(rec, spikes):
    """(counts, layer_spikes, input_events), tolerating third-party backends
    whose records predate ``SimRecord.input_events`` (same fallback as
    ``eval_int``'s serial path)."""
    in_ev = rec.input_events
    if in_ev is None:
        in_ev = jnp.sum(spikes != 0, axis=-1)
    return rec.spike_counts, tuple(rec.layer_spikes), in_ev


# --------------------------------------------------------------------------
# Multi-host fan-out (fleet-scale DSE: candidate lists partitioned by host)
# --------------------------------------------------------------------------


def host_bounds(n: int, index: int | None = None, count: int | None = None) -> tuple[int, int]:
    """Half-open slice [lo, hi) of ``n`` work items owned by this host.

    ``n`` must be a multiple of the process count -- callers pad the work
    axis to the host x device multiple first (exactly like
    :func:`pad_to_shards` pads to the device multiple), so every host runs
    an identically-shaped program.  ``index``/``count`` override the
    runtime's process rank/size for testing.
    """
    if count is None:
        count = compat.process_count()
    if index is None:
        index = compat.process_index()
    if not 0 <= index < count:
        raise ValueError(f"host index {index} outside [0, {count})")
    if n % count:
        raise ValueError(
            f"work axis of {n} does not divide over {count} hosts; pad it "
            f"to a multiple first (see pad_to_shards)"
        )
    per = n // count
    return index * per, (index + 1) * per


def allgather_hosts(local, count: int | None = None, gather=None):
    """Concatenate each host's leading-axis slice back into the full axis.

    The inverse of :func:`host_bounds` partitioning: every host contributes
    its local results and receives the concatenation in rank order.  At
    ``process_count() == 1`` (including the forced-host-device fallback)
    this is the identity, so single-host code pays nothing.  ``gather``
    injects a replacement for ``multihost_utils.process_allgather`` in
    tests.
    """
    if count is None:
        count = compat.process_count()
    if count == 1:
        return np.asarray(local)
    if gather is None:  # pragma: no cover - needs a real multi-host runtime
        from jax.experimental import multihost_utils

        def gather(x):
            return multihost_utils.process_allgather(x, tiled=True)

    return np.asarray(gather(local))


@functools.partial(jax.jit, static_argnames=("net", "backend"))
def _run_int_serial_jit(net, qparams, spikes, backend):
    return _record_parts(backend.run_int(net, list(qparams), spikes), spikes)


@functools.partial(jax.jit, static_argnames=("net", "dmesh", "backend"))
def _run_int_sharded_jit(net, qparams, spikes, dmesh, backend):
    def local(qp, s):
        return _record_parts(backend.run_int(net, list(qp), s), s)

    ax = dmesh.axis
    fn = compat.shard_map(
        local,
        mesh=dmesh.mesh,
        in_specs=(P(), P(None, ax)),
        out_specs=(P(ax), P(None, ax), P(None, ax)),
        check_vma=False,  # no replication claims: every output varies over ax
    )
    return fn(tuple(qparams), spikes)


def run_int_sharded(
    net, qparams, spikes_in, mesh, backend: str | InferenceBackend = "reference"
) -> SimRecord:
    """``run_int`` with the sample axis spread across a device mesh.

    Bit-exact with the serial backend run: per-sample dynamics are
    independent, each shard executes the identical int32 program on its
    slice, and reassembly is concatenation.  A ragged batch is zero-padded
    up to the shard multiple and sliced back.  ``mesh`` resolving to one
    device (or ``None``) runs the serial backend directly.

    A ``jit_compatible = False`` backend is asked for a ``jit_surrogate``
    before any mesh partition is abandoned: ``backend="event"`` (auto /
    gather / pallas) shards through the fixed-capacity pallas strategy with
    a budget measured from the concrete rasters, bit-exact with its serial
    run.  Only a backend with no surrogate (an *explicit* ``strategy="csr"``
    opt-in to the host-side path) falls back to the serial run -- with a
    ``UserWarning``, and only when a real multi-device partition is being
    given up (a 1-device mesh honors ``jit_compatible = False`` silently:
    the serial path was the contract anyway).
    """
    dmesh = resolve_mesh(mesh)
    resolved = get_backend(backend)
    spikes = jnp.asarray(spikes_in)
    if dmesh is None or dmesh.n_shards == 1:
        if not resolved.jit_compatible:  # e.g. event csr: compiles internally
            return resolved.run_int(net, list(qparams), spikes)
        counts, layers, in_ev = _run_int_serial_jit(net, list(qparams), spikes, resolved)
        return SimRecord(spike_counts=counts, layer_spikes=list(layers), input_events=in_ev)
    if not resolved.jit_compatible:
        surrogate = resolved.jit_surrogate(net, spikes)
        if surrogate is None:
            warnings.warn(
                f"backend {resolved.name!r} is not jit-compatible and offers no "
                f"jit surrogate; mesh ignored ({dmesh.n_shards} shards abandoned "
                "for the serial path). The event backend's strategy='pallas' "
                "shards; strategy='csr' is host-side by design.",
                UserWarning,
                stacklevel=2,
            )
            return resolved.run_int(net, list(qparams), spikes)
        resolved = surrogate
    B = spikes.shape[1]
    padded = pad_to_shards(spikes, dmesh, axis=1)
    counts, layers, in_ev = _run_int_sharded_jit(net, list(qparams), padded, dmesh, resolved)
    return SimRecord(
        spike_counts=counts[:B],
        layer_spikes=[l[:, :B] for l in layers],
        input_events=in_ev[:, :B],
    )


@functools.partial(jax.jit, static_argnames=("net", "backend", "spike_fn"))
def _run_float_serial_jit(net, params, spikes, backend, spike_fn):
    return _record_parts(backend.run_float(net, list(params), spikes, spike_fn), spikes)


@functools.partial(jax.jit, static_argnames=("net", "dmesh", "backend", "spike_fn"))
def _run_float_sharded_jit(net, params, spikes, dmesh, backend, spike_fn):
    def local(p, s):
        return _record_parts(backend.run_float(net, list(p), s, spike_fn), s)

    ax = dmesh.axis
    fn = compat.shard_map(
        local,
        mesh=dmesh.mesh,
        in_specs=(P(), P(None, ax)),
        out_specs=(P(ax), P(None, ax), P(None, ax)),
        check_vma=False,
    )
    return fn(tuple(params), spikes)


def run_float_sharded(
    net, params, spikes_in, spike_fn, mesh, backend: str | InferenceBackend = "reference"
) -> SimRecord:
    """``run_float`` with the sample axis spread across a device mesh.

    Same contract as :func:`run_int_sharded`; float simulation shards just
    as exactly because each sample's trajectory is still independent (the
    f32 ops run per sample regardless of how the batch is sliced).
    """
    dmesh = resolve_mesh(mesh)
    resolved = get_backend(backend)
    spikes = jnp.asarray(spikes_in)
    if dmesh is None or dmesh.n_shards == 1:
        counts, layers, in_ev = _run_float_serial_jit(
            net, list(params), spikes, resolved, spike_fn
        )
        return SimRecord(spike_counts=counts, layer_spikes=list(layers), input_events=in_ev)
    B = spikes.shape[1]
    padded = pad_to_shards(spikes, dmesh, axis=1)
    counts, layers, in_ev = _run_float_sharded_jit(
        net, list(params), padded, dmesh, resolved, spike_fn
    )
    return SimRecord(
        spike_counts=counts[:B],
        layer_spikes=[l[:, :B] for l in layers],
        input_events=in_ev[:, :B],
    )


# ---------------------------------------------------------------------------
# Candidate-axis sharding: the population DSE fan-out
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("net",))
def _population_serial_jit(net, stacked, beta_regs, alpha_regs, spikes):
    return run_int_population(
        net, list(stacked), beta_regs, alpha_regs, spikes, return_events=True
    )


@functools.partial(jax.jit, static_argnames=("net", "dmesh"))
def _population_sharded_jit(net, stacked, beta_regs, alpha_regs, spikes, dmesh):
    def local(st, b, a, s):
        return run_int_population(net, list(st), b, a, s, return_events=True)

    ax = dmesh.axis
    fn = compat.shard_map(
        local,
        mesh=dmesh.mesh,
        in_specs=(P(ax), P(ax), P(ax), P()),
        out_specs=(P(ax), P(ax)),
        check_vma=False,
    )
    return fn(tuple(stacked), beta_regs, alpha_regs, spikes)


@functools.partial(jax.jit, static_argnames=("shapes", "sharding"))
def _unflatten_population_jit(flat, shapes, sharding):
    # column slices of a buffer split along its rows: every device unpacks
    # its own candidates, nothing crosses between devices
    leaves, at = [], 0
    for shape in shapes:
        width = math.prod(shape)
        leaf = flat[:, at : at + width].reshape(flat.shape[0], *shape)
        leaves.append(jax.lax.with_sharding_constraint(leaf, sharding))
        at += width
    return leaves


def stack_population_sharded(nets, qparams_list, mesh):
    """``stack_population``, padded to the shard count and placed on ``mesh``.

    The same one-program build, with the candidate axis padded by repeating
    the last candidate and every output packed into one int32 buffer; that
    buffer moves onto the mesh in one transfer, split along the candidate
    axis, and one program there unpacks it into the stacked leaves and
    decay registers, each with ``NamedSharding(mesh, P(axis))`` -- the
    layout :func:`run_int_population_sharded` reads, so its launches move
    only their spikes.  A mesh of one device returns ``stack_population``'s
    result unchanged.
    """
    dmesh = resolve_mesh(mesh)
    if dmesh is None or dmesh.n_shards == 1:
        return stack_population(nets, qparams_list)
    qparams_list = [list(qp) for qp in qparams_list]
    sharding = NamedSharding(dmesh.mesh, P(dmesh.axis))
    flat = _stack_population_jit(
        qparams_list, _population_registers(nets), pad=dmesh.pad(len(nets)), flat=True
    )
    n_layers = len(qparams_list[0])
    shapes = tuple(a.shape for a in jax.tree.leaves(qparams_list[0])) + ((n_layers,),) * 2
    leaves = _unflatten_population_jit(jax.device_put(flat, sharding), shapes, sharding)
    stacked = jax.tree.unflatten(jax.tree.structure(qparams_list[0]), leaves[:-2])
    return stacked, leaves[-2], leaves[-1]


def place_replicated(x, mesh):
    """``x`` whole on every device of ``mesh``; on the default device with no mesh.

    On a mesh the result is ``NamedSharding(mesh, P())``, the layout
    :func:`run_int_population_sharded` reads its spikes in, so nothing
    derived from it moves between devices again.  The host sends ``x`` to
    each device itself: on a TPU v5e host of four chips that took as long,
    within 5%, as one transfer to the first chip and a broadcast from it.
    """
    dmesh = resolve_mesh(mesh)
    if dmesh is None or dmesh.n_shards == 1:
        return jax.device_put(x)
    return jax.device_put(x, NamedSharding(dmesh.mesh, P()))


def run_int_population_sharded(
    net, stacked_qparams, beta_regs, alpha_regs, spikes_in, mesh,
    return_events: bool = False,
):
    """``run_int_population`` with the *candidate* axis spread across devices.

    Each device scores its slice of the population through the identical
    vmapped dynamic-register sweep, so per-candidate results are bit-exact
    with the one-device sweep (and with serial ``eval_int``).  A population
    that does not divide by the shard count is padded by repeating the last
    candidate (structurally valid work, discarded on return); that padding
    is the profiler span ``neura.dse.shard_pad``.  A population from
    :func:`stack_population_sharded` is already padded and placed, and
    passes through without a copy.
    """
    dmesh = resolve_mesh(mesh)
    spikes = jnp.asarray(spikes_in)
    if dmesh is None or dmesh.n_shards == 1:
        counts, emitted = _population_serial_jit(
            net, list(stacked_qparams), beta_regs, alpha_regs, spikes
        )
        return (counts, emitted) if return_events else counts
    n_cand = beta_regs.shape[0]
    with jax.profiler.TraceAnnotation("neura.dse.shard_pad"):
        stacked = [
            jax.tree.map(lambda a: pad_to_shards(a, dmesh, axis=0, mode="edge"), qp)
            for qp in stacked_qparams
        ]
        beta = pad_to_shards(beta_regs, dmesh, axis=0, mode="edge")
        alpha = pad_to_shards(alpha_regs, dmesh, axis=0, mode="edge")
    counts, emitted = _population_sharded_jit(net, stacked, beta, alpha, spikes, dmesh)
    counts, emitted = counts[:n_cand], emitted[:n_cand]
    if return_events:
        return counts, emitted
    return counts


# ---------------------------------------------------------------------------
# Sample-axis sharding: the ragged batched runner (serving's whole-window form)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("net", "dmesh"))
def _run_int_batched_sharded_jit(net, qparams, rasters, lengths, dmesh):
    def local(qp, r, l):
        return _run_int_batched_jit(net, list(qp), r, l)

    ax = dmesh.axis
    fn = compat.shard_map(
        local,
        mesh=dmesh.mesh,
        in_specs=(P(), P(None, ax), P(ax)),
        out_specs=(P(ax), P(None, None, ax), P(None, ax)),
        check_vma=False,
    )
    return fn(tuple(qparams), rasters, lengths)


def run_int_batched_sharded(net, qparams, rasters, lengths, mesh) -> SimRecord:
    """Sharded form of ``backend.run_int_batched`` (callers pass ``mesh=``
    there; this is the implementation it dispatches to).

    Pads the sample axis with zero rasters of length 0 -- the in-scan
    validity masking already zeroes every contribution of a length-0 lane,
    so padding is inert -- and slices the reassembled record back to the
    true batch.
    """
    dmesh = resolve_mesh(mesh)
    rasters = jnp.asarray(rasters).astype(jnp.int32)
    T, B, _ = rasters.shape
    lengths = (
        jnp.full((B,), T, jnp.int32)
        if lengths is None
        else jnp.asarray(lengths, jnp.int32)
    )
    if lengths.shape != (B,):
        raise ValueError(f"lengths must be [B]={B}, got {lengths.shape}")
    if dmesh is None or dmesh.n_shards == 1:
        return run_int_batched(net, qparams, rasters, lengths)
    padded_r = pad_to_shards(rasters, dmesh, axis=1)
    padded_l = pad_to_shards(lengths, dmesh, axis=0)  # zero length = inert lane
    counts, emitted, input_events = _run_int_batched_sharded_jit(
        net, list(qparams), padded_r, padded_l, dmesh
    )
    return SimRecord(
        spike_counts=counts[:B],
        layer_spikes=[emitted[:, i, :B] for i in range(len(net.layers))],
        input_events=input_events[:, :B],
    )


# ---------------------------------------------------------------------------
# Lane-axis sharding: the serving engine's per-device lane shards
# ---------------------------------------------------------------------------


def wrap_lane_window(fn, dmesh: DeviceMesh):
    """Partition a lane-pool window function across a device mesh.

    ``fn(qparams, states, x_chunk, lane_meta) -> (states, packed)`` is the
    serving engine's whole-pool chunk advance; the wrapper splits the lane
    axis so each device carries ``n_lanes / n_shards`` resident lanes --
    lane state lives on its device across ticks, one jitted dispatch still
    advances every shard, and admission stays a global host-side decision
    (the engine just writes into whichever lane index is free; the index
    *is* the device placement).

    Specs: parameters replicated; states sharded on their leading lane
    axis; ``x_chunk`` [k, n_lanes, n_in] and ``lane_meta`` [2, n_lanes]
    sharded on axis 1; outputs mirror the inputs.  Lanes never interact, so
    a sharded pool is bit-exact with the unsharded pool (asserted by the
    serve parity tests).
    """
    ax = dmesh.axis
    return compat.shard_map(
        fn,
        mesh=dmesh.mesh,
        in_specs=(P(), P(ax), P(None, ax), P(None, ax)),
        out_specs=(P(ax), P(None, ax)),
        check_vma=False,
    )
