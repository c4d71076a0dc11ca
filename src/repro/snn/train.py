"""BPTT trainer for Flexi-NeurA networks (the Flex-plorer "Learning" stage).

Trains the float model with surrogate gradients (hardware-ordered dynamics,
see ``repro.core.snn_layer.float_layer_step``), then hands weights + leak
parameters to the Explorer for precision DSE, exactly as the paper's flow
(GUI -> Learning -> Explorer -> RTL Configurator) does.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as backend_lib
from repro.core import lowering
from repro.core import shard as shard_lib
from repro.core.network import NetworkConfig, init_float_params, run_float, run_int
from repro.core.snn_layer import Topology
from repro.data.snn_datasets import SpikeDataset
from repro.snn import qat as qat_lib
from repro.snn.surrogate import fast_sigmoid
from repro.train import optimizer as opt_lib

__all__ = [
    "TrainResult",
    "train_snn",
    "eval_float",
    "eval_int",
    "eval_int_population",
    "spike_count_loss",
]


def spike_count_loss(counts, labels, rate_reg: float = 1e-4, total_spikes=None):
    """Cross-entropy over output spike counts (rate decoding) + rate penalty.

    The rate penalty encourages the sparsity that the event-driven hardware's
    latency/energy model rewards -- the software knob that corresponds to the
    paper's observed sparse traffic.
    """
    logp = jax.nn.log_softmax(counts.astype(jnp.float32))
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    reg = 0.0
    if total_spikes is not None:
        reg = rate_reg * jnp.mean(total_spikes)
    return ce + reg


@dataclasses.dataclass
class TrainResult:
    params: list
    history: list[dict]
    net: NetworkConfig
    # set when trained quantization-aware: the precision-overridden network
    # the parameters were trained *for* (deploy by quantize_params on it)
    qat_net: NetworkConfig | None = None


def train_snn(
    net: NetworkConfig,
    train_ds: SpikeDataset,
    *,
    epochs: int = 8,
    batch_size: int = 128,
    lr: float = 2e-3,
    seed: int = 0,
    rate_reg: float = 1e-4,
    surrogate_slope: float = 25.0,
    log_every: int = 0,
    eval_ds: SpikeDataset | None = None,
    qat: "qat_lib.PrecisionConfig | NetworkConfig | None" = None,
    init_params: list | None = None,
) -> TrainResult:
    """Surrogate-gradient BPTT; optionally quantization-aware.

    ``qat`` switches the forward pass to the straight-through fake-quant
    simulation (``repro.snn.qat.run_qat``) at the given precisions -- a
    :class:`~repro.snn.qat.PrecisionConfig` overrides ``net``'s precision
    knobs, a full :class:`NetworkConfig` is used as-is (it must share
    ``net``'s structure).  The trained parameters then deploy through the
    ordinary ``quantize_params`` -> ``eval_int`` path bit-exactly at those
    precisions.  ``init_params`` warm-starts from existing float parameters
    (e.g. a float-trained network being QAT-fine-tuned); default is a fresh
    ``init_float_params``.
    """
    key = jax.random.PRNGKey(seed)
    params = list(init_params) if init_params is not None else init_float_params(key, net)
    spike_fn = fast_sigmoid(surrogate_slope)
    if qat is None:
        qat_net = None
    elif isinstance(qat, qat_lib.PrecisionConfig):
        qat_net = qat.apply(net)
    else:
        qat_net = qat

    # ceil: `SpikeDataset.batches` yields the ragged tail batch too, so an
    # epoch really takes ceil(n / batch) optimizer steps (schedule horizon)
    eff_batch = min(batch_size, len(train_ds.labels))
    steps_per_epoch = max(1, -(-len(train_ds.labels) // eff_batch))
    optimizer = opt_lib.adamw(
        opt_lib.linear_warmup_cosine(lr, steps_per_epoch, epochs * steps_per_epoch)
    )
    opt_state = optimizer.init(params)

    def loss_fn(params, spikes, labels):
        if qat_net is not None:
            rec = qat_lib.run_qat(qat_net, params, spikes, spike_fn)
        else:
            rec = run_float(net, params, spikes, spike_fn)
        total = sum(jnp.sum(s) for s in rec.layer_spikes) / spikes.shape[1]
        loss = spike_count_loss(rec.spike_counts, labels, rate_reg, total)
        acc = jnp.mean((rec.predictions() == labels).astype(jnp.float32))
        return loss, acc

    @jax.jit
    def train_step(params, opt_state, spikes, labels):
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, spikes, labels)
        grads, gnorm = opt_lib.clip_by_global_norm(grads, 1.0)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = opt_lib.apply_updates(params, updates)
        return params, opt_state, loss, acc, gnorm

    rng = np.random.default_rng(seed)
    history = []
    for epoch in range(epochs):
        t0 = time.time()
        losses, accs = [], []
        for spikes, labels in train_ds.batches(eff_batch, rng):
            params, opt_state, loss, acc, gnorm = train_step(
                params, opt_state, jnp.asarray(spikes), jnp.asarray(labels)
            )
            losses.append(float(loss))
            accs.append(float(acc))
        entry = {
            "epoch": epoch,
            "loss": float(np.mean(losses)),
            "train_acc": float(np.mean(accs)),
            "seconds": time.time() - t0,
        }
        if eval_ds is not None:
            if qat_net is not None:
                entry["eval_acc"] = qat_lib.eval_qat(qat_net, params, eval_ds, surrogate_slope)
            else:
                entry["eval_acc"] = eval_float(net, params, eval_ds, surrogate_slope)
        history.append(entry)
        if log_every and (epoch % log_every == 0 or epoch == epochs - 1):
            print(f"[train_snn:{net.name}] {entry}")
    return TrainResult(params=params, history=history, net=net, qat_net=qat_net)


def eval_float(
    net,
    params,
    ds: SpikeDataset,
    surrogate_slope: float = 25.0,
    batch_size: int = 256,
    backend="reference",
    mesh=None,
) -> float:
    spike_fn = fast_sigmoid(surrogate_slope)
    dmesh = shard_lib.resolve_mesh(mesh)

    if dmesh is not None and dmesh.n_shards > 1:
        def fwd(params, spikes):
            return shard_lib.run_float_sharded(
                net, params, spikes, spike_fn, dmesh, backend=backend
            ).predictions()
    else:
        @jax.jit
        def fwd(params, spikes):
            return run_float(net, params, spikes, spike_fn, backend=backend).predictions()

    correct = total = 0
    for spikes, labels in ds.batches(batch_size):
        preds = np.asarray(fwd(params, jnp.asarray(spikes)))
        correct += int((preds == labels).sum())
        total += len(labels)
    return correct / max(1, total)


def eval_int(
    net,
    qparams,
    ds: SpikeDataset,
    batch_size: int = 256,
    return_stats: bool = False,
    backend="reference",
    mesh=None,
):
    """Bit-exact hardware-faithful accuracy (the DSE's accuracy evaluator).

    With ``return_stats``, also returns per-layer mean events per step and
    input events per step -- the latency/energy model inputs (see
    ``hw_model.EventTraffic``).  ``backend`` selects the simulation engine
    (see ``repro.core.backend``); every registered backend is bit-exact on
    its supported configs, so the choice is a speed knob, not an accuracy
    knob.  Backends that declare ``jit_compatible = False`` (the
    event-driven backend sizes its gather budgets from concrete spike
    counts) are called without the outer jit and compile internally.

    ``mesh`` (``None`` | ``"auto"`` | int | ``repro.core.shard.DeviceMesh``)
    spreads each batch's sample axis across devices -- bit-exact with the
    serial path (see ``repro.core.shard``).  A non-jit-compatible backend
    shards through its ``jit_surrogate`` when it has one (``backend="event"``
    upgrades to the fixed-capacity pallas strategy per batch); only a
    backend with no surrogate (event ``strategy="csr"``) warns -- from
    ``run_int_sharded``, once per process -- and runs serially.
    """
    resolved = backend_lib.get_backend(backend)
    dmesh = shard_lib.resolve_mesh(mesh)

    if dmesh is not None and dmesh.n_shards > 1:
        def fwd(spikes):
            rec = shard_lib.run_int_sharded(net, qparams, spikes, dmesh, backend=resolved)
            return (
                rec.predictions(),
                [jnp.mean(s, axis=1) for s in rec.layer_spikes],
                jnp.mean(rec.input_events, axis=1),
            )
    else:
        def fwd(spikes):
            rec = run_int(net, qparams, spikes, backend=resolved)
            # tolerate third-party backends that predate SimRecord.input_events
            in_ev = rec.input_events
            if in_ev is None:
                in_ev = jnp.sum(spikes != 0, axis=-1)
            return (
                rec.predictions(),
                [jnp.mean(s, axis=1) for s in rec.layer_spikes],
                jnp.mean(in_ev, axis=1),
            )

        if resolved.jit_compatible:
            fwd = jax.jit(fwd)

    correct = total = 0
    layer_ev = None
    in_ev = None
    for spikes, labels in ds.batches(batch_size):
        spikes = jnp.asarray(spikes)
        preds, evs, iev = fwd(spikes)
        correct += int((np.asarray(preds) == labels).sum())
        n = len(labels)
        total += n
        # weight each batch's per-sample mean by its size so a partial
        # final batch doesn't bias the dataset-level event traffic
        evs = [np.asarray(e) * n for e in evs]
        iev = np.asarray(iev) * n
        layer_ev = evs if layer_ev is None else [a + b for a, b in zip(layer_ev, evs)]
        in_ev = iev if in_ev is None else in_ev + iev
    acc = correct / max(1, total)
    if not return_stats:
        return acc
    layer_ev = [e / max(1, total) for e in layer_ev]
    in_ev = in_ev / max(1, total)
    return acc, {"input_events_per_step": in_ev, "layer_events_per_step": layer_ev}


@functools.partial(jax.jit, static_argnums=0)
def _population_fwd(net, stacked_qparams, beta_regs, alpha_regs, spikes):
    counts, emitted = backend_lib.run_int_population(
        net, stacked_qparams, beta_regs, alpha_regs, spikes, return_events=True
    )
    # [P, batch] predictions; [P, T, L] batch-mean emitted events; [T] input
    return (
        jnp.argmax(counts, axis=-1),
        jnp.mean(emitted, axis=-1),
        jnp.mean(jnp.sum(spikes != 0, axis=-1), axis=-1),
    )


@functools.partial(jax.jit, static_argnums=(1, 2))
def _batch_windows(spikes, size, int8):
    """Placed ``[N, T, C]`` rasters cut into time-major ``[T, n, C]`` batches of ``size``.

    The last batch holds what is left (``N % size`` samples, if any). With
    ``int8`` each batch is cast to int8, the dtype that takes the population
    sweep's int8 planes (``lowering.population_feed``). Also returns each
    batch's mean input events per step ``[T]``, so that no launch needs a
    reduction of its own for them.
    """
    batches = [jnp.swapaxes(spikes[i : i + size], 0, 1) for i in range(0, spikes.shape[0], size)]
    in_ev = [jnp.mean(jnp.sum(b != 0, axis=-1), axis=-1) for b in batches]
    return [b.astype(jnp.int8) for b in batches] if int8 else batches, in_ev


_fits_int8 = jax.jit(lowering.fits_int8)


def eval_int_population(
    net,
    candidate_nets: Sequence[NetworkConfig],
    qparams_list: Sequence[list],
    ds: SpikeDataset,
    batch_size: int = 256,
    return_stats: bool = False,
    mesh=None,
):
    """Bit-exact accuracies for a population of precision candidates at once.

    All candidates share ``net``'s static structure (the DSE varies only
    quantized values and CG decay registers), so one jitted, vmapped program
    scores the whole population per data batch -- and, because the jit is
    module-level with the parameters passed as (stacked) arguments rather
    than closed over, successive populations of the same size reuse the
    compiled program.  This is what makes population-mode DSE fast: the
    serial path pays one trace+compile per candidate.

    Returns a float accuracy per candidate, identical to calling
    :func:`eval_int` per candidate (asserted by the parity suite).  With
    ``return_stats``, also returns one per-candidate event-traffic dict of
    the same shape as ``eval_int(..., return_stats=True)`` -- each
    candidate quantizes differently and therefore spikes differently, which
    is exactly what the event-aware DSE cost needs to see.

    ``mesh`` spreads the *candidate* axis across devices (the DSE fan-out):
    each device sweeps its slice of the population through the identical
    vmapped program, so per-candidate results stay bit-exact with both the
    one-device sweep and serial :func:`eval_int` (see ``repro.core.shard``).

    Each call builds its population in one program and, on a mesh, pads
    and places it over the devices once (``stack_population_sharded``).
    It then places ``ds.spikes`` on the device once, in its stored
    ``[N, T, C]`` layout (on a mesh, whole on every device), and cuts it
    there into time-major batches in one program, with each batch's input
    events; nothing is gathered or transposed on the host, and a batch's
    launch moves no spikes.  Before the cut, one reduction on the device
    tells the host whether the whole set fits int8 (``lowering.fits_int8``):
    then the batches are cut as int8 and every product of the sweep takes
    the int8 planes, else the first layer's takes the int32 dot.  The set
    takes N x T x C bytes of each device for the call, twice while it is
    cut: 71.7 MB for 1,024 rasters of 100 steps over 700 channels in uint8.

    Profiler spans: ``neura.dse.stack`` (arguments ``candidates``;
    ``cores``, the network's physical cores after the split of wide layers;
    ``recurrent_macs``, the sum of ``n_out ** 2`` over ATA-T layers; and
    ``shards``, the devices the population was placed over) around building
    the population; ``neura.dse.place`` (``bytes`` placed on each device,
    ``samples``) around placing the rasters, until they are on the device,
    their range test and launching their cut into batches; then one
    ``neura.dse.batch`` per data batch (``index``, ``samples``, ``steps``;
    ``ff_feed``, the lowering the batch's first product takes:
    ``lowering.INT8_PLANES`` or ``lowering.XLA_INT32``) holding its
    ``neura.dse.launch`` and ``neura.dse.readback``; the rest of a batch is
    the host reduction.
    """
    P = len(candidate_nets)
    # dense recurrent multiply-accumulates per sample and step of one candidate
    rec_macs = sum(lc.n_out**2 for lc in net.layers if lc.topology == Topology.ATA_T)
    with jax.profiler.TraceAnnotation(
        "neura.dse.stack", candidates=P, cores=net.n_cores, recurrent_macs=rec_macs
    ) as span:
        backend_lib.check_population_structure(net, candidate_nets)
        dmesh = shard_lib.resolve_mesh(mesh)
        stacked, beta_regs, alpha_regs = shard_lib.stack_population_sharded(
            candidate_nets, qparams_list, dmesh
        )
        span.set_metadata(shards=1 if dmesh is None else dmesh.n_shards)
    # (predictions, batch-mean emitted events) of one time-major batch
    if dmesh is not None and dmesh.n_shards > 1:
        def pop_fwd(spikes):
            counts, emitted = shard_lib.run_int_population_sharded(
                net, stacked, beta_regs, alpha_regs, spikes, dmesh, return_events=True
            )
            return jnp.argmax(counts, axis=-1), jnp.mean(emitted, axis=-1)
    else:
        def pop_fwd(spikes):
            return _population_fwd(net, stacked, beta_regs, alpha_regs, spikes)[:2]

    n_samples, steps = ds.spikes.shape[:2]
    size = max(1, min(batch_size, n_samples))
    with jax.profiler.TraceAnnotation(
        "neura.dse.place", bytes=int(ds.spikes.nbytes), samples=n_samples
    ):
        placed = shard_lib.place_replicated(ds.spikes, dmesh)
        int8 = bool(_fits_int8(placed))  # waits for the placement, then one reduction
        windows, window_in_ev = _batch_windows(placed, size, int8)
        del placed  # freed once cut: the windows hold the set
    ff_feed = lowering.population_feed(windows[0].dtype) if windows else None

    correct = np.zeros(P, np.int64)
    total = 0
    layer_ev = None  # [P, T, L] running size-weighted sum of batch means
    in_ev = None  # [T]
    for index, spikes in enumerate(windows):
        labels = ds.labels[index * size : (index + 1) * size]
        n = len(labels)
        with jax.profiler.TraceAnnotation(
            "neura.dse.batch", index=index, samples=n, steps=steps, ff_feed=ff_feed
        ):
            with jax.profiler.TraceAnnotation("neura.dse.launch"):
                out = pop_fwd(spikes)
            with jax.profiler.TraceAnnotation("neura.dse.readback"):
                preds, evs, iev = (np.asarray(a) for a in (*out, window_in_ev[index]))
            preds, evs = preds[:P], evs[:P]  # a mesh's padding candidates
            correct += (preds == labels[None, :]).sum(axis=1)
            total += n
            # size-weighted like eval_int: partial batches must not bias traffic
            evs, iev = evs * n, iev * n
            layer_ev = evs if layer_ev is None else layer_ev + evs
            in_ev = iev if in_ev is None else in_ev + iev
    accs = correct / max(1, total)
    if not return_stats:
        return accs
    layer_ev = layer_ev / max(1, total)
    in_ev = in_ev / max(1, total)
    stats = [
        {
            "input_events_per_step": in_ev,
            "layer_events_per_step": [layer_ev[p, :, l] for l in range(layer_ev.shape[2])],
        }
        for p in range(P)
    ]
    return accs, stats
