"""Chip smoke: drive the SNN main path once on a TPU, bit-exact to reference.

    python chip_smoke.py            # one chip: phases 1-6
    python chip_smoke.py --chips 4  # four chips: only the sharded paths

The main path is the paper's 256-128-10 LIF network (``w_bits=6``,
``u_bits=16``, as ``repro.launch.serve_snn`` builds it, weights drawn from
a seed) on mnist-like traffic at its default T=25, through the entry points
a user calls:

1. device  -- the JAX version and the device JAX reports; anything but a
   TPU exits 1 (there is no CPU fallback).
2. batch   -- ``run_int`` over 1024 mnist-like samples with the
   ``reference``, ``fused`` and ``EventBackend("pallas")`` backends, at
   ``w_bits=6`` and again at ``w_bits=12``: every record bit-identical to
   ``reference``, and the jitted fused / event programs hold their Pallas
   kernels (``tpu_custom_call``).  The device's integer and f32 matmuls
   are checked against numpy on the same operands.
3. serve   -- ``SNNServeEngine`` with the event backend's pallas strategy
   serves 64 mnist-like and 64 Bernoulli(3%) requests; each equals a serial
   ``run_int`` on its raster, and both the lane route and ``"event-pallas"``
   served.
4. stream  -- ``StreamSessionManager`` sessions fed uneven chunks, with idle
   eviction and restore; each equals one serial ``run_int`` over its stream.
5. dse+qat -- ``eval_int_population`` over 16 candidate precisions, each
   equal to its own serial ``eval_int``; then ``train_snn(qat=...)`` for one
   epoch of a few batches, with a finite loss.
6. shd     -- the SHD network of ``perfbench/configs/shd-syn-atat-700-200-20.json``
   (700-200-20, Synaptic, ATA-T hidden layer split over three fan-in cores,
   T=100) on 256 ``shd_like`` samples at 700 channels: ``eval_int`` through
   the ``fused``, ``event`` and ``EventBackend("pallas")`` backends and
   ``eval_int_population`` equal to ``reference``; the 700-wide int32 dot
   equal to numpy at ``w_bits=16``, where the f32 lowering must be refused;
   ``SNNServeEngine`` serving 32 of the samples, each equal to a serial
   ``run_int``.

With ``--chips 4`` only the sharded paths run, each against its one-device
form: ``SNNServeEngine(data_parallel=4)``, ``eval_int(mesh=4)`` and
``eval_int_population(mesh=4)``.

Each phase prints one line: what ran, the lowering each layer took
(``repro.core.lowering`` names), its bit-exact verdict, and host-clock
seconds of a cold run with the compile counts behind them (set-up time,
not a measurement).  Compiled programs persist in JAX's compilation cache
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` here), so a
second run shows cache hits.  Any failure exits non-zero; the last line of
a passing run is one JSON object naming the device.  Everything runs in
this one process: the chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
SEED = 0
T = 25  # mnist_like's default window
HIDDEN = 128
BATCH = 1024
N_REQUESTS = 64  # per traffic kind
LANES = 8
SHD_CONFIG = "shd-syn-atat-700-200-20"


class _CompileStats:
    """Counts compiles and persistent-cache traffic via jax.monitoring."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def snapshot(self):
        return (self.compiles, self.compile_s, self.hits, self.misses)


class Phase:
    """Times one phase and prints its line when the phase body succeeds."""

    def __init__(self, stats: _CompileStats, label: str):
        self.stats, self.label, self.parts = stats, label, []

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.s0 = self.stats.snapshot()
        return self

    def note(self, text: str) -> None:
        self.parts.append(text)

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        c, cs, h, m = (b - a for a, b in zip(self.s0, self.stats.snapshot()))
        self.parts.append(
            f"cold host-clock {wall:.2f} s (set-up, not a measurement): "
            f"{c} backend compiles {cs:.2f} s, cache hits {h} misses {m}"
        )
        print(f"[{self.label}] " + " | ".join(self.parts), flush=True)
        return False


def check(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def records_equal(a, b) -> bool:
    same = np.array_equal(np.asarray(a.spike_counts), np.asarray(b.spike_counts))
    same &= np.array_equal(np.asarray(a.input_events), np.asarray(b.input_events))
    same &= len(a.layer_spikes) == len(b.layer_spikes)
    for x, y in zip(a.layer_spikes, b.layer_spikes):
        same &= np.array_equal(np.asarray(x), np.asarray(y))
    return bool(same)


def serial_counts(net, qparams, n_steps):
    """Jitted single-sample reference ``run_int``: one compile per window."""
    from repro.core.network import run_int

    @jax.jit
    def one(raster):  # [n_steps, n_in]
        return run_int(net, qparams, raster[:, None, :].astype(jnp.int32)).spike_counts[0]

    return lambda raster: np.asarray(one(jnp.asarray(raster[:n_steps])))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_batch(stats, net, params, x):
    from repro.core import lowering
    from repro.core.backend import EventBackend
    from repro.core.network import quantize_params, run_int

    xs = np.asarray(x).reshape(-1, x.shape[-1]).astype(np.int64)
    for w_bits in (6, 12):
        with Phase(stats, f"2 batch w_bits={w_bits}") as ph:
            net_w = net.replace_precisions(w_bits=w_bits)
            qparams, _ = quantize_params(net_w, params)
            ph.note(f"run_int {T}x{BATCH}x{net.n_in} mnist-like")
            # ground the reference: the device's integer dot against numpy
            w0 = np.asarray(qparams[0].w_ff).astype(np.int64)
            want = xs @ w0
            got = np.asarray(jnp.einsum("mk,kn->mn", jnp.asarray(xs, jnp.int32), qparams[0].w_ff))
            check(np.array_equal(got, want), f"w_bits={w_bits}: device int32 dot != numpy")
            f32 = np.asarray(lowering.f32_currents(jnp.asarray(xs, jnp.int32), qparams[0].w_ff))
            f32_ok = lowering.f32_exact(w_bits, 1, net.n_in)
            f32_miss = int(np.count_nonzero(f32 != want))
            check(not f32_ok or f32_miss == 0, f"w_bits={w_bits}: certified f32 is not exact")
            ph.note(
                f"int32 dot == numpy; one-pass f32 {'certified' if f32_ok else 'refused'}, "
                f"{f32_miss} of {want.size} currents differ"
            )
            ref = run_int(net_w, qparams, x, backend="reference")
            ph.note(f"reference {ref.lowerings}")
            for name, backend in (("fused", "fused"), ("event-pallas", EventBackend("pallas"))):
                rec = run_int(net_w, qparams, x, backend=backend)
                check(records_equal(rec, ref), f"w_bits={w_bits}: {name} != reference")
                # the jitted form eval_int and the sharded paths run
                jb = backend if name == "fused" else backend.jit_surrogate(net_w, x)
                fwd = jax.jit(lambda s, b=jb: run_int(net_w, qparams, s, backend=b).spike_counts)
                compiled = fwd.lower(x).compile()
                n_kernels = compiled.as_text().count("tpu_custom_call")
                check(n_kernels > 0, f"w_bits={w_bits}: jitted {name} runs no Pallas kernel")
                counts = np.asarray(compiled(x))
                check(
                    np.array_equal(counts, np.asarray(ref.spike_counts)),
                    f"w_bits={w_bits}: jitted {name} != reference",
                )
                ph.note(
                    f"{name} {rec.lowerings} bit-exact (eager + jitted, "
                    f"{n_kernels} tpu_custom_call)"
                )


def _requests(rng):
    from repro.data.snn_datasets import mnist_like

    ds = mnist_like(n=N_REQUESTS, T=T, seed=SEED + 1)
    rasters = [ds.spikes[i] for i in range(N_REQUESTS)]
    rasters += [(rng.random((T, 256)) < 0.03).astype(np.uint8) for _ in range(N_REQUESTS)]
    return rasters


def phase_serve(stats, net, qparams, rng):
    from repro.core.backend import EventBackend
    from repro.serve.snn_engine import SNNRequest, SNNServeEngine

    with Phase(stats, "3 serve") as ph:
        engine = SNNServeEngine(net, qparams, max_batch=LANES, backend=EventBackend("pallas"))
        engine.warmup()
        rasters = _requests(rng)
        done = engine.run([SNNRequest(uid=i, raster=r) for i, r in enumerate(rasters)])
        check(len(done) == len(rasters), f"served {len(done)} of {len(rasters)}")
        serial = serial_counts(net, qparams, T)
        routes = {}
        for req in done:
            check(req.status == "completed", f"request {req.uid} {req.status}")
            check(
                np.array_equal(req.spike_counts, serial(rasters[req.uid])),
                f"request {req.uid} ({req.route}) != serial run_int",
            )
            routes[req.route] = routes.get(req.route, 0) + 1
        check(
            routes.get("lanes", 0) > 0 and routes.get("event-pallas", 0) > 0,
            f"routes {routes} miss the lane or the event-pallas route",
        )
        ph.note(
            f"SNNServeEngine max_batch={LANES} EventBackend('pallas') "
            f"{N_REQUESTS} mnist-like + {N_REQUESTS} Bernoulli(3%) requests, "
            f"{engine.n_ticks} ticks"
        )
        ph.note(f"lowerings {engine.route_lowerings()}")
        ph.note(f"routes {routes}; every request == serial run_int")
    return engine


def phase_stream(stats, net, qparams, engine, rng):
    from repro.serve.streaming import StreamConfig, StreamSessionManager

    n_sessions, steps = 6, 160
    with Phase(stats, "4 stream") as ph, tempfile.TemporaryDirectory() as ckpt:
        mgr = StreamSessionManager(
            engine, checkpoint_dir=ckpt, config=StreamConfig(window=16, stride=8, idle_budget=1)
        )
        streams = {}
        for i in range(n_sessions):
            density = 0.03 if i % 2 else 0.15
            raster = (rng.random((steps, net.n_in)) < density).astype(np.uint8)
            streams[mgr.open(f"s{i}").sid] = raster
        fed = dict.fromkeys(streams, 0)
        while any(fed[sid] < steps for sid in streams):
            for sid, raster in streams.items():
                if fed[sid] < steps and rng.random() < 0.6:
                    n = int(min(steps - fed[sid], rng.integers(1, 41)))  # uneven chunks
                    mgr.feed(sid, raster[fed[sid] : fed[sid] + n])
                    fed[sid] += n
            mgr.poll()
            if rng.random() < 0.2:  # idle spells: drained sessions evict
                mgr.pump()
                mgr.poll()
                mgr.poll()
        mgr.pump()
        serial = serial_counts(net, qparams, steps)
        evictions = restores = 0
        for sid, raster in streams.items():
            s = mgr.sessions[sid]
            check(s.t_total == steps, f"session {sid} absorbed {s.t_total} of {steps} steps")
            check(
                np.array_equal(s.counts_total, serial(raster)),
                f"session {sid} != serial run_int over its stream",
            )
            evictions += s.n_evictions
            restores += s.n_restores
        check(
            evictions > 0 and restores > 0,
            f"no idle eviction/restore happened ({evictions}/{restores})",
        )
        ph.note(
            f"StreamSessionManager {n_sessions} sessions x {steps} steps, uneven chunks, "
            f"{evictions} evictions / {restores} restores"
        )
        ph.note(f"lowerings {engine.route_lowerings()}; every session == serial run_int")


def phase_dse_qat(stats, net, params):
    from repro.core.network import quantize_params
    from repro.data.snn_datasets import mnist_like
    from repro.snn.qat import PrecisionConfig, eval_qat
    from repro.snn.train import eval_int, eval_int_population, train_snn

    with Phase(stats, "5 dse") as ph:
        ds = mnist_like(n=512, T=T, seed=SEED + 2)
        cands = [
            net.replace_precisions(w_bits=b, leak_bits=l)
            for b in (2, 3, 4, 5, 6, 8, 12, 16)
            for l in (4, 8)
        ]
        qps = [quantize_params(c, params)[0] for c in cands]
        pop = eval_int_population(net, cands, qps, ds, batch_size=256)
        serial = np.asarray([eval_int(c, q, ds, batch_size=256) for c, q in zip(cands, qps)])
        check(np.array_equal(pop, serial), f"population {pop} != serial eval_int {serial}")
        ph.note(
            f"eval_int_population over {len(cands)} candidates (w_bits 2..16 x leak 4/8) "
            f"on 512 mnist-like; lowering step-scan; every candidate == its serial eval_int"
        )
    with Phase(stats, "5 qat") as ph:
        train = mnist_like(n=512, T=T, seed=SEED + 3)
        res = train_snn(net, train, qat=PrecisionConfig(w_bits=4), epochs=1, batch_size=128)
        loss = res.history[-1]["loss"]
        check(np.isfinite(loss), f"QAT loss {loss} is not finite")
        qnet = res.qat_net
        deployed = eval_int(qnet, quantize_params(qnet, res.params)[0], ds)
        check(eval_qat(qnet, res.params, ds) == deployed, "QAT eval != deployed eval_int")
        ph.note(
            f"train_snn(qat=PrecisionConfig(w_bits=4)) 1 epoch x 4 batches of 128: "
            f"loss {loss:.4f} finite; eval_qat == deployed eval_int ({deployed:.4f})"
        )


def _shd_net():
    from repro.core.network import NetworkConfig
    from repro.core.snn_layer import LayerConfig, NeuronModel, ResetMode, Topology

    cfg = json.loads((ROOT / "perfbench" / "configs" / f"{SHD_CONFIG}.json").read_text())
    enums = {"neuron": NeuronModel, "topology": Topology, "reset": ResetMode}
    layers = tuple(
        LayerConfig(**{k: enums[k](v) if k in enums else v for k, v in layer.items()})
        for layer in cfg["layers"]
    )
    return NetworkConfig(layers=layers, n_steps=cfg["n_steps"], name=cfg["name"]), cfg


def phase_shd(stats):
    from repro.core import lowering
    from repro.core.backend import EventBackend
    from repro.core.network import init_float_params, quantize_params, run_int
    from repro.data.snn_datasets import shd_like
    from repro.serve.snn_engine import SNNRequest, SNNServeEngine
    from repro.snn.train import eval_int, eval_int_population

    with Phase(stats, "6 shd") as ph:
        net, cfg = _shd_net()
        gain = cfg["init"]["ff_gain"]  # the benchmark's feed-forward scale
        params = [
            p._replace(w_ff=p.w_ff * gain)
            for p in init_float_params(jax.random.PRNGKey(SEED + 4), net)
        ]
        qparams, _ = quantize_params(net, params)
        ds = shd_like(n=256, T=net.n_steps, seed=SEED + 4, channels=net.n_in)
        ph.note(f"{net.name}: {net.n_cores} cores, layer 1 on {net.layers[0].n_cores}")

        xs = ds.spikes.reshape(-1, net.n_in).astype(np.int64)
        w16 = np.asarray(quantize_params(net.replace_precisions(w_bits=16), params)[0][0].w_ff)
        got = np.asarray(jnp.einsum("mk,kn->mn", jnp.asarray(xs, jnp.int32), jnp.asarray(w16)))
        check(np.array_equal(got, xs @ w16.astype(np.int64)), "700-wide int32 dot != numpy")
        check(not lowering.f32_exact(16, 1, net.n_in), "f32 certified for 700 x w_bits=16")
        ph.note("700-wide int32 dot at w_bits=16 == numpy; f32 refused")

        ref = eval_int(net, qparams, ds, batch_size=256, return_stats=True)
        for name, backend in (
            ("fused", "fused"),
            ("event", "event"),
            ("event-pallas", EventBackend("pallas")),
        ):
            acc, st = eval_int(net, qparams, ds, batch_size=256, return_stats=True, backend=backend)
            same = acc == ref[0]
            for a, b in zip(st["layer_events_per_step"], ref[1]["layer_events_per_step"]):
                same &= np.array_equal(a, b)
            check(same, f"shd eval_int {name} != reference")
        events = ref[1]["layer_events_per_step"]
        rates = " ".join(f"{np.mean(e) / c.n_out:.4f}" for e, c in zip(events, net.layers))
        ph.note(f"eval_int fused/event/event-pallas == reference: acc {ref[0]:.4f}, rates {rates}")

        cands = [net.replace_precisions(w_bits=b, w_rec_bits=b) for b in (4, 8, 12, 16)]
        qps = [quantize_params(c, params)[0] for c in cands]
        pop = eval_int_population(net, cands, qps, ds, batch_size=256)
        serial = np.asarray([eval_int(c, q, ds, batch_size=256) for c, q in zip(cands, qps)])
        check(np.array_equal(pop, serial), f"shd population {pop} != serial {serial}")
        ph.note(f"eval_int_population over {len(cands)} candidates == serial eval_int")

        engine = SNNServeEngine(net, qparams, max_batch=LANES)
        engine.warmup()
        rasters = [ds.spikes[i] for i in range(32)]
        done = engine.run([SNNRequest(uid=i, raster=r) for i, r in enumerate(rasters)])
        check(len(done) == len(rasters), f"shd served {len(done)} of {len(rasters)}")
        serial = serial_counts(net, qparams, net.n_steps)
        for req in done:
            check(req.status == "completed", f"shd request {req.uid} {req.status}")
            check(
                np.array_equal(req.spike_counts, serial(rasters[req.uid])),
                f"shd request {req.uid} != serial run_int",
            )
        batch = run_int(net, qparams, jnp.asarray(np.transpose(ds.spikes[:32], (1, 0, 2))))
        served = np.stack([r.spike_counts for r in sorted(done, key=lambda r: r.uid)])
        check(np.array_equal(np.asarray(batch.spike_counts), served), "shd served != batch run_int")
        ph.note(
            f"SNNServeEngine {len(rasters)} requests, lowerings {engine.route_lowerings()}; "
            "every request == serial run_int"
        )


def one_chip(stats, net, params):
    from repro.core.network import quantize_params
    from repro.data.snn_datasets import mnist_like

    rng = np.random.default_rng(SEED)
    ds = mnist_like(n=BATCH, T=T, seed=SEED)
    x = jnp.asarray(np.transpose(ds.spikes, (1, 0, 2)))  # [T, B, n_in] uint8
    phase_batch(stats, net, params, x)
    qparams, _ = quantize_params(net, params)
    engine = phase_serve(stats, net, qparams, rng)
    phase_stream(stats, net, qparams, engine, rng)
    phase_dse_qat(stats, net, params)
    phase_shd(stats)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chips(stats, net, params):
    from repro.core import shard as shard_lib
    from repro.core.backend import EventBackend
    from repro.core.network import quantize_params
    from repro.data.snn_datasets import mnist_like
    from repro.serve.snn_engine import SNNRequest, SNNServeEngine
    from repro.snn.train import eval_int, eval_int_population

    n_dev = 4
    check(len(jax.devices()) >= n_dev, f"--chips 4 needs 4 devices, got {len(jax.devices())}")
    qparams, _ = quantize_params(net, params)
    rng = np.random.default_rng(SEED)

    with Phase(stats, "4chip serve") as ph:
        rasters = _requests(rng)
        results = {}
        for dp in (None, n_dev):
            engine = SNNServeEngine(
                net, qparams, max_batch=LANES, backend=EventBackend("pallas"), data_parallel=dp
            )
            engine.warmup()
            done = engine.run([SNNRequest(uid=i, raster=r) for i, r in enumerate(rasters)])
            if dp:
                check(engine.data_parallel == n_dev, f"clamped to {engine.data_parallel}")
                used = engine._states[0].u.sharding.device_set  # the pool after serving
                check(len(used) == n_dev, f"lane pool spans {len(used)} devices, not {n_dev}")
            results[dp] = {r.uid: (r.route, r.spike_counts) for r in done}
        check(len(results[n_dev]) == len(rasters), "sharded engine dropped requests")
        for uid, (route, counts) in results[None].items():
            check(
                np.array_equal(results[n_dev][uid][1], counts),
                f"request {uid}: data_parallel={n_dev} != one device",
            )
        routes = sorted({r for r, _ in results[n_dev].values()})
        ph.note(
            f"SNNServeEngine(data_parallel={n_dev}) lane pool over {n_dev} devices, "
            f"{len(rasters)} requests routes {routes}; every request == one-device engine"
        )

    ds = mnist_like(n=512, T=T, seed=SEED + 2)
    with Phase(stats, "4chip eval") as ph:
        mesh = shard_lib.resolve_mesh(n_dev)
        check(mesh.n_shards == n_dev, f"mesh has {mesh.n_shards} shards")
        one = eval_int(net, qparams, ds, batch_size=256, return_stats=True)
        shd = eval_int(net, qparams, ds, batch_size=256, return_stats=True, mesh=n_dev)
        check(one[0] == shd[0], f"eval_int mesh={n_dev} accuracy {shd[0]} != {one[0]}")
        for a, b in zip(one[1]["layer_events_per_step"], shd[1]["layer_events_per_step"]):
            check(np.array_equal(a, b), f"eval_int mesh={n_dev} event stats differ")
        ph.note(f"eval_int(mesh={n_dev}) on 512 mnist-like == serial, accuracy {one[0]:.4f}")

    with Phase(stats, "4chip population") as ph:
        cands = [net.replace_precisions(w_bits=b) for b in (2, 3, 4, 5, 6, 8, 12, 16)]
        qps = [quantize_params(c, params)[0] for c in cands]
        one = eval_int_population(net, cands, qps, ds, batch_size=256)
        shd = eval_int_population(net, cands, qps, ds, batch_size=256, mesh=n_dev)
        check(np.array_equal(one, shd), f"population mesh={n_dev} {shd} != one device {one}")
        ph.note(f"eval_int_population(mesh={n_dev}) over {len(cands)} candidates == one device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1, help="4: only the sharded paths"
    )
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    print(
        f"[1 device] jax {jax.__version__} platform={dev.platform} "
        f"kind={dev.device_kind} count={len(devices)}",
        flush=True,
    )
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports {dev.platform})", file=sys.stderr)
        return 1

    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.network import init_float_params
    from repro.distributed.compat import enable_compilation_cache
    from repro.launch.serve_snn import _build_net

    cache_dir = enable_compilation_cache()
    print(f"[1 device] compilation cache {cache_dir}", flush=True)
    stats = _CompileStats()
    net = _build_net(HIDDEN, T)
    params = init_float_params(jax.random.PRNGKey(SEED), net)
    (four_chips if args.chips == 4 else one_chip)(stats, net, params)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
