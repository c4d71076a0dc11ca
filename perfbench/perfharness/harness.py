"""One run of one cell: find its files by name, drive it, reduce, check, print.

Everything that belongs to one cell lives in files of its own, found by the
names in ``BENCHMARK.json``:

- ``configs/<config>.json``: the network, its widths and its reference;
- ``references/<reference>.py``: the plain reference the answers are held to;
- ``traffic/<traffic>.json``: the mix, whose ``runner`` names the general
  code that runs it (``perfharness/<runner>.py``);
- ``metrics/<metric>.py``: one reader per metric, ``read(run) -> float | None``.

A reader that finds nothing to read returns ``None`` and the metric is left
out of the line.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import shutil
import sys
import time
from types import ModuleType

from perfharness import BENCH_DIR, ROOT

CACHE_DIR = ROOT / ".jax_cache"  # fixed: the path is part of every entry's key
OUT_DIR = ROOT / "perfbench_out"


class CompileStats:
    """Counts backend compiles and persistent-cache traffic via ``jax.monitoring``."""

    def __init__(self):
        import jax

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


class GcStats:
    """Python's garbage collections while it is installed: count per generation and seconds."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.seconds = 0.0
        self.longest = 0.0
        self._t0 = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        took = time.perf_counter() - self._t0
        self.count[info["generation"]] += 1
        self.seconds += took
        self.longest = max(self.longest, took)


@dataclasses.dataclass
class Context:
    """What a runner is given: the cell's files, the run's knobs, the device."""

    name: str
    config: dict
    traffic: dict
    reference: ModuleType
    seed: int
    seconds: float
    trace: bool
    chips: int
    t_start: float
    stats: CompileStats
    peaks: dict
    devices: list

    def note(self, text: str) -> None:
        print(f"[{self.name}] {text}", flush=True)

    def start_window(self) -> float:
        """End set-up: collect its garbage, note its compiles and memory; returns ``setup_s``.

        What set-up made (the load generator's requests and data among it) is
        frozen out of Python's collector, so that the collections in the
        window scan what the system under test makes there, not the harness's
        pile of pending work.
        """
        gc.collect()
        gc.freeze()
        self._gc = GcStats()
        gc.callbacks.append(self._gc)
        s = self.stats
        self.note(
            f"set-up: {s.compiles} backend compiles ({s.compile_s:.2f} s),"
            f" persistent cache hits {s.hits} misses {s.misses}"
        )
        self.note("device memory at window start: " + memory_note(self.devices))
        self._compiles0 = s.compiles
        return time.perf_counter() - self.t_start

    def end_window(self) -> None:
        """Note how many programs compiled inside the window (there should be none), and memory."""
        gc.callbacks.remove(self._gc)
        gc.unfreeze()
        g = self._gc
        self.note(f"compiles in window: {self.stats.compiles - self._compiles0}")
        self.note(
            f"garbage collections in window: generations {g.count}, {g.seconds:.4f} s,"
            f" longest {g.longest:.4f} s"
        )
        self.note("device memory at window end: " + memory_note(self.devices))


@dataclasses.dataclass
class Run:
    """What a runner hands back; metric readers read it."""

    setup_s: float
    window_s: float
    attempted: int
    failed: int
    checks: list  # (name, value, limit)
    memory_peak_bytes: int
    chips: int
    peaks: dict
    data: dict  # runner-specific arrays the readers take their numbers from
    trace: object = None  # perfharness.trace.Trace of the traced window, or None


def _load_module(path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path.relative_to(ROOT)} for {name!r}")
    spec = importlib.util.spec_from_file_location(f"perfbench_{path.parent.name}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str) -> dict:
    path = BENCH_DIR / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no file {path.relative_to(ROOT)} for {name!r}")
    return json.loads(path.read_text())


def reader(metric: str) -> ModuleType:
    return _load_module(BENCH_DIR / "metrics" / f"{metric}.py", metric)


def reference(name: str) -> ModuleType:
    return _load_module(BENCH_DIR / "references" / f"{name}.py", name)


def runner(name: str) -> ModuleType:
    return importlib.import_module(f"perfharness.{name}")


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: dict, workload: str, traced: bool) -> list[dict]:
    """The metrics a run of this cell reports: end-to-end untraced, per-layer traced."""
    pool = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in pool if "workloads" not in m or workload in m["workloads"]]


def enable_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_device(chips: int):
    """The devices of this run; exits without a result unless ``chips`` TPUs are here."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"no accelerator: {e}")
    if devices[0].platform != "tpu":
        sys.exit(f"no TPU: JAX reports platform {devices[0].platform!r}")
    if len(devices) < chips:
        sys.exit(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices


def memory_peak(devices) -> int:
    """The process's peak device memory on the fullest chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def memory_note(devices) -> str:
    stats = [d.memory_stats() or {} for d in devices]
    in_use = max(s.get("bytes_in_use", 0) for s in stats)
    return f"in use {in_use} B, process peak {max(s.get('peak_bytes_in_use', 0) for s in stats)} B"


def trace_dir():
    path = OUT_DIR / "trace"
    shutil.rmtree(path, ignore_errors=True)
    return path


def start_trace(path) -> None:
    """Open the profiler on ``path``: device ops, XLA's host events and the harness's spans.

    Python's own tracer, which records every Python call, is off: it slows
    the host loop the traced numbers describe, and swells the trace.
    """
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(path), profiler_options=options)


def load_trace(path):
    from perfharness import trace

    files = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"the profiler wrote no trace under {path}")
    return trace.load(files[-1])


def run_cell(workload: str, seed: int, seconds: float, traced: bool, t_start: float,
             devices=None, peaks=None, config=None, traffic=None) -> dict:  # fmt: skip
    """Drive one run and return its result line (a dict), with every check in it.

    ``devices`` and ``peaks`` come from the device check, ``config`` and
    ``traffic`` from the cell's files; tests pass their own to drive the
    rest of a run without a chip, at a size a test can hold.
    """
    from perfharness import trace as trace_lib
    from perfharness.peaks import peaks as peaks_of

    bench = benchmark()
    w = cell(bench, workload)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    if config is None:
        config = json.loads((ROOT / cfg_entry["file"]).read_text())
    if traffic is None:
        traffic = load_json("traffic", w["traffic"])
    wanted = metrics_of(bench, workload, traced)
    readers = {m["name"]: reader(m["name"]) for m in wanted}
    if devices is None:
        devices = check_device(w["chips"])
    if peaks is None:
        peaks = peaks_of(devices[0].device_kind)
    ctx = Context(
        name=workload,
        config=config,
        traffic=traffic,
        reference=reference(config["reference"]),
        seed=seed,
        seconds=seconds,
        trace=traced,
        chips=w["chips"],
        t_start=t_start,
        stats=CompileStats(),
        peaks=peaks,
        devices=devices[: w["chips"]],
    )
    used = ctx.devices
    ctx.note(
        f"device platform={used[0].platform} kind={used[0].device_kind} count={len(devices)}"
        f" using {len(used)}"
    )
    run = runner(traffic["runner"]).run(ctx, used)
    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": used[0].platform,
        "kind": used[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    result = {
        "correct": all(v <= lim for _, v, lim in run.checks),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        busy = trace_lib.busy_s(run.trace)
        if not busy:
            raise ValueError("the trace holds no TPU device plane: nothing ran on the device")
        for i, b in busy.items():
            ctx.note(f"device {i} busy {b} s of {run.trace.window_s} s traced")
        device["busy_s"] = sum(busy.values()) / len(busy)
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": trace_lib.top_ops(run.trace),
            "idle_gaps": trace_lib.idle_gaps(run.trace),
        }
    result["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in run.checks}
    for name, v, lim in run.checks:
        if not (isinstance(v, (int, float)) and math.isfinite(v)):
            raise ValueError(f"check {name} read {v!r}")
    return result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    enable_cache()
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_start)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
