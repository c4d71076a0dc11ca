"""Reduction of a profiler trace to device busy time, program time and idle gaps.

A trace is read once into plain arrays (``load``), and every number is taken
from those: the union of the device's op intervals (busy), the module
events of one jitted program (its device time per execution), the ops that
took most time, and the idle gaps between busy intervals, each labelled
with the innermost host span that was open at its midpoint.

The traced window runs from the start of the first to the end of the last
host span whose name starts with ``bench.``: the spans the benchmark opens
around the calls it times. Device time outside it is not counted.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
WINDOW_PREFIX = "bench."


@dataclasses.dataclass
class Device:
    ops: np.ndarray  # float64 [n, 2]: start, end in ns
    op_names: list[str]
    modules: np.ndarray  # float64 [m, 2]
    module_names: list[str]


@dataclasses.dataclass
class Trace:
    devices: dict[int, Device]  # by TPU index
    host: np.ndarray  # float64 [h, 2]: host spans of the thread that opened the bench spans
    host_names: list[str]

    @property
    def window(self) -> tuple[float, float]:
        marks = [i for i, n in enumerate(self.host_names) if n.startswith(WINDOW_PREFIX)]
        if not marks:
            raise ValueError(f"no host span named {WINDOW_PREFIX}* in the trace")
        return float(self.host[marks, 0].min()), float(self.host[marks, 1].max())

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9


def _events(line):
    names, spans = [], []
    for ev in line.events:
        names.append(ev.name)
        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return np.asarray(spans, np.float64).reshape(-1, 2), names


def load(path) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host, host_names = {}, np.zeros((0, 2)), []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            none = (np.zeros((0, 2)), [])
            ops, op_names = _events(lines[_OPS_LINE]) if _OPS_LINE in lines else none
            mods, mod_names = _events(lines[_MODULES_LINE]) if _MODULES_LINE in lines else none
            devices[int(m.group(1))] = Device(ops, op_names, mods, mod_names)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans, names = _events(line)
                if any(n.startswith(WINDOW_PREFIX) for n in names):
                    host, host_names = spans, names
    return Trace(devices, host, host_names)


def _union(spans: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Disjoint sorted intervals covering ``spans`` clipped to ``[lo, hi]``."""
    s = np.clip(spans, lo, hi)
    s = s[s[:, 1] > s[:, 0]]
    if not len(s):
        return np.zeros((0, 2))
    s = s[np.argsort(s[:, 0], kind="stable")]
    ends = np.maximum.accumulate(s[:, 1])
    new = np.ones(len(s), bool)
    new[1:] = s[1:, 0] > ends[:-1]
    starts = s[new, 0]
    idx = np.flatnonzero(new)
    stops = ends[np.r_[idx[1:] - 1, len(s) - 1]]
    return np.stack([starts, stops], axis=1)


def busy_s(trace: Trace) -> dict[int, float]:
    """Seconds in which an op ran, per device, inside the window."""
    lo, hi = trace.window
    return {
        i: float(np.sum(np.diff(_union(d.ops, lo, hi), axis=1))) * 1e-9
        for i, d in sorted(trace.devices.items())
    }


def _module_base(name: str) -> str:
    return name.split("(")[0].strip()


def program_calls(trace: Trace, jit_names) -> dict[int, np.ndarray]:
    """Per device, the durations in seconds of each execution of the named programs.

    A program is named as the function given to ``jax.jit``: its module is
    ``jit_<name>``. Executions that start inside the window count.
    """
    want = {f"jit_{n}" for n in jit_names}
    lo, hi = trace.window
    out = {}
    for i, d in sorted(trace.devices.items()):
        keep = [
            j
            for j, n in enumerate(d.module_names)
            if _module_base(n) in want and lo <= d.modules[j, 0] < hi
        ]
        out[i] = np.diff(d.modules[keep], axis=1).ravel() * 1e-9
    return out


def _ranked(names, seconds, k: int) -> list[list]:
    totals: dict[str, float] = {}
    for n, v in zip(names, seconds):
        totals[n] = totals.get(n, 0.0) + float(v)
    return [[n, s] for n, s in sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` device ops that took most time in the window, summed over devices."""
    lo, hi = trace.window
    names, seconds = [], []
    for d in trace.devices.values():
        if not len(d.op_names):
            continue
        uniq, inv = np.unique(np.asarray(d.op_names, dtype=object), return_inverse=True)
        inside = (d.ops[:, 0] >= lo) & (d.ops[:, 0] < hi)
        dur = np.where(inside, d.ops[:, 1] - d.ops[:, 0], 0.0) * 1e-9
        names += list(uniq)
        seconds += list(np.bincount(inv, weights=dur, minlength=len(uniq)))
    return _ranked(names, seconds, k)


def idle_gaps(trace: Trace, k: int = 10, device: int | None = None) -> list[list]:
    """Idle time of one device in the window, summed by the host span open in each gap.

    Each gap between busy intervals is labelled with the innermost host span
    open at its midpoint (spans of one thread nest), or ``"no host span"``.
    Returns the ``k`` labels with the most idle seconds.
    """
    lo, hi = trace.window
    dev = trace.devices[min(trace.devices) if device is None else device]
    busy = _union(dev.ops, lo, hi)
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    mids = 0.5 * (gaps[:, 0] + gaps[:, 1])
    host = trace.host
    order = np.lexsort((-host[:, 1], host[:, 0]))  # by start, the enclosing span first
    labels, stack, i = [], [], 0
    for mid in mids[np.argsort(mids)]:
        while i < len(order) and host[order[i], 0] <= mid:
            while stack and host[stack[-1], 1] < host[order[i], 0]:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and host[stack[-1], 1] < mid:
            stack.pop()
        labels.append(trace.host_names[stack[-1]] if stack else "no host span")
    seconds = (gaps[:, 1] - gaps[:, 0])[np.argsort(mids)] * 1e-9
    return _ranked(labels, seconds, k)


def program_ms(trace: Trace, jit_names) -> float | None:
    """Mean device milliseconds per execution of the named programs, mean over devices."""
    per = [c.mean() for c in program_calls(trace, jit_names).values() if len(c)]
    return 1e3 * float(np.mean(per)) if per else None


def idle_pct(trace: Trace) -> float | None:
    """Percent of the window in which no op ran, mean over devices."""
    busy = busy_s(trace)
    if not busy:
        return None
    return 100.0 * (1.0 - float(np.mean(list(busy.values()))) / trace.window_s)
