"""Reduction of the program's own profiler spans to per-layer numbers.

The serve tick loop and the population sweep open host spans named
``neura.*`` (the program lists them in ``repro.serve.metrics.SPAN_NAMES``).
They land on the same timeline as the device's ``XLA Modules`` events, so
a span can be matched to the device execution it launched. Intervals come
from ``Trace.host`` / ``host_names`` (the thread that opened the ``bench.``
spans), device executions from ``Trace.devices``, and a span's arguments,
which ``trace.load`` does not keep, from a second read of the
``.xplane.pb`` the harness wrote.

Only spans that start inside the traced window count. Every function
returns ``None`` where the trace holds none of the spans it reads: an
untraced run, or a program older than the spans.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from perfharness import trace as trace_lib


def spans(trace, name: str) -> np.ndarray:
    """``[n, 2]`` start and end (ns) of the host spans named ``name``, by start."""
    if trace is None:
        return np.zeros((0, 2))
    lo, hi = trace.window
    idx = [i for i, n in enumerate(trace.host_names) if n == name]
    s = trace.host[idx].reshape(-1, 2)
    s = s[(s[:, 0] >= lo) & (s[:, 0] < hi)]
    return s[np.argsort(s[:, 0], kind="stable")]


def mean_ms(trace, name: str) -> float | None:
    """Mean duration of the spans named ``name``, in ms."""
    s = spans(trace, name)
    return 1e-6 * float(np.mean(s[:, 1] - s[:, 0])) if len(s) else None


def holder(outer: np.ndarray, inner: np.ndarray) -> np.ndarray:
    """For each inner span, the index of the outer span holding it, or -1.

    ``outer`` is sorted by start and its spans do not overlap (one thread's
    spans of one name never do).
    """
    if not len(outer):
        return np.full(len(inner), -1)
    j = np.searchsorted(outer[:, 0], inner[:, 0], side="right") - 1
    jj = np.clip(j, 0, None)
    return np.where((j >= 0) & (inner[:, 1] <= outer[jj, 1]), jj, -1)


def self_ms(trace, parent: str, children) -> float | None:
    """Mean duration of the ``parent`` spans less the ``children`` spans they hold, in ms."""
    p = spans(trace, parent)
    if not len(p):
        return None
    left = p[:, 1] - p[:, 0]
    for child in children:
        c = spans(trace, child)
        at = holder(p, c)
        np.subtract.at(left, at[at >= 0], (c[:, 1] - c[:, 0])[at >= 0])
    return 1e-6 * float(np.mean(left))


def executions(trace, jit_names) -> list[np.ndarray]:
    """Per device, ``[m, 2]`` start and end of each execution of the named programs, by start."""
    want = {f"jit_{n}" for n in jit_names}
    out = []
    for _, d in sorted(trace.devices.items()):
        keep = [j for j, n in enumerate(d.module_names) if n.split("(")[0].strip() in want]
        m = d.modules[keep].reshape(-1, 2)
        out.append(m[np.argsort(m[:, 0], kind="stable")])
    return out


def readback_tail_ms(trace, launch: str, readback: str, jit_names) -> float | None:
    """Mean time from the end of a launched execution to the end of its readback, in ms.

    Each ``launch`` span pairs with the first ``readback`` span that starts
    after it ends and before the next launch starts. Its execution is, on
    each device, the first of the named programs to start at or after the
    launch starts (and no later than the readback ends); the latest end
    over the devices counts. The tail is the readback's end less that, clipped
    at 0: the transfer and the host's own delay after the device finished,
    not the wait for the device.
    """
    L, R = spans(trace, launch), spans(trace, readback)
    if not len(L) or not len(R):
        return None
    r = np.searchsorted(R[:, 0], L[:, 1], side="left")
    next_launch = np.r_[L[1:, 0], np.inf]
    paired = r < len(R)
    rr = np.clip(r, 0, len(R) - 1)
    paired &= R[rr, 0] < next_launch
    r_end = R[rr, 1]
    done = np.full(len(L), -np.inf)
    for m in executions(trace, jit_names):
        if not len(m):
            continue
        j = np.searchsorted(m[:, 0], L[:, 0], side="left")
        jj = np.clip(j, 0, len(m) - 1)
        found = (j < len(m)) & (m[jj, 0] <= r_end)
        done = np.where(found, np.maximum(done, m[jj, 1]), done)
    ok = paired & np.isfinite(done)
    if not ok.any():
        return None
    return 1e-6 * float(np.mean(np.clip(r_end[ok] - done[ok], 0, None)))


def trace_file():
    """The ``.xplane.pb`` the harness's last traced run wrote, or ``None``."""
    from perfharness import harness

    files = sorted((harness.OUT_DIR / "trace").glob("plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


@functools.lru_cache(maxsize=2)
def _span_args(path: str, mtime_ns: int) -> dict[str, list[tuple[float, dict]]]:
    """By name, ``(start, arguments)`` of every ``neura.`` span on the harness's thread."""
    from jax.profiler import ProfileData

    out: dict[str, list[tuple[float, dict]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            events = list(line.events)
            if not any(ev.name.startswith(trace_lib.WINDOW_PREFIX) for ev in events):
                continue
            for ev in events:
                if ev.name.startswith("neura."):
                    out.setdefault(ev.name, []).append((ev.start_ns, dict(ev.stats)))
    return out


def arg_mean(trace, name: str, key: str) -> float | None:
    """Mean of argument ``key`` over the spans named ``name`` in the window.

    The arguments are read from the file the trace was loaded from, the one
    the harness's last traced run wrote.
    """
    if not len(spans(trace, name)):
        return None
    path = trace_file()
    if path is None:
        return None
    lo, hi = trace.window
    values = [
        float(args[key])
        for start, args in _span_args(str(path), os.stat(path).st_mtime_ns).get(name, [])
        if lo <= start < hi and key in args
    ]
    return float(np.mean(values)) if values else None
