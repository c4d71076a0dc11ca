"""Which device ops of a profiler trace ran under which ``jax.named_scope``.

``trace.load`` keeps each device op's name and interval, not its op
metadata. The scope path of an op (its ``tf_op`` stat, such as
``jit(f)/while/body/neura.core.ff/dot_general:``) sits in the event metadata
of the ``.xplane.pb``, which ``jax.profiler.ProfileData`` does not expose. So
this module reads the file itself, with a small reader of protobuf's wire
format over the profiler's ``xplane.proto`` (field numbers below). It decodes
the device planes' ``XLA Ops`` lines and event metadata and skips the rest.

A fusion carries the metadata of its root, so an op counts under a scope when
its root was written inside it.
"""

from __future__ import annotations

import functools
import os
import re

import numpy as np

from perfharness import trace as trace_lib

# xplane.proto field numbers
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_META_STATS = 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_STAT_META_NAME = 2
_MAP_KEY, _MAP_VALUE = 1, 2

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = b"XLA Ops"
_SLACK_NS = 100.0


def _fields(buf: bytes, lo: int, hi: int):
    """``(field, value)`` of one message in ``buf[lo:hi]``.

    A varint or fixed field gives its integer, a length-delimited one its
    ``(start, end)`` in ``buf``.
    """
    i = lo
    while i < hi:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        kind = key & 7
        if kind == 0 or kind == 2:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            if kind == 2:
                yield key >> 3, (i, i + v)
                i += v
            else:
                yield key >> 3, v
        elif kind == 1:
            yield key >> 3, int.from_bytes(buf[i : i + 8], "little")
            i += 8
        elif kind == 5:
            yield key >> 3, int.from_bytes(buf[i : i + 4], "little")
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")


def _map_entry(buf: bytes, span) -> tuple[int, tuple[int, int]]:
    key, value = 0, (span[0], span[0])
    for f, v in _fields(buf, *span):
        if f == _MAP_KEY:
            key = v
        elif f == _MAP_VALUE:
            value = v
    return key, value


def _tf_ops(buf: bytes, metas: list, stat_names: dict[int, str]) -> dict[int, str]:
    """Event metadata id to its ``tf_op`` string, for the metadata that has one."""
    by_name = {v: k for k, v in stat_names.items()}
    tf_op = by_name.get("tf_op")
    out = {}
    if tf_op is None:
        return out
    for span in metas:
        meta_id, body = _map_entry(buf, span)
        for f, v in _fields(buf, *body):
            if f != _META_STATS:
                continue
            stat = dict(_fields(buf, *v))
            if stat.get(_STAT_META_ID) != tf_op:
                continue
            if _STAT_STR in stat:
                lo, hi = stat[_STAT_STR]
                out[meta_id] = buf[lo:hi].decode("utf-8", "replace")
            elif _STAT_REF in stat:
                out[meta_id] = stat_names.get(stat[_STAT_REF], "")
    return out


def _plane(buf: bytes, span):
    name = b""
    lines, metas, stat_names = [], [], {}
    for f, v in _fields(buf, *span):
        if f == _PLANE_NAME:
            name = buf[v[0] : v[1]]
        elif f == _PLANE_LINES:
            lines.append(v)
        elif f == _PLANE_EVENT_META:
            metas.append(v)
        elif f == _PLANE_STAT_META:
            key, body = _map_entry(buf, v)
            for g, w in _fields(buf, *body):
                if g == _STAT_META_NAME:
                    stat_names[key] = buf[w[0] : w[1]].decode("utf-8", "replace")
    return name.decode("utf-8", "replace"), lines, metas, stat_names


@functools.lru_cache(maxsize=2)
def _read(path: str, mtime_ns: int) -> dict[int, tuple[np.ndarray, list[str]]]:
    with open(path, "rb") as fh:
        buf = fh.read()
    out = {}
    for f, span in _fields(buf, 0, len(buf)):
        if f != _SPACE_PLANES:
            continue
        name, lines, metas, stat_names = _plane(buf, span)
        m = _DEVICE_PLANE.match(name)
        if not m:
            continue
        tf_ops = _tf_ops(buf, metas, stat_names)
        spans, ops = [], []
        for line in lines:
            fields = list(_fields(buf, *line))
            names = [buf[v[0] : v[1]] for g, v in fields if g == _LINE_NAME]
            if names != [_OPS_LINE]:
                continue
            t0 = next((v for g, v in fields if g == _LINE_TIMESTAMP_NS), 0)
            for g, v in fields:
                if g != _LINE_EVENTS:
                    continue
                ev = dict(_fields(buf, *v))
                start = t0 + ev.get(_EVENT_OFFSET_PS, 0) * 1e-3
                spans.append((start, start + ev.get(_EVENT_DURATION_PS, 0) * 1e-3))
                ops.append(tf_ops.get(ev.get(_EVENT_META_ID, 0), ""))
        out[int(m.group(1))] = (np.asarray(spans, np.float64).reshape(-1, 2), ops)
    return out


def device_ops(path) -> dict[int, tuple[np.ndarray, list[str]]]:
    """Per device, ``[n, 2]`` start and end (ns) of each ``XLA Ops`` event and its ``tf_op``."""
    path = str(path)
    return _read(path, os.stat(path).st_mtime_ns)


def scoped_ms(trace, scope: str, jit_names) -> float | None:
    """Device time per execution of the named programs in ops under ``scope``, in ms.

    For each execution that starts in the window, the union of the intervals
    of the ops inside it whose ``tf_op`` holds the path component ``scope``;
    the mean over executions, then over devices. ``None`` where no such op ran:
    an untraced run, or a program that opens no such scope.
    """
    from perfharness import spans

    if trace is None:
        return None
    path = spans.trace_file()
    if path is None:
        return None
    lo, hi = trace.window
    calls = dict(zip(sorted(trace.devices), spans.executions(trace, jit_names)))
    per_device = []
    for dev, (ops, names) in sorted(device_ops(path).items()):
        inside = np.asarray([f"/{scope}/" in n for n in names], bool)
        mine = ops[inside]
        execs = calls.get(dev)
        if execs is None or not len(mine):
            continue
        mine = mine[np.argsort(mine[:, 0], kind="stable")]
        took = []
        for start, end in execs:
            if not lo <= start < hi:
                continue
            # the two readers round the ns a little differently: select with slack, clip exactly
            a, b = np.searchsorted(mine[:, 0], [start - _SLACK_NS, end + _SLACK_NS])
            union = trace_lib._union(mine[a:b], start, end)
            took.append(float(np.sum(np.diff(union, axis=1))) * 1e-6)
        if took:
            per_device.append(float(np.mean(took)))
    return float(np.mean(per_device)) if per_device else None
