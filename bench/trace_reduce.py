"""Reduce one profiler trace (``.xplane.pb``) to the device's busy time, its
idle share, the operations that took most time and the longest idle gaps.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:`` plane), clipped to the
traced window and averaged over the devices. An idle gap is a stretch of
the window in which no operation ran; each gap is named by what the host
was doing in it: the host span, among those given, that covers most of
the gap, or ``host_idle`` where none does.

The trace's clock is tied to ``time.monotonic()`` by one
``TraceAnnotation`` named ``SYNC`` that the caller enters at a monotonic
time it records.
"""
from __future__ import annotations

import collections
import glob
import json
import os
import re

SYNC = "bench.clock_sync"
OPS_LINE = "XLA Ops"
TOP = 10


_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"[a-z]+\d*\[\d[\d,]*\]")


def short_name(hlo: str) -> str:
    """An op event's name is its HLO text; keep the instruction's name, its
    opcode and its first non-scalar output shape, e.g. ``%while.24 while
    pred[4096]``."""
    token, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo[:80]
    op = _OPCODE.search(rest)
    shape = _SHAPE.search(rest)
    return " ".join([token] + ([op.group(1)] if op else [])
                    + ([shape.group(0)] if shape else []))


def union(intervals) -> list:
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(merged, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in merged if e > lo and s < hi]


def gaps(merged, lo: float, hi: float) -> list:
    """Idle stretches of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(gap, host_spans) -> str:
    """The host span name that covers most of ``gap``."""
    cover = collections.Counter()
    for name, s, e in host_spans:
        o = min(e, gap[1]) - max(s, gap[0])
        if o > 0:
            cover[name] += o
    return cover.most_common(1)[0][0] if cover else "host_idle"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_events(path: str):
    """``(device_ops, sync_s)``: per device plane, its op events as
    ``(name, start_s, end_s)`` on the trace's clock; and the start of the
    clock-sync annotation on the same clock (None if absent)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, sync = {}, None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if is_device and line.name == OPS_LINE:
                devices[plane.name] = [
                    (short_name(ev.name), ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events]
            elif not is_device and sync is None:
                for ev in line.events:
                    if ev.name == SYNC:
                        sync = ev.start_ns * 1e-9
                        break
    return devices, sync


def reduce(path: str, *, sync_mono: float, lo_mono: float, hi_mono: float,
           host_spans=()) -> dict:
    """Busy seconds (mean over devices), window seconds, the top device
    operations and the longest idle gaps, for the window
    ``[lo_mono, hi_mono]`` given on the monotonic clock; ``host_spans``
    are ``(name, start, end)`` on that clock."""
    devices, sync = read_events(path)
    if sync is None:
        raise ValueError(f"{path} holds no {SYNC!r} annotation")
    if not devices:
        raise ValueError(f"{path} holds no device plane with an "
                         f"{OPS_LINE!r} line")
    shift = sync - sync_mono            # trace clock minus monotonic
    lo, hi = lo_mono + shift, hi_mono + shift
    spans = [(n, s + shift, e + shift) for n, s, e in host_spans]
    busy, op_time, idle = [], collections.Counter(), []
    for ops in devices.values():
        merged = union((s, e) for _, s, e in ops)
        busy.append(sum(e - s for s, e in clip(merged, lo, hi)))
        for name, s, e in ops:
            o = min(e, hi) - max(s, lo)
            if o > 0:
                op_time[name] += o
        idle.extend(gaps(merged, lo, hi))
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": hi - lo,
        "n_devices": len(devices),
        "device_ops": [[n, t / len(devices)]
                       for n, t in op_time.most_common(TOP)],
        "idle_gaps": [[name_gap(g, spans), g[1] - g[0]]
                      for g in idle[:TOP]],
    }


def load_peaks(device_kind: str, path: str | None = None) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``; an unknown kind
    is an error, never a default."""
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"{path} knows {sorted(table['devices'])}")
    return table["devices"][device_kind]
