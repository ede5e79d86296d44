"""Reduction of a profiler trace to device busy time, idle share and a
breakdown.

The harness records a JAX profiler trace of its measured window and
marks the window and each host phase with ``TraceAnnotation`` spans
named ``bench.*``.  This module reads the ``.xplane.pb`` file with
nothing but JAX and reduces it:

- device operations: the events of each device plane's ``XLA Ops``
  line, clipped to the window (a device plane with events but no such
  line is an error; no other line stands in for it).  They nest (a
  ``while`` loop holds the ops of its body), so each op's own time is
  its duration less that of the ops nested in it;
- busy time: the length of the union of those intervals, averaged over
  the devices that ran anything;
- idle gaps: the stretches of the window with no operation on the
  device, each named after the innermost ``bench.*`` host span that
  covers its midpoint.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
# The line of a device plane that holds its operations.
OP_LINE = "XLA Ops"


@dataclass
class Timeline:
    """Plain intervals (ns, on the trace's common clock) read from one
    trace: host spans and each device's operations."""
    host: list = field(default_factory=list)      # (name, start, end)
    devices: dict = field(default_factory=dict)   # plane -> [(name, s, e)]


def find_trace(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Timeline:
    """Read one ``.xplane.pb`` (or ``.xplane.pb.gz``) into a
    :class:`Timeline`."""
    from jax.profiler import ProfileData

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    return timeline(data.planes)


def timeline(planes) -> Timeline:
    """A :class:`Timeline` from the planes of a trace (each with a
    ``name`` and ``lines`` of ``events``, as ``jax.profiler`` reads
    them)."""
    tl = Timeline()
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:"):
            if OP_LINE not in lines:
                if any(any(True for _ in line.events)
                       for line in plane.lines):
                    raise ValueError(f"device plane {plane.name!r} has "
                                     f"events but no {OP_LINE!r} line")
                continue
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines[OP_LINE].events]
            if ops:
                tl.devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        tl.host.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
    return tl


def window_of(tl: Timeline) -> tuple[float, float]:
    """The ``bench.window`` span: (start, end) in ns."""
    spans = [(s, e) for name, s, e in tl.host if name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def union(intervals, lo: float, hi: float) -> list:
    """Sorted disjoint union of ``intervals`` clipped to [lo, hi]."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def short_name(text: str) -> str:
    """``%fusion.7 = f32[8,128]{...} fusion(...)`` -> ``fusion.7
    f32[8,128]``: the HLO instruction's name and result shape."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:80]
    shape = re.match(r"[a-z0-9]+\[[0-9,]*\]", rest)
    return head.lstrip("%") + (" " + shape.group(0) if shape else "")


def self_times(ops) -> list:
    """(name, own ns) of each (name, start, end) op: its duration less
    the durations of the ops nested directly inside it."""
    out, stack = [], []          # stack entries: [name, start, end, inner]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][2]:
            n0, s0, e0, inner = stack.pop()
            out.append((n0, e0 - s0 - inner))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0])
    out.extend((n0, e0 - s0 - inner) for n0, s0, e0, inner in stack)
    return out


def host_phase(tl: Timeline, t: float) -> str:
    """Name of the innermost ``bench.*`` host span covering time ``t``
    (the window span itself only when nothing finer covers it)."""
    best = None
    for name, s, e in tl.host:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside bench spans"


def reduce(tl: Timeline, top: int = 10) -> dict | None:
    """Busy and window seconds, the device's busiest operations and the
    longest idle gaps inside the window; None when no device ran
    anything in it."""
    lo, hi = window_of(tl)
    per_device, op_time = [], defaultdict(float)
    gaps = []
    for ops in tl.devices.values():
        busy = union([(s, e) for _, s, e in ops], lo, hi)
        if not busy:
            continue
        per_device.append(sum(e - s for s, e in busy))
        clipped = [(name, max(s, lo), min(e, hi)) for name, s, e in ops
                   if min(e, hi) > max(s, lo)]
        for name, own in self_times(clipped):
            op_time[short_name(name)] += own
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((host_phase(tl, (s + e) / 2), e - s))
    if not per_device:
        return None
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[1])
    return {
        "busy_s": sum(per_device) / len(per_device) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(per_device),
        "device_ops": [[name, t / 1e9] for name, t in ops_sorted],
        "idle_gaps": [[name, t / 1e9] for name, t in gaps[:top]],
    }
