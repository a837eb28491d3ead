"""From a profiler trace (``.xplane.pb``) to device busy and idle time.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to
the traced window: the host span named ``window``.  Each idle gap inside
the window is labelled with the innermost host span (an annotation of the
harness, or a Python frame where the profiler recorded them) that covers
the gap's middle on the thread that opened the window.  Device ops are
ranked by self time: nested ops (the body of a ``while``) are not counted
twice.
"""
from __future__ import annotations

import bisect
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def load(path):
    """(device op events per device plane, host events per host line) as
    plain (name, start_ns, end_ns) tuples."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in \
                plane.name:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            mods = [(e.start_ns, e.start_ns + e.duration_ns,
                     e.name.split("(", 1)[0])
                    for e in lines[MODULES_LINE].events] \
                if MODULES_LINE in lines else []
            starts = [m[0] for m in mods]
            evs = []
            for e in lines[OPS_LINE].events:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] + ":" if i >= 0 and \
                    e.start_ns < mods[i][1] else ""
                evs.append((mod + op_name(e.name), e.start_ns,
                            e.start_ns + e.duration_ns))
            devices[plane.name] = evs
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host[line.name] = [(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events]
    return devices, host


def self_times(evs, w0, w1) -> dict:
    """Time of each op name inside [w0, w1] less the time of the ops nested
    in it (a ``while`` holds the ops of its body on the same line)."""
    out = defaultdict(float)
    stack = []          # [name, end, self time] of the enclosing ops
    for n, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            name, _, t = stack.pop()
            out[name] += t
        if stack:
            stack[-1][2] -= e - s
        stack.append([n, e, e - s])
    for name, _, t in stack:
        out[name] += t
    return out


def reduce(devices: dict, host: dict, top: int = 10) -> dict:
    """busy_s, window_s (per device, averaged), the ``top`` device ops by
    self time and the ``top`` longest idle gaps with their host label."""
    win = [(s, e, line) for line, evs in host.items()
           for n, s, e in evs if n == WINDOW]
    if not win or not devices:
        return {}
    w0, w1, wline = max(win, key=lambda t: t[1] - t[0])
    spans = sorted((e - s, n, s, e) for n, s, e in host[wline]
                   if s >= w0 and e <= w1)
    busy, ops, gaps = [], defaultdict(float), []
    for evs in devices.values():
        merged = _union((max(s, w0), min(e, w1)) for _, s, e in evs
                        if e > w0 and s < w1)
        busy.append(sum(e - s for s, e in merged))
        for n, t in self_times(evs, w0, w1).items():
            ops[n] += t / len(devices)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(b - a, a) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps = sorted(gaps, reverse=True)[:top]
    labels = [next((n for _, n, s, e in spans if s <= a + g / 2 <= e), WINDOW)
              for g, a in gaps]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label, g / 1e9] for (g, _), label in zip(gaps, labels)],
    }
