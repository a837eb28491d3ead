"""From a profiler trace to device idle time inside the program's spans and
device time under its named scopes.

The program opens its spans as profiler annotations named
``<track>.<name>`` (``repro.telemetry.trace.span``: ``engine.admit``,
``engine.dispatch``, ``engine.sync``, ``engine.host``, ``engine.evict``,
``quantize.leaf``, ``quantize.search``, ``quantize.report``,
``quantize.global``), and names the scopes of its device work
(``repro.telemetry.scopes``).  Like ``trace_reduce.reduce`` these
reductions take the trace as plain ``(name, start_ns, end_ns)`` tuples
(``trace_reduce.load``), clip it to the host span ``window``, and average
over devices.

* :func:`idle_in` — device-idle seconds inside each program span name, and
  inside each track (``engine.*``: the union of every span of the track),
  on the thread that opened the window.
* :func:`device_scopes` — device self seconds per scope, from a
  ``{"<module>:<op>": scope path}`` map (``scopes.op_scopes`` of each
  compiled module): an op counts towards every scope on its path.

``trace_reduce.reduce`` picks the same window with code of its own.
"""
from __future__ import annotations

from collections import defaultdict

from benchmarks.chip.trace_reduce import WINDOW, _union, self_times

TRACKS = ("engine", "quantize")


def window(host: dict):
    """(start, end, thread) of the longest ``window`` span, or None."""
    win = [(s, e, line) for line, evs in host.items()
           for n, s, e in evs if n == WINDOW]
    return max(win, key=lambda t: t[1] - t[0]) if win else None


def idle_intervals(evs, w0, w1) -> list:
    """The gaps inside [w0, w1] in which no op of ``evs`` ran."""
    merged = _union((max(s, w0), min(e, w1)) for _, s, e in evs
                    if e > w0 and s < w1)
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def overlap(a, b) -> int:
    """Total length shared by two sorted lists of disjoint intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_in(devices: dict, host: dict) -> dict:
    """``{span name or "<track>.*": device-idle seconds inside it}`` for the
    program's spans (tracks :data:`TRACKS`) on the window's thread."""
    w = window(host)
    if not w or not devices:
        return {}
    w0, w1, wline = w
    spans = defaultdict(list)
    for n, s, e in host[wline]:
        track = n.split(".", 1)[0]
        if track in TRACKS and "." in n and e > w0 and s < w1:
            iv = (max(s, w0), min(e, w1))
            spans[n].append(iv)
            spans[f"{track}.*"].append(iv)
    idles = [idle_intervals(evs, w0, w1) for evs in devices.values()]
    return {n: sum(overlap([tuple(x) for x in _union(ivs)], idle)
                   for idle in idles) / len(idles) / 1e9
            for n, ivs in sorted(spans.items())}


def self_seconds(devices: dict, host: dict) -> dict:
    """``{op: device self seconds}`` inside the window, averaged over
    devices."""
    w = window(host)
    if not w or not devices:
        return {}
    out = defaultdict(float)
    for evs in devices.values():
        for op, t in self_times(evs, w[0], w[1]).items():
            out[op] += t / len(devices) / 1e9
    return dict(out)


def device_scopes(devices: dict, host: dict, scope_map: dict) -> dict:
    """``{scope: device self seconds of the ops on whose path it lies}``
    inside the window, averaged over devices."""
    out = defaultdict(float)
    for op, t in self_seconds(devices, host).items():
        for scope in filter(None, scope_map.get(op, "").split("/")):
            out[scope] += t
    return dict(out)
