"""Program spans on the profiler's clock, and Chrome/Perfetto trace export.

:func:`span` is the one span primitive of the program.  It always opens a
``jax.profiler.TraceAnnotation`` named ``"<track>.<name>"`` (a few
microseconds when no profiler runs), so a ``jax.profiler`` trace shows the
program's own phases: ``engine.admit``, ``engine.dispatch``,
``engine.sync``, ``engine.host``, ``engine.evict`` in the serving engine;
``quantize.leaf``, ``quantize.search``, ``quantize.report``,
``quantize.global`` in ``repro.quantize.quantize``.  When a
:class:`Tracer` is passed, the span is also recorded as a Chrome "X" event
on track ``track``.

:class:`Tracer` serializes its events in the Chrome trace-event JSON-object
format::

    {"traceEvents": [...], "displayTimeUnit": "ms"}

Open the file in Perfetto (https://ui.perfetto.dev) or
``chrome://tracing``.  Its timestamps come from :func:`clock_us`, the clock
the profiler stamps host events with (``CLOCK_REALTIME``), so the JSON and
an ``.xplane.pb`` of the same run lie on one time axis: an xplane event's
``start_ns`` is an offset from :func:`xplane_origin_us`.  Recording an
event is an O(1) list append of values already on the host; the tracer
never touches the device.
"""
from __future__ import annotations

import contextlib
import json
import time

import jax

TRACE_PID = 1   # one "process": the engine


def clock_us() -> float:
    """Microseconds on the profiler's host clock (``CLOCK_REALTIME``)."""
    return time.time_ns() / 1e3


def xplane_origin_us(profile_data) -> float:
    """The instant, in :func:`clock_us` microseconds, that the event times
    of a ``jax.profiler.ProfileData`` count from: its ``profile_start_time``
    (``start_ns / 1e3`` plus this is an event's Chrome ``ts``)."""
    for plane in profile_data.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                return value / 1e3
    raise ValueError("the trace records no profile_start_time")


@contextlib.contextmanager
def span(track: str, name: str, tracer: "Tracer | None" = None, **args):
    """A program span: a profiler annotation ``"<track>.<name>"`` carrying
    ``args``, and, with a ``tracer``, a Chrome "X" event on ``track``."""
    with jax.profiler.TraceAnnotation(f"{track}.{name}", **args):
        if tracer is None:
            yield
            return
        t0 = clock_us()
        try:
            yield
        finally:
            tracer.complete(track, name, t0, args)


class Tracer:
    """Host-side Chrome trace-event recorder.

    Events within a track are recorded in wall order, so per-track ``ts``
    is non-decreasing (a schema property the tests pin).  Duration ("X")
    events take their start from :func:`clock_us`, captured by the caller
    (or by :func:`span`) before the spanned work.
    """

    def __init__(self):
        self._tids: dict[str, int] = {}
        self.events: list[dict] = []

    def _tid(self, track: str) -> int:
        tid = self._tids.get(track)
        if tid is None:
            tid = len(self._tids) + 1
            self._tids[track] = tid
            self.events.append({
                "name": "thread_name", "ph": "M", "pid": TRACE_PID,
                "tid": tid, "args": {"name": track},
            })
        return tid

    def complete(self, track: str, name: str, start_us: float,
                 args: dict | None = None) -> None:
        """A duration ("X") event spanning ``start_us`` .. now."""
        ev = {
            "name": name, "ph": "X", "pid": TRACE_PID,
            "tid": self._tid(track), "ts": start_us,
            "dur": max(clock_us() - start_us, 0.0),
        }
        if args:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, name: str, values: dict) -> None:
        """A counter ("C") sample: ``values`` are series-name -> number,
        rendered by the viewer as a stacked area track."""
        self.events.append({
            "name": name, "ph": "C", "pid": TRACE_PID,
            "ts": clock_us(),
            "args": {k: float(v) for k, v in values.items()},
        })

    def to_dict(self) -> dict:
        return {
            "traceEvents": [
                {"name": "process_name", "ph": "M", "pid": TRACE_PID,
                 "args": {"name": "repro.engine"}},
                *self.events,
            ],
            "displayTimeUnit": "ms",
        }

    def save(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1)
            f.write("\n")
