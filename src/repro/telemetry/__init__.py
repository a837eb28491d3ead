"""Observability for the serving engine and the quantizer, all free on
the hot path:

* **Device-resident counters** (:mod:`repro.telemetry.counters`): a small
  int32 pytree (``state["ctr"]``) threaded through the dispatch scan carry
  by ``engine/scheduler.py`` / ``engine/spec.py`` and bumped where the
  events happen (token emission, block pops/releases, CoW copies, prefix
  hits, chunk pieces, blocked speculative slots).  The counters ride the
  donated state tree, so they are read *for free* at the once-per-K host
  sync the engine already pays — zero new host syncs, zero recompiles
  (pinned by the staticcheck fingerprint manifest and a compile-count
  test).
* **Request-lifecycle metrics** (:mod:`repro.telemetry.metrics`): a
  host-side :class:`MetricsRegistry` of counters, gauges and log-bucketed
  histograms — per-request TTFT / TPOT / queue-wait / lengths, acceptance
  rate, prefix-hit fraction, allocator gauges — snapshotted to a stable
  JSON schema (``repro.telemetry.metrics/v1``) and summarized
  (p50/p95/p99) by the serve CLI.
* **Spans and trace export** (:mod:`repro.telemetry.trace`): the
  program's spans (``engine.*``, ``quantize.*``) are profiler annotations
  opened by :func:`~repro.telemetry.trace.span`; a :class:`Tracer` also
  records them as Chrome/Perfetto trace-event JSON on the profiler's
  clock, with a prefill-chunk track and counter tracks sampled from the
  device counters.  Open the file in Perfetto (https://ui.perfetto.dev)
  or ``chrome://tracing``.
* **Named scopes** (:mod:`repro.telemetry.scopes`): the scopes of the
  program's device work, read back per op from compiled HLO.

Enable the host-side layers with ``Engine(..., metrics=MetricsRegistry(),
tracer=Tracer())`` or ``python -m repro.launch.serve --metrics-out PATH
--trace-out PATH``; the device counters are always on (a handful of
scalar adds inside the scan) and surface as ``stats["counters"]``.
"""
from repro.telemetry.counters import (COUNTER_KEYS, bump, counter_totals,
                                      init_counters)
from repro.telemetry.metrics import (Counter, Gauge, Histogram,
                                     MetricsRegistry, METRICS_SCHEMA)
from repro.telemetry.trace import TRACE_PID, Tracer, span

__all__ = [
    "COUNTER_KEYS", "init_counters", "bump", "counter_totals",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "METRICS_SCHEMA",
    "Tracer", "TRACE_PID", "span",
]
