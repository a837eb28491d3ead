"""Device idle inside the engine's program spans, in percent of the traced
serving window: each device's idle intervals intersected with the union of
the spans ``engine.admit``, ``engine.dispatch``, ``engine.sync``,
``engine.host`` and ``engine.evict`` on the window's thread
(``span_reduce.idle_in``, key ``engine.*``), averaged over devices."""


def read(ctx):
    t = ctx.get("trace") or {}
    idle = (t.get("idle_in") or {}).get("engine.*")
    if idle is None or not t.get("window_s"):
        return None
    return 100.0 * idle / t["window_s"]
