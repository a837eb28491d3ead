"""Device idle inside the quantizer's ``quantize.report`` spans (the
blocking reads of each leaf's report), in percent of the traced quantize
window (``span_reduce.idle_in``), averaged over devices."""


def read(ctx):
    t = ctx.get("trace") or {}
    idle = (t.get("idle_in") or {}).get("quantize.report")
    if idle is None or not t.get("window_s"):
        return None
    return 100.0 * idle / t["window_s"]
