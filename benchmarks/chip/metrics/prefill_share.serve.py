"""Device self time of the ops under the named scope ``prefill_chunk``, the
in-scan chunked prefill (its quantized linears and attention included), in
percent of the device's busy time in the traced serving window
(``span_reduce.device_scopes``; an op counts towards every scope on its
path)."""


def read(ctx):
    t = ctx.get("trace") or {}
    scopes = t.get("device_scopes")
    if not scopes or not t.get("busy_s"):
        return None
    return 100.0 * scopes.get("prefill_chunk", 0.0) / t["busy_s"]
