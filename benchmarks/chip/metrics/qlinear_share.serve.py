"""Device self time of the ops under the named scope ``qlinear``, the
quantized linears (dequantize + matmul, the tied head, the embedding
take), in percent of the device's busy time in the traced serving window
(``span_reduce.device_scopes``; an op counts towards every scope on its
path)."""


def read(ctx):
    t = ctx.get("trace") or {}
    scopes = t.get("device_scopes")
    if not scopes or not t.get("busy_s"):
        return None
    return 100.0 * scopes.get("qlinear", 0.0) / t["busy_s"]
