"""Fill of the grouped expert matmuls, in percent: the (token, held
expert) assignments over the rows the held experts' matmuls computed for
them, tile padding included; both are device counters of the expert
layer (``moe_assigned``, ``moe_rows``), summed over the window."""


def read(ctx):
    w = ctx["work"]
    if not w.get("moe_rows"):
        return None
    return 100.0 * w["moe_assigned"] / w["moe_rows"]
