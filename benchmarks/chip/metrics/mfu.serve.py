"""Model FLOPs of every token the window processed (prompt tokens actually
prefilled, prefix hits excluded, plus generated tokens; counts.py) over
the window's wall time and the chip's bf16 peak, in percent."""


def read(ctx):
    w = ctx["work"]
    if not w.get("model_flops"):
        return None
    return 100.0 * w["model_flops"] / ctx["window_s"] / ctx["peaks"]["bf16_flops"]
