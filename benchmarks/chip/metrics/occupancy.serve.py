"""Scheduler occupancy, in percent: generated tokens over (decode steps x
slots), both exact counts of the engine."""


def read(ctx):
    w = ctx["work"]
    if not w.get("decode_steps"):
        return None
    return 100.0 * w["generated"] / (w["decode_steps"] * w["slots"])
