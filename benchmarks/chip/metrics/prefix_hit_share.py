"""Prefix-cache hit share, in percent: the engine's device counter
``prefix_hit_tokens`` over all prompt tokens served in the window."""


def read(ctx):
    w = ctx["work"]
    if not w.get("prompt_tokens"):
        return None
    return 100.0 * w["prefix_hit_tokens"] / w["prompt_tokens"]
