"""The host-driven serving loop over the contiguous cache: the engine's
parity reference.

Batch-1 prefill (one ``model.prefill`` a request) with a Python-side cache
scatter per slot, then one ``model.decode_step`` at a time with a blocking
``int()`` host sync per slot per decoded token.  It shares no code with the
engine's paged pool, chunked prefill or dispatch scan, which is what makes
it an oracle: the engine's greedy-parity tests pin token-exactness against
it.

The one behavioral change from the historical code: the ``greedy=False``
branch used to compute ``int(logits.argmax())`` — identical to the greedy
branch — so non-greedy serving was never real.  Both paths now route
through :mod:`repro.engine.sampler`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.engine.sampler import SamplingParams, sample
from repro.engine.scheduler import make_decode_step
from repro.models.lm import Model


def single_slot_prefill(model: Model, params, cache, tokens_row, slot: int,
                        cache_len: int):
    """Prefill one request into ``slot`` of a live batch cache.

    Runs a batch-1 prefill and scatters the resulting per-layer cache rows
    into the slot (the per-slot path of host-driven continuous batching)."""
    logits, one_cache = model.prefill(
        params, {"tokens": tokens_row[None]}, cache_len=cache_len)

    # scatter every [n_periods, 1, ...] leaf into [n_periods, B, ...] slot
    def scatter(full_leaf, one_leaf):
        return full_leaf.at[:, slot].set(one_leaf[:, 0].astype(full_leaf.dtype))

    new_stack = jax.tree.map(scatter, cache["stack"], one_cache["stack"])
    new_cache = dict(cache)
    new_cache["stack"] = new_stack
    if "prefix" in cache:
        new_cache["prefix"] = jax.tree.map(scatter, cache["prefix"],
                                           one_cache["prefix"])
    new_cache["lengths"] = cache["lengths"].at[slot].set(
        one_cache["lengths"][0])
    return logits[0], new_cache


def serve_host_loop(model: Model, params, requests: list[jnp.ndarray], *,
                    batch: int, gen_tokens: int, cache_len: int,
                    sampling: SamplingParams | None = None, seed: int = 0,
                    return_stats: bool = False):
    """Serve ``requests`` with a B-slot host-scheduled batcher over the
    contiguous cache (``model.init_cache``); each gets ``gen_tokens``."""
    sp = sampling or SamplingParams()
    step = jax.jit(make_decode_step(model, sp), donate_argnums=2)
    cache = model.init_cache(batch, cache_len)
    cur = jnp.zeros((batch, 1), jnp.int32)
    active = [-1] * batch                 # request id per slot
    remaining = [0] * batch
    outputs: dict[int, list[int]] = {}
    queue = list(range(len(requests)))
    key = jax.random.PRNGKey(seed)
    stats = {"host_syncs": 0, "dispatches": 0, "prefill_calls": 0,
             "decode_steps": 0, "tokens": 0}

    def fill_slot(slot, cache, cur, key):
        # a request whose whole budget is its first token leaves the slot
        # to the next one at once
        while queue and active[slot] < 0:
            rid = queue.pop(0)
            logits, cache = single_slot_prefill(model, params, cache,
                                                requests[rid], slot,
                                                cache_len)
            key, sub = jax.random.split(key)
            nxt = int(sample(logits[None], sub, sp)[0])
            stats["prefill_calls"] += 1
            stats["host_syncs"] += 1
            stats["tokens"] += 1
            cur = cur.at[slot, 0].set(nxt)
            outputs[rid] = [nxt]
            active[slot] = rid if gen_tokens > 1 else -1
            remaining[slot] = gen_tokens - 1
        return cache, cur, key

    for slot in range(batch):
        cache, cur, key = fill_slot(slot, cache, cur, key)

    while any(a >= 0 for a in active):
        key, sub = jax.random.split(key)
        cur, logits, cache = step(params, cur, cache, sub)
        stats["dispatches"] += 1
        stats["decode_steps"] += 1
        for slot in range(batch):
            rid = active[slot]
            if rid < 0:
                continue
            outputs[rid].append(int(cur[slot, 0]))   # 1 sync per slot-token
            stats["host_syncs"] += 1
            stats["tokens"] += 1
            remaining[slot] -= 1
            if remaining[slot] <= 0:
                active[slot] = -1
                cache, cur, key = fill_slot(slot, cache, cur, key)
    outs = [outputs[i] for i in sorted(outputs)]
    return (outs, stats) if return_stats else outs
