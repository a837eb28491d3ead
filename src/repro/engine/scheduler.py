"""Device-resident slot scheduler: state layout + multi-token decode dispatch.

The continuous batcher's per-slot state (``cur`` token, ``active`` flag,
``remaining`` budget, the prompt buffer and its prefill cursor) lives in jnp
arrays and is updated *inside* the jitted dispatch, so the host never
round-trips per token.  One dispatch runs ``k_steps`` steps under
``lax.scan`` over the paged cache and returns the emitted token grid
``[B, K]`` plus the emission mask — the host syncs once per K steps instead
of once per slot per token.

Every slot decodes every step (inactive slots' writes route to the pool's
trash block), ``remaining`` is decremented only while a slot is active, and
a slot deactivates — and pushes its blocks back on the free stack, inside
the scan — when its budget reaches zero.  Under greedy sampling the emitted
tokens are token-identical to the host loop (engine/legacy.py).

**Chunked prefill** (``chunk > 0``): prompt prefill rides *inside* the same
``lax.scan`` — each scan step runs one decode step for the decoding slots
AND one ``chunk``-token prefill piece for the slots still in prefill phase
(state fields ``prompt`` / ``pf_pos`` / ``pf_len``, armed by the engine's
admission).  A long prompt therefore does not stall in-flight decode: it
streams through K*chunk prompt tokens per dispatch while other slots keep
emitting.  The step a slot's last chunk lands, its first token is sampled
from the chunk's logits and emitted through the same token grid, and
decoding starts the following step ("prefill, sample first, then decode").
Because the two sub-steps share one batch, each pass restores the rows of
slots in the *other* phase (per-slot lengths and SSM state), so a
prefilling slot's accumulating state is never touched by the decode pass's
masked garbage.  The engine runs the ``chunk == 0`` dispatch whenever no
slot is prefilling.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.engine.paged import BSTATE_KEYS, release_slots
from repro.engine.sampler import SamplingParams, sample
from repro.models.lm import Model, decode_rows_read
from repro.telemetry.counters import bump, init_counters


def init_slot_state(n_slots: int, prompt_cap: int,
                    moe: bool = False) -> dict:
    """Zeroed device-side slot state for a fresh pool of ``n_slots``.

    The chunked-prefill fields are a per-slot prompt buffer of
    ``prompt_cap`` tokens plus prefill cursor/length and the
    post-first-token decode budget (armed by the engine's admission).
    ``ctr`` is the device-resident telemetry counter tree
    (repro.telemetry.counters): bumped inside the scan, read for free at
    the existing dispatch sync; ``moe`` adds the expert-layer counters
    (models with MoE layers)."""
    def z():   # a buffer per field: the dispatch donates the state
        return jnp.zeros((n_slots,), jnp.int32)
    return {
        "cur": jnp.zeros((n_slots, 1), jnp.int32),      # last sampled token
        "active": jnp.zeros((n_slots,), bool),          # slot serving a req?
        "remaining": z(),                               # decode budget left
        "ctr": init_counters(moe),                      # device counters
        "prompt": jnp.zeros((n_slots, prompt_cap), jnp.int32),
        "pf_pos": z(),                                  # next prompt row
        "pf_len": z(),                                  # prompt length
        "budget": z(),                                  # decode budget
        "pf_shared": z(),                               # prefix-hit mark
    }


@jax.named_scope("keep_rows")
def _keep_rows(new_cache: dict, old_cache: dict, keep) -> dict:
    """Merge two paged caches per slot: ``lengths`` of slots in ``keep``
    come from ``new_cache``, the others' from ``old_cache``.  Everything
    else stays from ``new_cache``: writes of non-kept slots into the pools
    were trash-routed, and per-slot (SSM) state is kept in the layer scan
    itself (``keep`` of ``decode_step_paged``; a prefill chunk with
    ``valid == 0`` leaves a slot's state exactly as it was), so no
    state-sized select or copy is made here."""
    return {**new_cache,
            "lengths": jnp.where(keep, new_cache["lengths"],
                                 old_cache["lengths"])}


def _bump_moe(ctr: dict, cache: dict) -> dict:
    """Add the last forward's MoE counts (models with MoE layers)."""
    if "moe_counts" not in cache:
        return ctr
    c = cache["moe_counts"]
    return bump(ctr, moe_assigned=c[0], moe_rows=c[1])


@jax.named_scope("prefill_chunk")
def chunk_prefill_substep(model: Model, sp: SamplingParams, chunk: int,
                          params, st: dict, cache: dict, first_key):
    """One in-scan chunked-prefill piece — the prefill *phase* of a scan
    step, shared by the plain decode dispatch and the speculative dispatch
    (engine/spec.py).

    Runs a ``chunk``-token prefill piece for every slot still in prefill
    phase (``pf_pos < pf_len``), restores the rows of slots in other phases
    (``_keep_rows``), samples the first token of slots whose last chunk
    just landed (from ``first_key``), arms them for decode, and releases
    the blocks of zero-budget slots.  Returns ``(st, cache, first [B],
    completed [B])`` — the caller merges ``first`` into its own token grid
    (the plain dispatch's ``[B, K]`` column, the speculative dispatch's
    round column 0, which the just-completed slot — inactive during the
    round — left free).
    """
    pcap = st["prompt"].shape[1]
    pf_left = st["pf_len"] - st["pf_pos"]
    valid = jnp.clip(pf_left, 0, chunk)
    prefilling = valid > 0
    idx = jnp.clip(st["pf_pos"][:, None] + jnp.arange(chunk)[None],
                   0, pcap - 1)
    toks = jnp.take_along_axis(st["prompt"], idx, axis=1)
    logits_pf, new_cache = model.prefill_chunk_paged(
        params, toks, cache, st["pf_pos"], valid, st["pf_shared"])
    cache = _keep_rows(new_cache, cache, prefilling)
    st = {**st, "ctr": _bump_moe(st["ctr"], cache)}
    completed = prefilling & (pf_left <= chunk)
    first = sample(logits_pf, first_key, sp)
    go = completed & (st["budget"] > 0)
    cache = {**cache, "slot_active": cache["slot_active"] | go}
    nf0 = cache["n_free"]
    bstate = release_slots({k: cache[k] for k in BSTATE_KEYS},
                           completed & ~go)
    cache = {**cache, **bstate}
    ctr = bump(st["ctr"],
               tokens=jnp.sum(completed),   # first tokens emit via the grid
               chunk_pieces=jnp.sum(prefilling),
               chunks_completed=jnp.sum(completed),
               blocks_released=cache["n_free"] - nf0)
    st = {**st,
          "cur": jnp.where(completed[:, None], first[:, None], st["cur"]),
          "active": st["active"] | go,
          "remaining": jnp.where(completed, st["budget"], st["remaining"]),
          "pf_pos": st["pf_pos"] + valid,
          "ctr": ctr}
    return st, cache, first, completed


def named_dispatch(fn, name: str):
    """``fn`` under ``name``: jit names the module ``jit_<name>``, so each
    dispatch variant's device ops read ``jit_<name>:<op>`` in a trace."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def make_decode_dispatch(model: Model, sp: SamplingParams, k_steps: int,
                         *, cow: bool = False, chunk: int = 0,
                         n_spec: int = 0):
    """Build the jitted K-step decode dispatch.

    ``dispatch(params, state, cache, key)`` -> (state, cache, tokens [B, K],
    emitted [B, K] bool).  ``emitted[b, j]`` marks tokens produced while slot
    ``b`` was still active; it is a contiguous prefix per row, so the host
    can append ``tokens[b, emitted[b]]`` verbatim.

    ``n_spec > 0`` swaps each scan step for a **speculative round** (draft
    ``n_spec`` tokens with a quantized tree, verify with one full-precision
    forward — engine/spec.py): the returned dispatch then takes an extra
    ``draft_params`` argument after ``params`` and a runtime ``depth``
    scalar before ``key`` (the dynamic speculation depth, 1..n_spec — a
    plain traced operand, so moving it never recompiles), and its grids
    widen to ``[B, k_steps * (n_spec + 1)]`` (acceptance telemetry rides
    the ``state["ctr"]`` counter tree).  Speculation **composes** with both
    flags: ``cow=True`` makes the round's span allocation copy-on-write (a
    draft/verify write into a prefix-shared block pops a private copy
    first, exactly like a decode write), and ``chunk > 0`` appends the
    chunked-prefill phase to every round — the three are orthogonal phases
    of one scan step.

    The cache is the paged block pool (``model.init_paged_cache``): each
    step runs ``decode_step_paged`` (which pops blocks from the device
    free-list as slots cross block boundaries) and the moment a slot's
    budget drains its blocks are pushed back **inside the scan** — capacity
    recycles mid-dispatch without a host round-trip.  ``cow=True`` enables
    the copy-on-write write path (refcounted prefix caching).  ``chunk > 0``
    piggybacks chunked prefill on the scan (see module docstring); the
    prefill state fields ride through the ``chunk == 0`` dispatch
    untouched, so one state pytree serves both.
    """
    if n_spec:
        from repro.engine.spec import make_spec_dispatch
        return make_spec_dispatch(model, sp, k_steps, n_spec, cow=cow,
                                  chunk=chunk)
    if model.decode_step_paged is None or model.prefill_chunk_paged is None:
        raise NotImplementedError(
            f"model family {model.cfg.family!r} has no paged decode path")

    def dispatch(params, state: dict, cache: dict, key):
        def body(carry, step_key):
            st, cache = carry
            # ---- decode sub-step (slots in decode phase) ----------------
            # allocator deltas around the step count pops/CoW
            nf0, ref0 = cache["n_free"], cache["ref"]
            ctr = bump(st["ctr"], kv_rows_read=decode_rows_read(cache,
                                                                model.cfg))
            # idle and prefilling slots keep their per-slot state
            logits, new_cache = model.decode_step_paged(
                params, st["cur"], cache, cow=cow, keep=st["active"])
            ctr = _bump_moe(ctr, new_cache)
            if chunk:  # prefilling/idle slots' rows must stay untouched
                new_cache = _keep_rows(new_cache, cache, st["active"])
            cache = new_cache
            nxt = sample(logits, step_key, sp)
            emitted = st["active"]
            remaining = st["remaining"] - emitted.astype(jnp.int32)
            active = emitted & (remaining > 0)
            # alloc_step only pops; a CoW pop is the only ref decrement
            ctr = bump(ctr,
                       blocks_popped=nf0 - cache["n_free"],
                       cow_copies=jnp.sum(cache["ref"] < ref0))
            nf1 = cache["n_free"]
            bstate = release_slots({k: cache[k] for k in BSTATE_KEYS},
                                   emitted & ~active)
            cache = {**cache, **bstate}
            ctr = bump(ctr, blocks_released=cache["n_free"] - nf1)
            tok_out, em_out = nxt, emitted
            st = {**st, "cur": nxt[:, None], "active": active,
                  "remaining": remaining,
                  "ctr": bump(ctr, tokens=jnp.sum(emitted))}
            # ---- chunked-prefill sub-step -------------------------------
            if chunk:
                st, cache, first, completed = chunk_prefill_substep(
                    model, sp, chunk, params, st, cache,
                    jax.random.fold_in(step_key, 1))
                tok_out = jnp.where(completed, first, tok_out)
                em_out = em_out | completed
                st = {**st, "cur": tok_out[:, None]}
            return (st, cache), (tok_out, em_out)

        keys = jax.random.split(key, k_steps)
        (state, cache), (toks, emitted) = jax.lax.scan(
            body, (state, cache), keys)
        return state, cache, toks.T, emitted.T

    return named_dispatch(dispatch, "chunk_dispatch" if chunk
                          else "decode_dispatch")


def make_decode_step(model: Model, sp: SamplingParams | None = None):
    """One decode step + sampling: (params, tokens, cache, key=) ->
    (next_tok [B, 1], logits [B, V], new cache).

    The contiguous-cache step of the host loop (engine/legacy.py);
    ``launch.steps.make_serve_step`` is a deprecated greedy alias of it.
    """
    sp = sp or SamplingParams()

    def step(params, tokens, cache, key=None):
        logits, cache = model.decode_step(params, tokens, cache)
        k = key if key is not None else jax.random.PRNGKey(0)
        nxt = sample(logits, k, sp)[:, None]
        return nxt, logits, cache

    return step
