"""Self-speculative decoding: the quantized param tree drafts, the
full-precision tree verifies — inside one jitted K-round dispatch, composed
with chunked prefill and refcounted prefix caching.

DAQ's claim is that delta-aware quantization preserves the *behavior* the
fine-tune encoded in small-magnitude ΔW, not just per-tensor reconstruction
error.  This subsystem operationalizes that claim in the serving hot path:
the quantized model (any ``repro.quantize`` registry method — ``daq``,
``absmax``, …) autoregressively drafts up to ``n_spec`` tokens, one
multi-token verify forward of the full-precision model scores them all, and
a prefix is accepted.  The **draft acceptance rate** is then a data-free,
end-to-end, token-level behavioral-fidelity metric for the quantization
method — and every accepted draft is a decode step the verifier never had
to run serially, so it is also a tok/s win wherever a C-token forward costs
less than C single-token forwards (every memory-bound accelerator).

One speculative **round** (one step of the K-step dispatch scan) is built
from orthogonal phases; chunk-prefill pieces and copy-on-write prefix
semantics compose with speculation instead of excluding it:

1. **span allocation (+ CoW)** — ``paged.alloc_span`` pops the blocks
   covering the round's write span ``[len, len + n_spec + 1)`` once, so
   neither the draft steps nor the verify forward allocate (SWA rings are
   fully allocated at admission already).  With prefix caching
   (``cow=True``) the span's first block may be a partially-matched prompt
   block shared through the prefix index: the span allocator then pops a
   private copy, rewires the table, drops one reference on the source —
   exactly what ``alloc_step`` does for a shared decode target — and
   ``models.lm.cow_copy_blocks`` materializes the copy before any write of
   the round lands.  A slot whose CoW pop failed (pool dry; unreachable
   under the engine's reservation ledger) is masked out of the whole round
   and retries next round, so a draft write can never corrupt a block
   other owners read.
2. **draft** — ``n_spec`` ordinary ``decode_step_paged`` calls with the
   quantized tree, scanned on a working copy of the cache.  The draft
   reads the verifier's (full-precision) KV for all history and its own
   fresh rows for the current round; its writes land in the same span the
   verify forward overwrites, so no draft-quality KV ever survives a round.
   Slots still in chunked-prefill phase are not ``slot_active``, so their
   draft writes trash-route and their accumulating state is untouched.
3. **verify** — one ``model.verify_chunk_paged`` forward of the
   full-precision tree over ``[cur, d_1 .. d_n]`` returns logits at every
   position, each row a bitwise mirror of the decode step the
   non-speculative engine would have run (decode-softmax attention over
   the gathered table — prefix-shared full blocks gather like any other —
   exact per-token SSM recurrence; models/lm.py).
4. **accept** — greedy: the longest prefix with ``argmax(p_i) == d_i``,
   then the verifier's own argmax as correction/bonus.  Sampled: lossless
   rejection sampling over the *warped* (temperature/top-k/top-p)
   distributions — accept ``d_i`` with prob ``min(1, p_i(d)/q_i(d))``,
   sample the first rejection from ``norm(max(p - q, 0))``, the
   all-accepted bonus from ``p_{n+1}``.  Both rules take the runtime
   ``depth`` scalar (dynamic speculation depth, see below): positions at
   or beyond ``depth`` are treated as never-proposed (greedy: forced
   mismatch; sampled: rejected with ``q := 0``, so the cutoff position
   resamples from ``p`` itself — the bonus formula), which makes depth-d
   rounds distribution-identical to static ``n_spec = d`` rounds.
5. **rollback** — rejected positions roll back per slot: ``lengths``
   rewinds to the accepted point (stale KV rows beyond it are masked by
   every later read and overwritten by later writes; their blocks stay in
   the slot's table for the slot to grow into).  Families with recurrent
   or ring state (SSM / hybrid / SWA) cannot rewind by masking alone, so
   they run a **second** verify pass with ``valid = accepted`` over the
   pre-round cache — recomputing exactly the accepted rows' state — while
   pure linear-attention stacks (dense / MoE) keep the first pass's cache
   and only rewind ``lengths``.
6. **chunked prefill** (``chunk > 0``) — the same in-scan prefill piece
   the plain dispatch runs (``scheduler.chunk_prefill_substep``): slots in
   prefill phase stream ``chunk`` prompt tokens per round while the other
   slots speculate; the round a slot's last chunk lands its first token is
   emitted through column 0 of the round's grid slice (free — the slot
   was inactive during the speculative phase) and it starts speculating
   the following round.

**Dynamic speculation depth** — the dispatch takes ``depth`` (a traced
``int32`` scalar, 1..n_spec) instead of baking the round depth into the
program: the draft still runs ``n_spec`` steps and the grids stay sized
``n_spec + 1``, so moving ``depth`` between dispatches never changes the
jitted signature (zero recompiles — pinned by the staticcheck fingerprint
manifest and tests), while the acceptance rules mask positions beyond it.
:class:`DepthController` is the host-side policy: it reads the per-dispatch
``drafted`` / ``accepted`` deltas of the device counter tree
(``state["ctr"]``, repro.telemetry.counters — fetched in the same sync as
the token grid) and walks the depth up on sustained high acceptance,
halving it on misses — AIMD on the acceptance rate — so a garbage draft
stops wasting n_spec draft forwards per round without a single retrace.

Guarantee: greedy speculative output is **token-exact** against the
non-speculative paged engine (and therefore the contiguous engine and the
legacy host loop) for any draft tree and any depth trajectory whatsoever —
the draft only decides how many verifier-identical tokens emit per round,
never their values.

Budget clamp: a round may accept more tokens than the slot's remaining
budget; emission is clamped (``min(accepted + 1, remaining)``) and every
clamped-away position is provably beyond the request's final token, so the
clamp never changes emitted values.  Acceptance counters report the raw
verifier-agreement prefix (the fidelity metric) against the *depth*
actually drafted, not the clamped emission.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.engine.paged import BSTATE_KEYS, alloc_span, release_slots
from repro.engine.sampler import SamplingParams, probs, sample
from repro.engine.scheduler import chunk_prefill_substep, named_dispatch
from repro.models.lm import Model, cow_copy_blocks
from repro.telemetry.counters import bump


# ---------------------------------------------------------------------------
# Acceptance rules (pure, unit-testable)
# ---------------------------------------------------------------------------

def greedy_accept(drafts: jnp.ndarray, p_logits: jnp.ndarray, depth=None):
    """Greedy prefix acceptance.

    ``drafts`` [B, n] proposed tokens; ``p_logits`` [B, n+1, V] verifier
    logits (row ``i`` scores proposal ``i``; row ``n`` is the bonus
    position).  Returns ``(out [B, n+1], n_acc [B])``: rows ``< n_acc`` of
    ``out`` are the accepted drafts, row ``n_acc`` the verifier's own
    argmax (the correction after a mismatch, or the bonus token when all
    drafts matched); rows past that are don't-care.

    ``depth`` (traced scalar or per-slot [B]) caps the accepted prefix:
    positions at or beyond it count as mismatches, so the round behaves
    exactly like a static ``n_spec = depth`` round (the correction at
    position ``depth`` is the verifier argmax after ``depth`` accepted
    drafts — the bonus token).
    """
    B, n1 = p_logits.shape[:2]
    n = n1 - 1
    tgt = jnp.argmax(p_logits, axis=-1).astype(jnp.int32)       # [B, n+1]
    match = tgt[:, :n] == drafts
    if depth is not None:
        match = match & (jnp.arange(n)[None, :]
                         < jnp.reshape(depth, (-1, 1)))
    match = match.astype(jnp.int32)
    a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)             # [B] 0..n
    out = jnp.concatenate([drafts, jnp.zeros((B, 1), jnp.int32)], axis=1)
    fix = jnp.take_along_axis(tgt, a[:, None], axis=1)[:, 0]
    return out.at[jnp.arange(B), a].set(fix), a


def rejection_accept(key, drafts: jnp.ndarray, q_logits: jnp.ndarray,
                     p_logits: jnp.ndarray, sp: SamplingParams, depth=None):
    """Lossless speculative rejection sampling (Leviathan et al.) over the
    **warped** draft/target distributions.

    ``drafts`` [B, n] were sampled from ``probs(q_logits, sp)``; draft ``i``
    is accepted with probability ``min(1, p_i(d_i) / q_i(d_i))``, the first
    rejection is resampled from ``norm(max(p_i - q_i, 0))``, and the
    all-accepted case draws the bonus token from ``p_{n+1}`` (the same
    formula with ``q := 0``).  The emitted-token distribution equals plain
    sampling from the warped target — pinned by a frequency test.

    ``depth`` caps the proposal: positions at or beyond it are rejected
    outright AND their ``q`` is zeroed, so when the accept chain stops at
    the cutoff the resample draws from ``norm(max(p - 0, 0)) = p`` — the
    bonus formula — and the emitted distribution is identical to a static
    ``n_spec = depth`` round (losslessness is depth-independent).
    Returns ``(out [B, n+1], n_acc [B])`` like :func:`greedy_accept`.
    """
    B, n1, V = p_logits.shape
    n = n1 - 1
    qp = probs(q_logits, sp)                                    # [B, n, V]
    pp = probs(p_logits, sp)                                    # [B, n+1, V]
    pd = jnp.take_along_axis(pp[:, :n], drafts[..., None], axis=-1)[..., 0]
    qd = jnp.take_along_axis(qp, drafts[..., None], axis=-1)[..., 0]
    ku, kr = jax.random.split(key)
    u = jax.random.uniform(ku, (B, n))
    accept = u * qd < pd                        # P[accept] = min(1, p/q)
    if depth is not None:
        live = jnp.arange(n)[None, :] < jnp.reshape(depth, (-1, 1))
        accept = accept & live
        qp = qp * live[..., None].astype(qp.dtype)
    accept = accept.astype(jnp.int32)
    a = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)            # [B] 0..n
    pa = jnp.take_along_axis(pp, a[:, None, None], axis=1)[:, 0]
    q_ext = jnp.concatenate([qp, jnp.zeros((B, 1, V), qp.dtype)], axis=1)
    qa = jnp.take_along_axis(q_ext, a[:, None, None], axis=1)[:, 0]
    r = jnp.maximum(pa - qa, 0.0)
    z = jnp.sum(r, axis=-1, keepdims=True)
    r = jnp.where(z > 0, r / z, pa)             # p == q numerically: use p
    tail = jax.random.categorical(kr, jnp.log(jnp.maximum(r, 1e-38)),
                                  axis=-1).astype(jnp.int32)
    out = jnp.concatenate([drafts, jnp.zeros((B, 1), jnp.int32)], axis=1)
    return out.at[jnp.arange(B), a].set(tail), a


# ---------------------------------------------------------------------------
# Dynamic speculation depth (host-side policy, telemetry-driven)
# ---------------------------------------------------------------------------

@dataclass
class DepthController:
    """AIMD controller for the speculative draft depth.

    The engine feeds it the per-dispatch ``(drafted, accepted)`` deltas of
    the device counter tree; :meth:`update` moves ``depth`` between 1 and
    ``n_max``: additive-increase after ``patience`` consecutive dispatches
    at acceptance rate >= ``hi`` (the draft is earning its forwards —
    speculate deeper), multiplicative-decrease (halve) the moment the rate
    drops below ``lo`` (a misaligned draft burns a draft forward per
    rejected position — collapse toward plain decoding).  Rates in between
    hold depth and reset the streak.

    Depth is a *runtime operand* of the jitted dispatch (the grids stay
    sized for ``n_max``), so every move here is free: zero recompiles,
    pinned by tests and the staticcheck fingerprint manifest.
    """
    n_max: int
    lo: float = 0.45
    hi: float = 0.75
    patience: int = 2
    depth: int = 0          # 0 -> start at n_max (set in __post_init__)
    streak: int = 0

    def __post_init__(self):
        if self.n_max < 1:
            raise ValueError(f"n_max must be >= 1, got {self.n_max}")
        if not self.depth:
            self.depth = self.n_max
        self.depth = max(1, min(self.depth, self.n_max))

    def update(self, drafted: int, accepted: int) -> int:
        """Fold one dispatch's counters in; returns the depth for the next
        dispatch.  Zero-draft dispatches (all slots prefilling) are
        ignored — no evidence, no move."""
        if drafted <= 0:
            return self.depth
        rate = accepted / drafted
        if rate >= self.hi:
            self.streak += 1
            if self.streak >= self.patience:
                self.depth = min(self.n_max, self.depth + 1)
                self.streak = 0
        elif rate < self.lo:
            self.depth = max(1, self.depth // 2)
            self.streak = 0
        else:
            self.streak = 0
        return self.depth


# ---------------------------------------------------------------------------
# The K-round speculative dispatch
# ---------------------------------------------------------------------------

def make_spec_dispatch(model: Model, sp: SamplingParams, k_steps: int,
                       n_spec: int, *, cow: bool = False, chunk: int = 0):
    """Build the jitted K-round speculative dispatch.

    ``dispatch(params, draft_params, state, cache, depth, key)`` ->
    ``(state, cache, tokens [B, K*(n_spec+1)], emitted [B, K*(n_spec+1)])``
    — ``emitted[b]`` marks the tokens slot ``b`` really produced (a
    contiguous prefix per round, rounds concatenated in order, so the host
    appends ``tokens[b, emitted[b]]`` verbatim, exactly like the plain
    dispatch's grid).  The round bumps the device counter tree
    (``state["ctr"]`` — drafted/accepted/rejected, CoW copies, blocked
    retries, block pops/releases), which the host reads in the same sync;
    its per-dispatch drafted/accepted deltas are the acceptance-rate
    telemetry the :class:`DepthController` consumes.  ``depth`` is the
    dynamic speculation depth (a traced ``int32``; pass ``jnp.int32(d)``,
    a weak Python literal would retrace per value).

    ``cow=True`` composes with refcounted prefix caching: the round's span
    allocation copies-on-write a shared first block (see module
    docstring).  ``chunk > 0`` appends the in-scan chunked-prefill phase
    to every round.  The same ``state`` pytree as the plain dispatch is
    used (plus the prefill fields when chunked); blocks of slots that
    drain mid-dispatch are pushed back inside the scan, as in the
    non-speculative path.
    """
    if model.decode_step_paged is None or model.verify_chunk_paged is None:
        raise NotImplementedError(
            f"model family {model.cfg.family!r} has no paged decode/verify "
            f"path")
    if chunk and model.prefill_chunk_paged is None:
        raise NotImplementedError(
            f"model family {model.cfg.family!r} has no chunked-prefill path")
    mcfg = model.cfg
    # SSM state is recurrent and SWA rings are position-keyed: rejected
    # rows cannot be rewound by masking, so those families re-run the
    # verify with valid = accepted over the pre-round cache (pass 2)
    two_pass = mcfg.family in ("ssm", "hybrid") or bool(mcfg.sliding_window)
    S1 = n_spec + 1

    def dispatch(params, draft_params, state, cache, depth, key):
        B = state["active"].shape[0]
        depth = jnp.clip(jnp.asarray(depth, jnp.int32), 1, n_spec)

        def round_body(carry, step_key):
            st, cache = carry
            ctr = st["ctr"]
            active = st["active"]
            lengths = cache["lengths"]
            blocked = jnp.zeros((B,), bool)
            nf_r0 = cache["n_free"]      # pops this round, by free-list delta
            # ---- 1. span allocation + CoW (once per round) --------------
            leaf = next((l for l in cache["stack"].values() if "pk" in l),
                        None)
            if leaf is not None:
                bs = leaf["pk"].shape[2]
                cap = cache["tbl"].shape[1] * bs
                ring = bool(mcfg.sliding_window) and cap == mcfg.sliding_window
                bstate, cow_src, cow_dst, blocked = alloc_span(
                    {k: cache[k] for k in BSTATE_KEYS}, lengths, S1, bs,
                    cap, ring, cow=cow)
                cache = {**cache, **bstate}
                if cow:
                    cache = cow_copy_blocks(cache, cow_src, cow_dst)
                    ctr = bump(ctr, cow_copies=jnp.sum(cow_src != cow_dst))
            # a slot whose shared block could not be CoWed sits the round
            # out entirely (no draft writes, no verify, no emission) and
            # retries next round — unreachable under the reservation
            # ledger, but a draft write into a live shared block would be
            # silent corruption, so the mask is enforced regardless
            active_r = active & ~blocked
            sa = cache["slot_active"]
            # ---- 2. draft (quantized tree, working cache copy) ----------
            def draft_body(dc, dk):
                dcache, cur = dc
                logits, dcache = model.decode_step_paged(draft_params, cur,
                                                         dcache)
                nxt = sample(logits, dk, sp)
                return (dcache, nxt[:, None]), (nxt, logits)

            dkeys = jax.random.split(jax.random.fold_in(step_key, 0), n_spec)
            (dcache, _), (dtoks, dlogits) = jax.lax.scan(
                draft_body, ({**cache, "slot_active": sa & ~blocked},
                             st["cur"]), dkeys)
            drafts = dtoks.T                                    # [B, n]
            # ---- 3. verify (full-precision tree, one forward) -----------
            vtoks = jnp.concatenate([st["cur"], drafts], axis=1)
            vvalid = jnp.where(active_r, S1, 0)
            # one-pass families reuse the draft's cache (its span rows are
            # fully overlaid/overwritten by the verify); two-pass families
            # must keep the pre-round cache for the commit pass.  The
            # blocked-slot mask on slot_active is undone either way.
            vc_in = {**(cache if two_pass else dcache), "lengths": lengths,
                     "slot_active": sa}
            v_logits, vcache = model.verify_chunk_paged(
                params, vtoks, vc_in, lengths, vvalid)
            # ---- 4. accept (depth-masked) -------------------------------
            if sp.greedy:
                out, a = greedy_accept(drafts, v_logits, depth)
            else:
                out, a = rejection_accept(
                    jax.random.fold_in(step_key, 1), drafts,
                    dlogits.transpose(1, 0, 2), v_logits, sp, depth)
            m = jnp.where(active_r, jnp.minimum(a + 1, st["remaining"]), 0)
            # ---- 5. commit + rollback -----------------------------------
            new_len = jnp.where(active_r, lengths + m, lengths)
            if two_pass:
                _, ccache = model.verify_chunk_paged(
                    params, vtoks, {**cache, "lengths": lengths}, lengths,
                    m)
                cache = {**ccache, "lengths": new_len}
            else:
                cache = {**vcache, "lengths": new_len}
            # ---- 6. emit + budget ---------------------------------------
            em = active_r[:, None] & (jnp.arange(S1)[None, :] < m[:, None])
            cur = jnp.take_along_axis(out, jnp.maximum(m - 1, 0)[:, None],
                                      axis=1)
            cur = jnp.where(active_r[:, None], cur, st["cur"])
            remaining = st["remaining"] - m
            new_active = active & (remaining > 0)
            # ---- 7. recycle drained slots' blocks in-scan ---------------
            nf1 = cache["n_free"]
            bstate = release_slots({k: cache[k] for k in BSTATE_KEYS},
                                   active & ~new_active)
            cache = {**cache, **bstate}
            drafted = jnp.sum(jnp.where(active_r, depth, 0))
            accepted = jnp.sum(jnp.where(active_r, a, 0))
            ctr = bump(ctr,
                       tokens=jnp.sum(m),
                       drafted=drafted,
                       accepted=accepted,
                       rejected=drafted - accepted,
                       blocked_retries=jnp.sum(blocked),
                       blocks_popped=nf_r0 - nf1,
                       blocks_released=cache["n_free"] - nf1)
            st = {**st, "cur": cur, "active": new_active,
                  "remaining": remaining, "ctr": ctr}
            out_grid = out
            # ---- 8. chunked-prefill phase -------------------------------
            if chunk:
                st, cache, first, completed = chunk_prefill_substep(
                    model, sp, chunk, params, st, cache,
                    jax.random.fold_in(step_key, 2))
                # a slot completing prefill this round was inactive during
                # the speculative phase, so its grid row is all don't-care:
                # the first token goes in column 0
                col0 = jnp.arange(S1)[None, :] == 0
                hit = completed[:, None] & col0
                out_grid = jnp.where(hit, first[:, None], out)
                em = em | hit
            return (st, cache), (out_grid, em)

        keys = jax.random.split(key, k_steps)
        (state, cache), (toks, em) = jax.lax.scan(
            round_body, (state, cache), keys)
        toks = toks.transpose(1, 0, 2).reshape(B, k_steps * S1)
        em = em.transpose(1, 0, 2).reshape(B, k_steps * S1)
        return state, cache, toks, em

    return named_dispatch(dispatch, "spec_chunk_dispatch" if chunk
                          else "spec_dispatch")
