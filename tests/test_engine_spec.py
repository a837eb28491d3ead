"""Self-speculative decoding tests: greedy token-exactness against the
non-speculative paged engine across every family (any draft, good or
terrible), the composed-config matrix (speculation × prefix cache ×
chunked prefill, including warm partial hits that copy-on-write the
speculative span), rollback/allocator invariants under randomized stress,
the dynamic-depth controller (pinned trajectories + zero recompiles),
the acceptance rules as pure functions, sampler distribution correctness
(temperature / top-k / top-p frequency + lossless rejection-sampling
unbiasedness), config validation, and the quantized-head matmul."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import QuantConfig, get_arch, reduced
from repro.data import LanguageSpec, sample_batch
from repro.engine import (DepthController, Engine, SamplingParams,
                          alloc_span, blocks_for, greedy_accept,
                          init_block_state, probs, rejection_accept, sample)
from repro.models import build_model
from repro.quantize import quantize

KEY = jax.random.PRNGKey(0)

_BUILT: dict = {}


def _setup(arch="glm4-9b"):
    """Model + params (+ a quantized absmax draft tree and a wrong-seed
    'bad' draft), cached per arch so jit caches stay warm."""
    key = arch
    if key not in _BUILT:
        cfg = reduced(get_arch(arch))
        model = build_model(cfg)
        params = model.init(KEY)
        draft, _ = quantize(params, None,
                            QuantConfig(method="absmax",
                                        granularity="channel"),
                            mode="storage", out_dtype="bfloat16")
        bad = model.init(jax.random.PRNGKey(99))
        _BUILT[key] = (cfg, model, params, draft, bad,
                       LanguageSpec(vocab=cfg.vocab_size))
    return _BUILT[key]


def _prompts(spec, lens, seed=0):
    return [sample_batch(jax.random.PRNGKey(seed * 1000 + i), spec, 1, L)[0]
            for i, L in enumerate(lens)]


def _shared_prompts(spec, lens, share, seed=0, dup=True):
    """Prompts sharing a ``share``-token system prefix; with ``dup`` the
    first prompt is appended again verbatim, so serving it a second time
    lands a warm *partial* hit (the whole prompt, final part-block
    included, is already cached — the spot where a speculative span's
    first entry is a shared block and must copy-on-write)."""
    pre = sample_batch(jax.random.PRNGKey(7 + seed), spec, 1, share)[0][:share]
    tails = _prompts(spec, [L - share for L in lens], seed=seed)
    out = [jnp.concatenate([pre, t]) for t in tails]
    return out + [out[0]] if dup else out


# ---------------------------------------------------------------------------
# Greedy token-exactness: spec == non-spec paged engine, every family
# ---------------------------------------------------------------------------

def test_spec_token_exact_matrix():
    """Speculative greedy output must equal the non-speculative paged
    engine token for token on dense, SWA-ring+MoE, MoE, pure-SSM and
    hybrid configs (MoE dropless on every path).  The draft is a real
    absmax-quantized tree, so rounds mix accepts and rejections; the
    acceptance rate must be meaningful (> 0) for a draft this close."""
    cases = [
        ("glm4-9b", [10, 25, 6, 17], 40),
        ("mixtral-8x22b", [9, 21, 9, 14], 34),   # SWA ring + MoE
        ("deepseek-v3", [9, 21, 14], 34),        # MoE, prefix stack
        ("mamba2-780m", [9, 40, 12], 48),        # pure SSM
        ("jamba-v0.1-52b", [9, 40, 12], 48),     # hybrid
    ]
    for arch, lens, cache_len in cases:
        cfg, model, params, draft, _, spec = _setup(arch)
        prompts = _prompts(spec, lens)
        base = Engine(model, params, slots=2, cache_len=cache_len,
                      k_steps=3, paged=True, block_size=8
                      ).serve(prompts, gen_tokens=5)
        seng = Engine(model, params, slots=2, cache_len=cache_len,
                      k_steps=3, paged=True, block_size=8, n_spec=2,
                      draft_params=draft, check_invariants=True)
        outs, stats = seng.serve(prompts, gen_tokens=5, return_stats=True)
        assert outs == base, arch
        assert stats["draft_tokens"] > 0
        assert 0 < stats["draft_accepted"] <= stats["draft_tokens"], arch


def test_spec_composed_token_exact_matrix():
    """The full composition — speculation × prefix cache × chunked
    prefill — must equal the *non-speculative* paged+prefix engine token
    for token on every family.  Prompts share a system prefix and one
    prompt repeats verbatim, so the dense/MoE runs land full-block hits,
    a warm partial hit (copy-on-write of the speculative span's first
    entry), and admissions that start chunking while resident slots are
    mid-speculation.  Ring (SWA) and recurrent (SSM/hybrid) families run
    the same composition unshared — exactness must hold with zero hits
    too."""
    cases = [
        # arch, chunk, lens (> share), cache_len, hits expected
        ("glm4-9b", 8, [18, 25, 18, 21], 40, True),
        ("mixtral-8x22b", 8, [18, 21, 18], 34, False),   # SWA ring
        ("deepseek-v3", 8, [18, 21, 18], 34, True),      # MoE
        ("mamba2-780m", 32, [18, 40, 18], 48, False),    # pure SSM
        ("jamba-v0.1-52b", 32, [18, 40, 18], 48, False),  # hybrid
    ]
    for arch, chunk, lens, cache_len, can_hit in cases:
        cfg, model, params, draft, _, spec = _setup(arch)
        prompts = _shared_prompts(spec, lens, share=16)
        base = Engine(model, params, slots=2, cache_len=cache_len,
                      k_steps=3, paged=True, block_size=8, chunk_size=chunk,
                      prefix_cache=True).serve(prompts, gen_tokens=5)
        seng = Engine(model, params, slots=2, cache_len=cache_len,
                      k_steps=3, paged=True, block_size=8, chunk_size=chunk,
                      prefix_cache=True, n_spec=2, draft_params=draft,
                      check_invariants=True)
        outs, stats = seng.serve(prompts, gen_tokens=5, return_stats=True)
        assert outs == base, arch
        assert 0 < stats["draft_accepted"] <= stats["draft_tokens"], arch
        if can_hit:  # shared prefix + duplicated prompt must actually hit
            assert stats["prefix_hits"] > 0, arch
        else:        # ring / recurrent caches never share
            assert stats["prefix_hits"] == 0, arch


def test_spec_composed_warm_prefix_hit_mid_speculation():
    """Serving the same requests twice on one composed engine: the second
    pass is fully warm — every admission is a prefix hit landing while a
    resident slot is mid-speculation, and the duplicated prompt's partial
    hit forces the speculative span's first entry through copy-on-write.
    Both passes must match the non-speculative prefix engine served
    identically."""
    cfg, model, params, draft, _, spec = _setup()
    prompts = _shared_prompts(spec, [18, 25, 21], share=16)
    base = Engine(model, params, slots=2, cache_len=40, k_steps=3,
                  paged=True, block_size=8, chunk_size=8, prefix_cache=True)
    b1 = base.serve(prompts, gen_tokens=5)
    b2 = base.serve(prompts, gen_tokens=5)
    seng = Engine(model, params, slots=2, cache_len=40, k_steps=3,
                  paged=True, block_size=8, chunk_size=8, prefix_cache=True,
                  n_spec=2, draft_params=draft, check_invariants=True)
    o1, s1 = seng.serve(prompts, gen_tokens=5, return_stats=True)
    o2, s2 = seng.serve(prompts, gen_tokens=5, return_stats=True)
    assert o1 == b1 and o2 == b2
    assert s2["prefix_hits"] > s1["prefix_hits"]   # warm second pass
    assert s2["draft_accepted"] > 0


def test_spec_composed_exact_for_garbage_draft():
    """A wrong-seed draft (≈0% acceptance, a rollback every round) through
    the full composition: every rollback rolls a length back *into* CoW'd
    and freshly-popped span blocks, and the output must still equal the
    non-speculative prefix engine exactly."""
    cfg, model, params, _, bad, spec = _setup()
    prompts = _shared_prompts(spec, [18, 21, 18], share=16)
    base = Engine(model, params, slots=2, cache_len=40, k_steps=4,
                  paged=True, block_size=8, chunk_size=8, prefix_cache=True
                  ).serve(prompts, gen_tokens=6)
    outs, stats = Engine(model, params, slots=2, cache_len=40, k_steps=4,
                         paged=True, block_size=8, chunk_size=8,
                         prefix_cache=True, n_spec=2, draft_params=bad,
                         check_invariants=True
                         ).serve(prompts, gen_tokens=6, return_stats=True)
    assert outs == base
    assert stats["prefix_hits"] > 0
    assert stats["draft_accepted"] < stats["draft_tokens"] // 4


def test_spec_exact_for_any_draft_even_garbage():
    """The lossless guarantee is structural: a draft from a completely
    different seed (≈0% acceptance → a rollback every round) must still
    reproduce the non-speculative greedy output exactly — the draft only
    chooses how many verifier-identical tokens emit per round."""
    cfg, model, params, _, bad, spec = _setup()
    prompts = _prompts(spec, [10, 13, 6, 9])
    base = Engine(model, params, slots=2, cache_len=32, k_steps=4,
                  paged=True, block_size=8).serve(prompts, gen_tokens=6)
    outs, stats = Engine(model, params, slots=2, cache_len=32, k_steps=4,
                         paged=True, block_size=8, n_spec=2,
                         draft_params=bad, check_invariants=True
                         ).serve(prompts, gen_tokens=6, return_stats=True)
    assert outs == base
    # wrong-seed drafts agree with the verifier about nothing
    assert stats["draft_accepted"] < stats["draft_tokens"] // 4


def test_spec_budget_clamp_edges():
    """A round can accept past the remaining budget; emission is clamped
    without changing values.  gen=1 never decodes, gen=2 clamps the very
    first round (n_spec=3 > remaining=1)."""
    cfg, model, params, draft, _, spec = _setup()
    prompts = _prompts(spec, [10, 13, 6, 9])
    for gen in (1, 2, 4):
        base = Engine(model, params, slots=2, cache_len=32, k_steps=5,
                      paged=True, block_size=8).serve(prompts,
                                                      gen_tokens=gen)
        outs = Engine(model, params, slots=2, cache_len=32, k_steps=5,
                      paged=True, block_size=8, n_spec=3,
                      draft_params=draft, check_invariants=True
                      ).serve(prompts, gen_tokens=gen)
        assert outs == base, gen
        assert [len(o) for o in outs] == [gen] * len(prompts)


def test_spec_tight_pool_with_reservation_slack():
    """The reservation ledger counts the speculative span (up to n_spec
    rows past the budget) into each slot's worst case: a pool sized to
    exactly that bound serializes but stays exact and never starves."""
    cfg, model, params, draft, _, spec = _setup()
    prompts = _prompts(spec, [20, 20, 20, 20])
    base = Engine(model, params, slots=2, cache_len=32, k_steps=4,
                  paged=True, block_size=8).serve(prompts, gen_tokens=5)
    need = blocks_for(20 + 5 - 1 + 2, 8)          # lifetime + n_spec slack
    tight = Engine(model, params, slots=2, cache_len=32, k_steps=4,
                   paged=True, block_size=8, num_blocks=need, n_spec=2,
                   draft_params=draft, check_invariants=True)
    outs, stats = tight.serve(prompts, gen_tokens=5, return_stats=True)
    assert outs == base
    assert stats["prefill_calls"] == 4            # one slot at a time fits


def test_spec_sampled_mode_deterministic_and_complete():
    """Sampled speculative serving is not token-exact vs non-speculative
    sampling (different PRNG consumption) but must be deterministic under
    a fixed seed and deliver full budgets of in-vocab tokens."""
    cfg, model, params, draft, _, spec = _setup()
    prompts = _prompts(spec, [10, 13, 6])
    sp = SamplingParams(greedy=False, temperature=0.9, top_k=40, top_p=0.9)
    eng = Engine(model, params, slots=2, cache_len=32, k_steps=4,
                 paged=True, block_size=8, n_spec=2, draft_params=draft,
                 sampling=sp, check_invariants=True)
    o1 = eng.serve(prompts, gen_tokens=6, seed=7)
    o2 = eng.serve(prompts, gen_tokens=6, seed=7)
    assert o1 == o2
    assert [len(o) for o in o1] == [6, 6, 6]
    assert all(0 <= t < cfg.vocab_size for o in o1 for t in o)


# ---------------------------------------------------------------------------
# Randomized stress: mixed accept/reject rollbacks + allocator invariants
# ---------------------------------------------------------------------------

@settings(max_examples=3, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_spec_stress_randomized(seed):
    """Adversarial sweep: random prompt lengths / request counts / budgets
    / draft depth / pool tightness, alternating a close (quantized) and a
    hostile (wrong-seed) draft — so rounds mix full accepts, partial
    rollbacks and full rejections while slots churn and blocks recycle.
    Output must equal the non-speculative paged engine token for token,
    with allocator conservation asserted after every dispatch
    (check_invariants)."""
    rng = np.random.RandomState(seed)
    cfg, model, params, draft, bad, spec = _setup()
    slots = 2
    n_req = int(rng.randint(slots, slots + 4))
    lens = [int(rng.randint(4, 29)) for _ in range(n_req)]
    gen = int(rng.randint(2, 7))
    k_steps = int(rng.randint(2, 4))
    n_spec = int(rng.randint(1, k_steps))          # < k_steps
    cache_len = max(lens) + gen + int(rng.randint(0, 6))
    dtree = draft if seed % 2 == 0 else bad
    prompts = _prompts(spec, lens, seed=seed % 997)
    lens = [int(p.shape[0]) for p in prompts]   # sample_batch adds a token

    base = Engine(model, params, slots=slots, cache_len=cache_len,
                  k_steps=k_steps, paged=True, block_size=8
                  ).serve(prompts, gen_tokens=gen)
    full = slots * blocks_for(cache_len, 8)
    lo = max(blocks_for(L + gen - 1 + n_spec, 8) for L in lens)
    num_blocks = int(rng.randint(lo, full + 1))    # sometimes starved pool
    outs, stats = Engine(model, params, slots=slots, cache_len=cache_len,
                         k_steps=k_steps, paged=True, block_size=8,
                         num_blocks=num_blocks, n_spec=n_spec,
                         draft_params=dtree, check_invariants=True
                         ).serve(prompts, gen_tokens=gen, return_stats=True)
    assert outs == base
    # device-counter conservation over the whole randomized run
    c = stats["counters"]
    assert c["drafted"] == c["accepted"] + c["rejected"]
    assert c["blocks_popped"] == c["blocks_released"]  # fully drained
    assert c["drafted"] == stats["draft_tokens"]
    assert c["accepted"] == stats["draft_accepted"]


@settings(max_examples=3, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_spec_composed_stress_randomized(seed):
    """The composed sweep: random shared-prefix workloads (one prompt
    duplicated, so warm partial hits copy-on-write the speculative span)
    through speculation × prefix cache × chunked prefill, with the pool
    randomly squeezed down to *exactly* the reservation bound — lifetime
    blocks + n_spec slack + the one-CoW spare.  Refcount conservation
    (``n_free + |ref>0| == num_blocks``) is asserted after every dispatch
    (check_invariants), i.e. after every speculative rollback and CoW pop;
    output must equal the non-speculative prefix engine token for token."""
    rng = np.random.RandomState(seed)
    cfg, model, params, draft, bad, spec = _setup()
    slots = 2
    n_req = int(rng.randint(slots, slots + 3))
    share = 8 * int(rng.randint(0, 3))             # 0 / 8 / 16 shared rows
    lens = [int(rng.randint(share + 2, 29)) for _ in range(n_req)]
    gen = int(rng.randint(2, 7))
    k_steps = int(rng.randint(2, 4))
    n_spec = int(rng.randint(1, k_steps))          # < k_steps
    chunk = 8 * int(rng.randint(1, 3))
    cache_len = max(lens) + gen + int(rng.randint(0, 6))
    dtree = draft if seed % 2 == 0 else bad
    prompts = _shared_prompts(spec, lens, share, seed=seed % 997)
    lens = [int(p.shape[0]) for p in prompts]   # sample_batch adds a token

    base = Engine(model, params, slots=slots, cache_len=cache_len,
                  k_steps=k_steps, paged=True, block_size=8,
                  chunk_size=chunk, prefix_cache=True
                  ).serve(prompts, gen_tokens=gen)
    mb = blocks_for(cache_len, 8)
    lo = max(min(blocks_for(L + gen - 1 + n_spec, 8), mb)
             for L in lens) + 1                    # + the CoW spare
    num_blocks = int(rng.randint(lo, slots * mb + 1))
    eng = Engine(model, params, slots=slots, cache_len=cache_len,
                 k_steps=k_steps, paged=True, block_size=8,
                 chunk_size=chunk, prefix_cache=True,
                 num_blocks=num_blocks, n_spec=n_spec, draft_params=dtree,
                 check_invariants=True)
    outs, stats = eng.serve(prompts, gen_tokens=gen, return_stats=True)
    assert outs == base
    # device-counter conservation: drafts balance, and after the drain the
    # only blocks still out of the pool are the prefix index's holds
    c = stats["counters"]
    assert c["drafted"] == c["accepted"] + c["rejected"]
    assert (c["blocks_popped"] - c["blocks_released"]
            == len(eng._hold_blocks))
    assert c["prefix_hit_tokens"] == stats["prefix_hits"]


# ---------------------------------------------------------------------------
# Dynamic draft depth: controller trajectories + zero recompiles
# ---------------------------------------------------------------------------

def test_depth_controller_pinned_trajectories():
    """AIMD depth moves on synthetic acceptance traces, pinned exactly."""
    # sustained hits at the ceiling stay at the ceiling
    c = DepthController(4)
    assert c.depth == 4
    assert [c.update(8, 8) for _ in range(5)] == [4] * 5
    # sustained misses halve to 1 and stay: 4 -> 2 -> 1 -> 1
    c = DepthController(4)
    assert [c.update(8, 0) for _ in range(4)] == [2, 1, 1, 1]
    # climb from 1: one step up per `patience` consecutive hits
    c = DepthController(4, depth=1)
    assert [c.update(4, 4) for _ in range(8)] == [1, 2, 2, 3, 3, 4, 4, 4]
    # alternating hit/miss decays to 1 and is stable there
    c = DepthController(4)
    trace = [c.update(4, 4 if i % 2 == 0 else 0) for i in range(8)]
    assert trace == [4, 2, 2, 1, 1, 1, 1, 1]
    # mid-band rates hold depth and reset the hit streak
    c = DepthController(4, depth=2)
    assert [c.update(10, r) for r in (10, 6, 10, 6)] == [2, 2, 2, 2]
    # zero-draft dispatches (all slots prefilling) are no evidence: depth
    # *and* streak survive them
    c = DepthController(4, depth=1)
    assert c.update(4, 4) == 1
    assert c.update(0, 0) == 1
    assert c.update(4, 4) == 2      # streak was preserved across the gap


def test_depth_controller_validation_and_clamps():
    with pytest.raises(ValueError, match="n_max"):
        DepthController(0)
    assert DepthController(2, depth=5).depth == 2    # clamped into 1..n_max
    assert DepthController(3).depth == 3             # depth=0 -> start at max
    # static mode (thresholds outside [0,1]) never moves
    c = DepthController(4, lo=-1.0, hi=2.0)
    assert [c.update(4, a) for a in (4, 0, 4, 0)] == [4, 4, 4, 4]


def test_spec_depth_swing_zero_recompiles():
    """Depth is a runtime operand of the jitted dispatch: a garbage draft
    collapses the controller from n_spec to 1 *within* a serve, and a
    second (warm-prefix) serve swings it again from the top — the jit
    cache must hold exactly one traced signature per speculative entry
    point throughout (no shape drift, no weak-type literals)."""
    cfg, model, params, draft, bad, spec = _setup()
    prompts = _shared_prompts(spec, [18, 21, 18], share=16)
    eng = Engine(model, params, slots=2, cache_len=40, k_steps=4,
                 paged=True, block_size=8, chunk_size=8, prefix_cache=True,
                 n_spec=3, draft_params=bad, check_invariants=True)
    _, stats = eng.serve(prompts, gen_tokens=6, return_stats=True)
    assert stats["spec_depth"] == 1        # ~0% acceptance collapsed it
    counts = eng.compile_counts()
    spec_entries = [n for n in counts if "spec" in n]
    assert spec_entries
    if all(v >= 0 for v in counts.values()):   # cache-size probe available
        assert all(counts[n] <= 1 for n in spec_entries), counts
        eng.serve(prompts, gen_tokens=6)       # warm pass, fresh swing
        assert eng.compile_counts() == counts  # flat: zero recompiles


# ---------------------------------------------------------------------------
# alloc_span copy-on-write (the composed allocator primitive, in isolation)
# ---------------------------------------------------------------------------

def _shared_block_state():
    """Slot 0 holds block 0 — a partially-filled prompt block also
    referenced by the prefix index (ref 2); slot 1 is inactive.  Blocks
    1..3 are free."""
    b = init_block_state(2, 4, 4)
    return {**b,
            "tbl": b["tbl"].at[0, 0].set(0),
            "ref": b["ref"].at[0].set(2),
            "free": jnp.asarray([1, 2, 3, 0], jnp.int32),
            "n_free": jnp.int32(3),
            "slot_active": jnp.asarray([True, False])}


def test_alloc_span_cow_pops_rewires_and_reports():
    """A shared first span entry gets a private block popped, the table
    rewired, one reference dropped on the source, and the (src, dst) pair
    reported for the row copy; the inactive slot reports the no-copy
    sentinel (src == dst) and conservation holds."""
    b = _shared_block_state()
    out, src, dst, blocked = alloc_span(
        b, jnp.asarray([4, 0], jnp.int32), 2, 8, 32, False, cow=True)
    new = int(out["tbl"][0, 0])
    assert new != 0 and int(out["ref"][new]) == 1
    assert int(out["ref"][0]) == 1            # index still holds the source
    assert int(out["n_free"]) == 2
    assert (int(src[0]), int(dst[0])) == (0, new)
    assert int(src[1]) == int(dst[1])         # slot 1: nothing to copy
    assert not bool(blocked[0]) and not bool(blocked[1])
    assert int(out["n_free"]) + int(jnp.sum(out["ref"] > 0)) == 4


def test_alloc_span_cow_skips_private_blocks():
    """ref == 1 (a block this slot owns outright) is not shared: no pop,
    no copy pair, the table entry stays."""
    b = _shared_block_state()
    b = {**b, "ref": b["ref"].at[0].set(1)}
    out, src, dst, blocked = alloc_span(
        b, jnp.asarray([4, 0], jnp.int32), 2, 8, 32, False, cow=True)
    assert int(out["tbl"][0, 0]) == 0
    assert int(out["n_free"]) == 3
    assert int(src[0]) == int(dst[0])
    assert not bool(blocked[0])


def test_alloc_span_cow_spanning_into_fresh_block():
    """A span crossing from the shared block into unallocated territory
    pops two blocks in one call — a CoW replacement for entry 0 and a
    plain allocation for entry 1 — and decrements only the shared
    source."""
    b = _shared_block_state()
    out, src, dst, blocked = alloc_span(
        b, jnp.asarray([6, 0], jnp.int32), 4, 8, 32, False, cow=True)
    e0, e1 = int(out["tbl"][0, 0]), int(out["tbl"][0, 1])
    assert e0 != 0 and e1 >= 0 and e1 != e0
    assert int(out["ref"][0]) == 1 and int(out["ref"][e0]) == 1
    assert int(out["ref"][e1]) == 1
    assert int(out["n_free"]) == 1
    assert (int(src[0]), int(dst[0])) == (0, e0)
    assert int(out["n_free"]) + int(jnp.sum(out["ref"] > 0)) == 4


def test_alloc_span_cow_dry_pool_blocks_the_slot():
    """With the free stack empty a shared first entry cannot CoW: the
    slot is reported blocked, and *nothing* moves — table, refs and the
    stack are untouched, so the round can mask the slot out and retry."""
    b = _shared_block_state()
    b = {**b, "n_free": jnp.int32(0)}
    out, src, dst, blocked = alloc_span(
        b, jnp.asarray([4, 0], jnp.int32), 2, 8, 32, False, cow=True)
    assert bool(blocked[0]) and not bool(blocked[1])
    assert int(out["tbl"][0, 0]) == 0
    assert int(out["ref"][0]) == 2
    assert int(out["n_free"]) == 0
    assert int(src[0]) == int(dst[0])         # no copy while blocked


def test_alloc_span_ring_is_a_no_op():
    """Ring (SWA) tables are fully allocated at admission and never
    shared: the ring case pops nothing and reports no-copy sentinels."""
    b = _shared_block_state()
    out, src, dst, blocked = alloc_span(
        b, jnp.asarray([4, 0], jnp.int32), 2, 8, 32, True, cow=True)
    assert int(out["n_free"]) == 3
    assert np.asarray(src == dst).all()
    assert not np.asarray(blocked).any()


# ---------------------------------------------------------------------------
# Acceptance rules as pure functions
# ---------------------------------------------------------------------------

def test_greedy_accept_prefix_and_correction():
    p_logits = jnp.asarray([
        # verifier argmaxes: [2, 0, 3] — drafts [2, 0, 1]: accept 2, fix 3
        [[0, 1, 9, 2], [9, 1, 0, 2], [0, 1, 2, 9]],
        # verifier argmaxes: [1, 3, 0] — drafts [2, 3, 0]: reject at 0
        [[0, 9, 1, 2], [0, 1, 2, 9], [9, 1, 2, 0]],
    ], jnp.float32)
    drafts = jnp.asarray([[2, 0, 1], [2, 3, 0]], jnp.int32)
    out, a = greedy_accept(drafts[:, :2], p_logits)
    np.testing.assert_array_equal(np.asarray(a), [2, 0])
    assert out[0, 0] == 2 and out[0, 1] == 0 and out[0, 2] == 3  # bonus row
    assert out[1, 0] == 1                                       # correction


def test_rejection_accept_identical_draft_always_accepts():
    """q == p accepts every draft (the ratio test is >= 1) and the bonus
    comes from p_{n+1}."""
    V = 8
    k1, k2 = jax.random.split(KEY)
    p = jax.random.normal(k1, (4, 3, V))
    drafts = jax.random.categorical(k2, p[:, :2], axis=-1).astype(jnp.int32)
    sp = SamplingParams(greedy=False, temperature=0.8)
    out, a = rejection_accept(jax.random.PRNGKey(5), drafts, p[:, :2], p, sp)
    np.testing.assert_array_equal(np.asarray(a), [2, 2, 2, 2])
    np.testing.assert_array_equal(np.asarray(out[:, :2]), np.asarray(drafts))


def test_rejection_sampling_unbiased_on_toy_vocab():
    """The emitted first token of a speculative round must be distributed
    exactly as plain sampling from the warped target — for a draft
    distribution that disagrees with the target.  Empirical frequencies
    over a fixed-seed batch of rounds vs the exact warped target probs."""
    V, N = 6, 8000
    p_logits = jnp.asarray([[0.5, -0.2, 1.1, 0.0, -1.0, 0.4],
                            [1.0, 0.0, 0.0, -0.5, 0.3, -0.2],
                            [0.0, 0.2, -0.3, 0.8, 0.1, -0.9]], jnp.float32)
    q_logits = jnp.asarray([[1.2, 0.1, -0.5, 0.3, 0.0, -0.2],
                            [-0.3, 0.9, 0.2, 0.0, -1.0, 0.5]], jnp.float32)
    sp = SamplingParams(greedy=False, temperature=0.9, top_k=5, top_p=0.95)

    def one_round(key):
        kd, ka = jax.random.split(key)
        drafts = sample(q_logits, kd, sp)[None]            # [1, 2] from q
        out, _ = rejection_accept(ka, drafts, q_logits[None],
                                  p_logits[None], sp)
        return out[0, 0]                                   # first emitted

    toks = jax.vmap(one_round)(jax.random.split(jax.random.PRNGKey(42), N))
    freq = np.bincount(np.asarray(toks), minlength=V) / N
    want = np.asarray(probs(p_logits[0], sp))
    np.testing.assert_allclose(freq, want, atol=0.02)
    # and tokens cut by the warp never appear
    assert np.all(freq[want == 0] == 0)


# ---------------------------------------------------------------------------
# Sampler distribution correctness (temperature / top-k / top-p)
# ---------------------------------------------------------------------------

def test_sampler_frequency_matches_warped_distribution():
    """Fixed-seed frequency test: empirical sampling frequencies track the
    warped (top-k -> temperature -> top-p) distribution, and masked tokens
    have exactly zero mass."""
    V, N = 8, 8000
    logits = jnp.asarray([2.0, 1.5, 1.2, 0.8, 0.2, -0.5, -1.0, -3.0])
    cases = [
        SamplingParams(greedy=False, temperature=0.7),
        SamplingParams(greedy=False, temperature=1.3, top_k=4),
        SamplingParams(greedy=False, temperature=1.0, top_p=0.6),
        SamplingParams(greedy=False, temperature=0.8, top_k=5, top_p=0.8),
    ]
    for sp in cases:
        keys = jax.random.split(jax.random.PRNGKey(123), N)
        toks = jax.vmap(lambda k: sample(logits, k, sp))(keys)
        freq = np.bincount(np.asarray(toks), minlength=V) / N
        want = np.asarray(probs(logits, sp))
        np.testing.assert_allclose(freq, want, atol=0.02, err_msg=repr(sp))
        assert np.all(freq[want == 0] == 0), repr(sp)


def test_top_p_nucleus_boundary():
    """top_p keeps the smallest prefix of the sorted distribution whose
    mass reaches p — the top token always survives, even when its own
    probability exceeds p."""
    logits = jnp.log(jnp.asarray([0.5, 0.3, 0.15, 0.05]))
    p_small = probs(logits, SamplingParams(greedy=False, top_p=0.4))
    np.testing.assert_allclose(np.asarray(p_small), [1.0, 0, 0, 0],
                               atol=1e-6)
    p_mid = probs(logits, SamplingParams(greedy=False, top_p=0.6))
    assert np.asarray(p_mid)[2] == 0 and np.asarray(p_mid)[3] == 0
    np.testing.assert_allclose(np.asarray(p_mid)[:2], [0.625, 0.375],
                               atol=1e-4)
    # top_p=1 is bitwise the old sampler (no truncation)
    p_all = probs(logits, SamplingParams(greedy=False))
    assert np.all(np.asarray(p_all) > 0)


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(greedy=False, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(greedy=False, top_p=1.5)


# ---------------------------------------------------------------------------
# Config validation (early, friendly)
# ---------------------------------------------------------------------------

def test_spec_config_validation():
    cfg, model, params, draft, _, spec = _setup()
    with pytest.raises(ValueError, match="paged"):
        Engine(model, params, slots=2, cache_len=32, n_spec=2,
               draft_params=draft)
    with pytest.raises(ValueError, match="n_spec must be < k_steps"):
        Engine(model, params, slots=2, cache_len=32, paged=True,
               block_size=8, k_steps=2, n_spec=2, draft_params=draft)
    with pytest.raises(ValueError, match="draft_params"):
        Engine(model, params, slots=2, cache_len=32, paged=True,
               block_size=8, n_spec=2)
    with pytest.raises(ValueError, match="draft_params without n_spec"):
        Engine(model, params, slots=2, cache_len=32, paged=True,
               block_size=8, draft_params=draft)


def test_spec_composes_with_prefix_and_chunking():
    """The former restriction is gone: n_spec composed with prefix_cache
    *and* chunk_size constructs, serves, and matches the non-speculative
    prefix engine — at the deepest draft (n_spec = k_steps - 1)."""
    cfg, model, params, draft, _, spec = _setup()
    prompts = _prompts(spec, [10, 13])
    base = Engine(model, params, slots=2, cache_len=48, k_steps=5,
                  paged=True, block_size=8, chunk_size=8, prefix_cache=True
                  ).serve(prompts, gen_tokens=4)
    eng = Engine(model, params, slots=2, cache_len=48, k_steps=5,
                 paged=True, block_size=8, chunk_size=8, prefix_cache=True,
                 n_spec=4, draft_params=draft, check_invariants=True)
    assert eng.serve(prompts, gen_tokens=4) == base


def test_swa_block_size_validation_is_early():
    """block_size not dividing the SWA window fails at Engine construction
    with a friendly message, not as a deep shape error at first serve."""
    cfg, model, params, _, _, spec = _setup("mixtral-8x22b")
    with pytest.raises(ValueError, match="must divide the sliding window"):
        Engine(model, params, slots=2, cache_len=34, paged=True,
               block_size=6)


# ---------------------------------------------------------------------------
# Quantized-head matmul (the draft's per-step hot op)
# ---------------------------------------------------------------------------

def test_matmul_t_matches_dequantized_head():
    """matmul_t (x @ w.T with the scales hoisted around the transpose)
    matches the dequantize-then-transpose reference for tensor/channel
    granularities, eq_scale epilogue included; block granularity falls
    back to the exact dequantize path."""
    from repro.core.formats import get_format
    from repro.core.granularity import absmax_scale, quantize_store
    from repro.quant_runtime import qlinear
    from repro.quant_runtime.qparams import QuantizedTensor

    fmt = get_format("fp8_e4m3")
    table = jax.random.normal(KEY, (40, 24), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 24), jnp.bfloat16)
    for gran, bs in (("tensor", 128), ("channel", 128), ("block", 16)):
        scale = absmax_scale(table, gran, fmt, bs)
        q = quantize_store(table, scale, gran, fmt, bs)
        for eq in (None, jnp.abs(jax.random.normal(
                jax.random.PRNGKey(1), (40,))) + 0.5):
            qt = QuantizedTensor(q, scale, fmt="fp8_e4m3", granularity=gran,
                                 block_size=bs, out_dtype="bfloat16",
                                 eq_scale=eq)
            got = qlinear.matmul_t(x, qt)
            want = jnp.matmul(x, qt.dequantize().T.astype(x.dtype))
            assert got.shape == want.shape == (2, 3, 40)
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                rtol=0.05, atol=0.05,
                err_msg=f"{gran} eq={eq is not None}")
    # dense tables: bitwise the old resolve-transpose path
    got = qlinear.matmul_t(x, table)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.matmul(x, table.T.astype(x.dtype))))
