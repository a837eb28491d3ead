"""Prefix-cache + chunked-prefill tests: refcounted-allocator invariants
under adversarial workloads, copy-on-write unit semantics, LRU eviction
instead of admission deadlock, and warm-vs-cold token exactness.

The allocator invariants (asserted by ``Engine(check_invariants=True)``
after *every* admission and dispatch, on the device truth):

* no block leaked, no double-free: the free stack and the referenced
  blocks partition the pool (``n_free + |{ref > 0}| == num_blocks``);
* every block's refcount equals its live table references plus the host
  index/pending hold — so a dangling reference, a missed decrement or a
  double release trips immediately;
* no slot ever writes a block with ``refcount > 1``: prefill-chunk writes
  below the prefix-hit watermark are dropped (``span_targets``) and decode
  writes into shared blocks pop a private copy first (``alloc_step`` CoW)
  — pinned here both as unit tests and as warm-output bit-exactness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_arch, reduced
from repro.data import LanguageSpec, sample_batch
from repro.engine import (Engine, PrefixIndex, admit_slot, alloc_step,
                          blocks_for, chain_hashes, init_block_state,
                          release_refs, release_slots, span_targets)
from repro.engine.paged import NEG
from repro.models import build_model

KEY = jax.random.PRNGKey(0)

_BUILT: dict = {}


def _setup(arch="glm4-9b"):
    if arch not in _BUILT:
        cfg = reduced(get_arch(arch))
        model = build_model(cfg)
        params = model.init(KEY)
        _BUILT[arch] = (cfg, model, params,
                        LanguageSpec(vocab=cfg.vocab_size))
    return _BUILT[arch]


def _tokens(spec, L, seed=0):
    return sample_batch(jax.random.PRNGKey(seed), spec, 1, L)[0]


# ---------------------------------------------------------------------------
# Allocator unit invariants: refcounts, sharing, CoW, host holds
# ---------------------------------------------------------------------------

def _conserved(bs, NB):
    n_free = int(bs["n_free"])
    free = [int(b) for b in np.asarray(bs["free"])[:n_free]]
    ref = np.asarray(bs["ref"])
    held = {b for b in range(NB) if ref[b] > 0}
    assert len(set(free)) == n_free
    assert not (set(free) & held)
    assert n_free + len(held) == NB
    return ref


def test_admit_slot_shared_and_retained_refs():
    """Prefix-hit admission: shared blocks gain a reference without
    consuming pool capacity; popped blocks start at ref 1, pre-retained
    (to-be-registered) ones at ref 2."""
    B, MB, NB = 2, 4, 10
    bs = init_block_state(B, MB, NB)
    # slot 0 allocates 3 blocks the classic way (simulating a past prompt)
    bs, ids0 = admit_slot(bs, jnp.int32(0), jnp.full((MB,), NEG, jnp.int32),
                          jnp.int32(0), jnp.int32(3), jnp.int32(2), MB)
    ids0 = [int(i) for i in np.asarray(ids0)[:3]]
    ref = _conserved(bs, NB)
    assert [ref[b] for b in ids0] == [2, 2, 1]    # 2 retained + 1 private
    # slot 1 admits sharing slot 0's two retained blocks + 1 fresh block
    shared = np.full((MB,), NEG, np.int32)
    shared[:2] = ids0[:2]
    bs, ids1 = admit_slot(bs, jnp.int32(1), jnp.asarray(shared),
                          jnp.int32(2), jnp.int32(1), jnp.int32(0), MB)
    ref = _conserved(bs, NB)
    assert [ref[b] for b in ids0[:2]] == [3, 3]   # +1 table ref each
    assert int(bs["n_free"]) == NB - 4            # sharing costs nothing
    tbl = np.asarray(bs["tbl"])
    assert tbl[1, 0] == ids0[0] and tbl[1, 1] == ids0[1]

    # releasing slot 1 only decrements; the shared blocks survive
    bs2 = release_slots(bs, jnp.asarray([False, True]))
    ref = _conserved(bs2, NB)
    assert [ref[b] for b in ids0[:2]] == [2, 2]
    # releasing slot 0 leaves the index hold (ref 1) on retained blocks;
    # the private block frees
    bs3 = release_slots(bs2, jnp.asarray([True, False]))
    ref = _conserved(bs3, NB)
    assert [ref[b] for b in ids0] == [1, 1, 0]
    # evicting the index holds frees everything — and is NOT idempotent
    # abuse-proof by design: each call drops one hold
    bs4 = release_refs(bs3, jnp.asarray(ids0[:2], jnp.int32))
    ref = _conserved(bs4, NB)
    assert int(bs4["n_free"]) == NB
    assert not np.any(ref)


def test_alloc_step_cow_pops_private_copy():
    """A decode write landing in a shared block (ref > 1) must rewire the
    slot to a fresh block and report the source for the row copy."""
    B, MB, NB = 2, 3, 6
    bs = init_block_state(B, MB, NB)
    # both slots share block table entry 0 -> block id via admit
    bs, ids = admit_slot(bs, jnp.int32(0), jnp.full((MB,), NEG, jnp.int32),
                         jnp.int32(0), jnp.int32(1), jnp.int32(1), MB)
    b0 = int(np.asarray(ids)[0])
    shared = np.full((MB,), NEG, np.int32)
    shared[0] = b0
    bs, _ = admit_slot(bs, jnp.int32(1), jnp.asarray(shared), jnp.int32(1),
                       jnp.int32(0), jnp.int32(0), MB)
    bs["slot_active"] = jnp.asarray([True, True])
    assert int(bs["ref"][b0]) == 3                # 2 tables + 1 hold
    # slot 1 writes at position 4 (inside the shared block, block_size 8)
    lengths = jnp.asarray([0, 4], jnp.int32)
    bs["slot_active"] = jnp.asarray([False, True])
    b2, wblk, woff, cow_src = alloc_step(bs, lengths, 8, MB * 8, False,
                                         cow=True)
    ref = _conserved(b2, NB)
    w1 = int(wblk[1])
    assert w1 != b0 and w1 < NB                   # private copy popped
    assert int(cow_src[1]) == b0                  # copy source reported
    assert int(woff[1]) == 4
    assert ref[b0] == 2                           # slot 1's ref moved off
    assert ref[w1] == 1
    assert int(np.asarray(b2["tbl"])[1, 0]) == w1
    # without sharing, cow is the identity (cow_src == wblk)
    b3, wblk3, _, cow3 = alloc_step(b2, lengths + 1, 8, MB * 8, False,
                                    cow=True)
    assert int(cow3[1]) == int(wblk3[1])


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and (
        a.tobytes() == b.tobytes())


@pytest.mark.parametrize("shared", [False, True],
                         ids=["no_cow", "one_slot_cows"])
def test_decode_step_cow_copies_block_then_writes_row(shared):
    """One ``decode_step_paged(cow=True)`` step equals, bit for bit, the
    copy-on-write made explicit: copy every slot's write block from its
    ``cow_src`` in every layer (the identity where they are equal, trash
    onto trash for the idle slots), then run the step with nothing left to
    copy.  Slot 0 writes row 6, inside its second block: a partial prompt
    block that the prefix index also holds when ``shared``."""
    from repro.engine.paged import BSTATE_KEYS
    cfg, model, params, spec = _setup()
    B, bs, NB = 4, 4, 12
    pc = model.init_paged_cache(B, 16, block_size=bs, num_blocks=NB)
    MB = pc["tbl"].shape[1]
    leaves, tdef = jax.tree.flatten(pc["stack"])
    pc["stack"] = jax.tree.unflatten(tdef, [
        jax.random.normal(k, l.shape).astype(l.dtype)
        for k, l in zip(jax.random.split(KEY, len(leaves)), leaves)])
    bst = {k: pc[k] for k in BSTATE_KEYS}
    none = jnp.full((MB,), NEG, jnp.int32)
    bst, _ = admit_slot(bst, jnp.int32(0), none, jnp.int32(0), jnp.int32(2),
                        jnp.int32(2 if shared else 0), MB)
    bst, _ = admit_slot(bst, jnp.int32(1), none, jnp.int32(0), jnp.int32(2),
                        jnp.int32(0), MB)
    bst["slot_active"] = jnp.asarray([True, True, False, False])
    pc = {**pc, **bst, "lengths": jnp.asarray([6, 5, 0, 0], jnp.int32)}
    tokens = jnp.asarray([[3], [5], [7], [9]], jnp.int32)
    step = jax.jit(model.decode_step_paged, static_argnames="cow")
    logits, got = step(params, tokens, pc, cow=True)

    b2, wblk, woff, cow_src = alloc_step(bst, pc["lengths"], bs, MB * bs,
                                         False, cow=True)
    assert int(jnp.sum(cow_src != wblk)) == int(shared)
    assert int(jnp.sum(wblk == NB)) == 2          # two slots onto trash
    copied = {lk: {n: (l.at[:, wblk].set(l[:, cow_src])
                       if n in ("pk", "pv") else l)
                   for n, l in lv.items()}
              for lk, lv in pc["stack"].items()}
    want_logits, want = step(params, tokens,
                             {**pc, **b2, "stack": copied}, cow=False)
    assert _same_bits(logits, want_logits)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _same_bits(g, w)
    if shared:   # the private copy holds the shared block's other rows
        src, dst, off = int(cow_src[0]), int(wblk[0]), int(woff[0])
        keep = np.arange(bs) != off
        for name in ("pk", "pv"):
            pool = np.asarray(got["stack"]["L0"][name], np.float32)
            old = np.asarray(pc["stack"]["L0"][name], np.float32)
            np.testing.assert_array_equal(pool[:, dst][:, keep],
                                          old[:, src][:, keep])
            np.testing.assert_array_equal(pool[:, src], old[:, src])


def test_span_targets_drop_shared_watermark():
    """Prefill-chunk writes below the prefix-hit watermark are dropped
    (the cached rows already hold the identical KV): no slot ever writes a
    block another owner reads."""
    B, MB, NB = 1, 4, 8
    bs = init_block_state(B, MB, NB)
    shared = np.full((MB,), NEG, np.int32)
    bs, ids = admit_slot(bs, jnp.int32(0), jnp.asarray(shared), jnp.int32(0),
                         jnp.int32(3), jnp.int32(0), MB)
    wblk, woff = span_targets(bs, jnp.asarray([14], jnp.int32),
                              jnp.asarray([6], jnp.int32), 8, 8, MB * 8,
                              False, jnp.asarray([16], jnp.int32))
    w = np.asarray(wblk)[0]
    tbl = np.asarray(bs["tbl"])[0]
    assert np.all(w[:2] == NB)                    # rows 14,15 < watermark
    assert np.all(w[2:6] == tbl[2])               # rows 16..19 writable
    assert np.all(w[6:] == NB)                    # pads beyond valid
    np.testing.assert_array_equal(np.asarray(woff)[0, 2:6], [0, 1, 2, 3])


# ---------------------------------------------------------------------------
# PrefixIndex unit behavior
# ---------------------------------------------------------------------------

def test_prefix_index_match_register_evict():
    idx = PrefixIndex(block_size=4)
    toks = list(range(10))                        # 2 full blocks + tail 2
    assert chain_hashes(toks, 4) == chain_hashes(toks + [99], 4)
    assert idx.match(toks) == ([], None, [])
    dups = idx.register(toks, [7, 3], 0)
    assert dups == [] and len(idx) == 2
    full, partial, keys = idx.match(toks)
    assert full == [7, 3] and partial is None and len(keys) == 2
    # a shorter prompt with a partial tail matching block 1's first rows
    full, partial, _ = idx.match(toks[:6])
    assert full == [7] and partial == 3
    # diverging content stops the chain at the divergence
    full, partial, _ = idx.match([0, 1, 2, 3, 9, 9, 9, 9, 5])
    assert full == [7] and partial is None
    # duplicate registration keeps the original
    assert idx.register(toks, [11, 12], 0) == [11, 12]
    # pinned entries refuse eviction; parents outlive their children
    full, _, keys = idx.match(toks)
    idx.pin(keys)
    assert idx.evict(2) == []
    idx.unpin(keys)
    assert idx.evict(2) == [3, 7]                 # leaf first, then parent
    assert len(idx) == 0


# ---------------------------------------------------------------------------
# Eviction instead of FIFO-wait deadlock
# ---------------------------------------------------------------------------

def test_admission_evicts_cached_blocks_instead_of_deadlocking():
    """A pool whose capacity is entirely held by cached (table-unreferenced)
    prefix blocks must evict LRU entries at admission, not wait forever."""
    cfg, model, params, spec = _setup()
    common = _tokens(spec, 16, seed=5)
    a = jnp.concatenate([common, _tokens(spec, 6, seed=6)])
    b = jnp.concatenate([common, _tokens(spec, 6, seed=7)])
    c = _tokens(spec, 22, seed=8)                 # unrelated content
    contig = Engine(model, params, slots=1, cache_len=32,
                    k_steps=2).serve([a, b, c], gen_tokens=4)
    # pool of 5 blocks: one 22-token request demands 4 (2 cached-prefix
    # holds + tail block + decode growth); after a+b the index still holds
    # a's 2-block prefix, so admitting c (2 new holds + 2 slot blocks on
    # top of the 2 cached) exceeds the pool and must evict LRU entries
    eng = Engine(model, params, slots=1, cache_len=32, k_steps=2,
                 paged=True, block_size=8, num_blocks=5, prefix_cache=True,
                 chunk_size=8, check_invariants=True)
    outs, stats = eng.serve([a, b, c], gen_tokens=4, return_stats=True)
    assert outs == contig
    assert stats["prefix_evictions"] > 0
    assert stats["prefix_hits"] > 0               # b still hit a's prefix


def test_warm_partial_hit_on_saturated_pool_degrades_not_crashes():
    """A pool whose every block is cached AND matched by the incoming
    request: the request's own pins would make nothing evictable and the
    partial-hit CoW spare cannot be found — admission must unpin and
    force-evict its own matches (degrading toward a cold prefill) instead
    of stalling an idle pool."""
    cfg, model, params, spec = _setup()
    long = _tokens(spec, 24, seed=51)[:24]        # exactly 3 full blocks
    short = long[:20]                             # partial hit in block 2
    contig_l = Engine(model, params, slots=1, cache_len=24,
                      k_steps=2).serve([long], gen_tokens=8)
    contig_s = Engine(model, params, slots=1, cache_len=24,
                      k_steps=2).serve([short], gen_tokens=8)
    eng = Engine(model, params, slots=1, cache_len=24, k_steps=2,
                 paged=True, block_size=8, num_blocks=3, prefix_cache=True,
                 chunk_size=8, check_invariants=True)
    assert eng.serve([long], gen_tokens=8) == contig_l
    # all 3 pool blocks are now index-held; the partial hit would pin all
    # of them and still need a CoW spare — must evict its own LRU match
    outs, stats = eng.serve([short], gen_tokens=8, return_stats=True)
    assert outs == contig_s
    assert stats["prefix_evictions"] > 0


def test_serve_after_the_first_compiles_nothing_when_it_evicts():
    """The serve that builds the prefix-cached loop's cache compiles every
    entry the loop runs, eviction included: a later serve whose admissions
    evict cached blocks compiles nothing (a compile mid-serve stalls every
    slot)."""
    cfg, model, params, spec = _setup()
    eng = Engine(model, params, slots=1, cache_len=32, k_steps=2,
                 paged=True, block_size=8, num_blocks=5, prefix_cache=True,
                 chunk_size=8)
    _, first = eng.serve([_tokens(spec, 22, seed=11)], gen_tokens=4,
                         return_stats=True)
    assert first["prefix_evictions"] == 0
    before = eng.compile_counts()
    _, stats = eng.serve([_tokens(spec, 22, seed=12),
                          _tokens(spec, 22, seed=13)], gen_tokens=4,
                         return_stats=True)
    assert stats["prefix_evictions"] > 0
    assert eng.compile_counts() == before


# ---------------------------------------------------------------------------
# Warm vs cold token exactness (incl. partial-hit CoW) + fewer prefills
# ---------------------------------------------------------------------------

def test_prefix_warm_hits_are_token_exact_and_cheaper():
    cfg, model, params, spec = _setup()
    sysp = _tokens(spec, 20, seed=11)             # 2.5 blocks of 8
    prompts = [jnp.concatenate([sysp, _tokens(spec, 6, seed=20 + i)])
               for i in range(3)]
    contig = Engine(model, params, slots=2, cache_len=48,
                    k_steps=3).serve(prompts, gen_tokens=6)

    eng = Engine(model, params, slots=2, cache_len=48, k_steps=3,
                 paged=True, block_size=8, num_blocks=24, prefix_cache=True,
                 chunk_size=8, check_invariants=True)
    cold, cs = eng.serve(prompts, gen_tokens=6, return_stats=True)
    warm, ws = eng.serve(prompts, gen_tokens=6, return_stats=True)
    assert cold == contig                         # in-run sharing is exact
    assert warm == contig                         # cross-run hits are exact
    assert ws["prefill_tokens"] < cs["prefill_tokens"]
    assert ws["prefix_hits"] > cs["prefix_hits"]


def test_partial_block_hit_copy_on_write_exact():
    """A prompt that is a mid-block prefix of a cached prompt maps the
    cached partial block shared; its first decode write must CoW a private
    copy — the cached request re-served afterwards still sees its own rows
    (bit-exact), proving the copy really copied."""
    cfg, model, params, spec = _setup()
    long = _tokens(spec, 24, seed=31)             # 3 full blocks
    short = long[:20]                             # partial hit in block 2
    contig_l = Engine(model, params, slots=1, cache_len=40,
                      k_steps=2).serve([long], gen_tokens=5)
    contig_s = Engine(model, params, slots=1, cache_len=40,
                      k_steps=2).serve([short], gen_tokens=5)
    eng = Engine(model, params, slots=1, cache_len=40, k_steps=2,
                 paged=True, block_size=8, num_blocks=12, prefix_cache=True,
                 chunk_size=8, check_invariants=True)
    assert eng.serve([long], gen_tokens=5) == contig_l
    assert eng.serve([short], gen_tokens=5) == contig_s   # CoW path
    assert eng._index.partial_hits > 0
    assert eng.serve([long], gen_tokens=5) == contig_l    # rows uncorrupted


def test_prefix_gen_tokens_one_releases_and_still_caches():
    """gen_tokens=1 drains the slot inside the very dispatch that finishes
    its prefill; the pre-retained prompt blocks must survive the in-scan
    release and serve the next request's hits."""
    cfg, model, params, spec = _setup()
    prompts = [_tokens(spec, 16, seed=41)] * 3
    contig = Engine(model, params, slots=2, cache_len=24,
                    k_steps=2).serve(prompts, gen_tokens=1)
    eng = Engine(model, params, slots=2, cache_len=24, k_steps=2,
                 paged=True, block_size=8, num_blocks=8, prefix_cache=True,
                 chunk_size=8, check_invariants=True)
    outs, stats = eng.serve(prompts, gen_tokens=1, return_stats=True)
    assert outs == contig
    assert stats["prefix_hits"] > 0


# ---------------------------------------------------------------------------
# Hypothesis-seeded stress sweep: prompt families, churn, tight pools
# ---------------------------------------------------------------------------

@settings(max_examples=3, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_prefix_stress_randomized(seed):
    """Random prompt families (shared prefixes of random depth), more
    requests than slots (interleaved arrivals + slot churn), random
    chunk/k_steps/gen and a randomly tightened pool — with the allocator
    conservation invariants asserted after every admission and dispatch
    (check_invariants), outputs token-exact vs the contiguous engine, both
    cold and warm."""
    rng = np.random.RandomState(seed)
    cfg, model, params, spec = _setup()
    slots = int(rng.randint(2, 4))
    n_fam = int(rng.randint(1, 4))
    fams = [_tokens(spec, int(rng.randint(4, 22)), seed=seed % 911 + f)
            for f in range(n_fam)]
    n_req = int(rng.randint(slots, slots + 4))
    prompts, lens = [], []
    for i in range(n_req):
        fam = fams[int(rng.randint(n_fam))]
        depth = int(rng.randint(0, fam.shape[0] + 1))
        tail = _tokens(spec, int(rng.randint(1, 9)), seed=seed % 877 + 50 + i)
        p = jnp.concatenate([fam[:depth], tail])
        prompts.append(p)
        lens.append(int(p.shape[0]))
    gen = int(rng.randint(1, 7))
    k_steps = int(rng.randint(1, 4))
    chunk = int(rng.choice([4, 8, 16]))
    cache_len = max(lens) + gen + int(rng.randint(0, 6))
    contig = Engine(model, params, slots=slots, cache_len=cache_len,
                    k_steps=k_steps).serve(prompts, gen_tokens=gen)
    lo = max(blocks_for(min(L + gen - 1, cache_len), 8) + 1 for L in lens)
    full = slots * blocks_for(cache_len, 8) + 4
    num_blocks = int(rng.randint(lo, full + 1))   # sometimes starved pool
    eng = Engine(model, params, slots=slots, cache_len=cache_len,
                 k_steps=k_steps, paged=True, block_size=8,
                 num_blocks=num_blocks, prefix_cache=True, chunk_size=chunk,
                 check_invariants=True)
    outs, stats = eng.serve(prompts, gen_tokens=gen, return_stats=True)
    assert outs == contig
    # device-counter conservation: after the drain the only blocks out of
    # the pool are the prefix index's holds ("popped == released + live")
    c = stats["counters"]
    assert (c["blocks_popped"] - c["blocks_released"]
            == len(eng._hold_blocks))
    assert c["prefix_hit_tokens"] == stats["prefix_hits"]
    assert c["tokens"] == stats["tokens"]
    held0 = len(eng._hold_blocks)
    outs, stats = eng.serve(prompts, gen_tokens=gen, return_stats=True)
    assert outs == contig                                 # warm pass
    c = stats["counters"]
    # warm counters re-zero and re-baseline on the blocks held at start
    assert (held0 + c["blocks_popped"] - c["blocks_released"]
            == len(eng._hold_blocks))
    assert c["prefix_hit_tokens"] == stats["prefix_hits"]
