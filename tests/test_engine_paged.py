"""Paged KV-cache tests: greedy token-exactness against the contiguous
engine AND the legacy host loop under adversarial workloads (mixed prompt
lengths, interleaved arrivals, slot churn, tight pools), allocator unit
invariants, and config validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_arch, reduced
from repro.data import LanguageSpec, sample_batch
from repro.engine import (Engine, blocks_for, init_block_state,
                          release_slots, serve_host_loop)
from repro.engine.paged import NEG, alloc_admit, alloc_step, gather_blocks
from repro.models import build_model

KEY = jax.random.PRNGKey(0)

_BUILT: dict = {}


def _setup(arch="glm4-9b"):
    """Model + params, cached per arch so the jit caches stay warm across
    the randomized examples."""
    if arch not in _BUILT:
        cfg = reduced(get_arch(arch))
        model = build_model(cfg)
        params = model.init(KEY)
        _BUILT[arch] = (cfg, model, params,
                        LanguageSpec(vocab=cfg.vocab_size))
    return _BUILT[arch]


def _prompts(spec, lens, seed=0):
    return [sample_batch(jax.random.PRNGKey(seed * 1000 + i), spec, 1, L)[0]
            for i, L in enumerate(lens)]


# ---------------------------------------------------------------------------
# Token-exactness: paged == contiguous == legacy host loop
# ---------------------------------------------------------------------------

def test_paged_token_exact_dense_mixed_lengths():
    """Dense causal stack, wildly different prompt lengths, more requests
    than slots (continuous slot churn)."""
    cfg, model, params, spec = _setup()
    prompts = _prompts(spec, [10, 25, 6, 17, 9, 12])
    legacy = serve_host_loop(model, params, prompts, batch=2, gen_tokens=6,
                             cache_len=40)
    contig = Engine(model, params, slots=2, cache_len=40,
                    k_steps=3).serve(prompts, gen_tokens=6)
    peng = Engine(model, params, slots=2, cache_len=40, k_steps=3,
                  paged=True, block_size=8)
    pout, stats = peng.serve(prompts, gen_tokens=6, return_stats=True)
    assert contig == legacy
    assert pout == contig
    # the paged pool at capacity parity is the same order of bytes as the
    # contiguous cache (block-rounding + one trash block of overhead)
    assert stats["cache_bytes"] > 0


def test_paged_token_exact_swa_ring():
    """SWA config: the paged cache pages the ring itself (window 16,
    blocks of 8) and must reproduce ring-cache decoding exactly, including
    prompts longer than the window."""
    cfg, model, params, spec = _setup("mixtral-8x22b")
    assert cfg.sliding_window == 16
    prompts = _prompts(spec, [9, 21, 9, 14])
    legacy = serve_host_loop(model, params, prompts, batch=2, gen_tokens=5,
                             cache_len=34)
    peng = Engine(model, params, slots=2, cache_len=34, k_steps=2,
                  paged=True, block_size=8)
    assert peng.serve(prompts, gen_tokens=5) == legacy


@pytest.mark.parametrize("arch", ["mamba2-780m", "jamba-v0.1-52b",
                                  "granite-4.0-h-small"])
def test_paged_routes_around_contiguous_state(arch):
    """Mamba (pure SSM) and hybrid (Jamba; Granite with a held expert
    share) stacks: SSM state has no sequence axis to page and stays
    per-slot dense; outputs still match."""
    cfg, model, params, spec = _setup(arch)
    prompts = _prompts(spec, [9, 12, 9])
    contig = Engine(model, params, slots=2, cache_len=34,
                    k_steps=2).serve(prompts, gen_tokens=4)
    pout = Engine(model, params, slots=2, cache_len=34, k_steps=2,
                  paged=True, block_size=8).serve(prompts, gen_tokens=4)
    assert pout == contig, arch


def test_paged_tight_pool_serializes_but_stays_exact():
    """A pool too small for two concurrent requests forces sequential
    admission; outputs stay token-exact and every request completes."""
    cfg, model, params, spec = _setup()
    prompts = _prompts(spec, [20, 20, 20, 20])
    contig = Engine(model, params, slots=2, cache_len=32,
                    k_steps=2).serve(prompts, gen_tokens=5)
    tight = Engine(model, params, slots=2, cache_len=32, k_steps=2,
                   paged=True, block_size=8, num_blocks=4)
    assert blocks_for(20 + 5 - 1, 8) == 3        # 3 of 4 blocks per request
    outs, stats = tight.serve(prompts, gen_tokens=5, return_stats=True)
    assert outs == contig
    # one admission round per request: the pool can never hold two
    assert stats["prefill_calls"] == 4


def test_paged_overlong_prompt_does_not_leak_blocks():
    """A prompt longer than the per-slot capacity only keeps its first
    ``cache_len`` rows (the contiguous cache drops the overflow the same
    way); the allocator must debit exactly the blocks the scatter places —
    an unclamped count would leak pool blocks and later hand out
    duplicates.  Serving many such prompts through a capacity-parity pool
    still terminates with every request answered."""
    cfg, model, params, spec = _setup()
    prompts = _prompts(spec, [24, 24, 24, 24])       # cap is 16 rows
    contig = Engine(model, params, slots=2, cache_len=16,
                    k_steps=2).serve(prompts, gen_tokens=3)
    pout = Engine(model, params, slots=2, cache_len=16, k_steps=2,
                  paged=True, block_size=8).serve(prompts, gen_tokens=3)
    assert [len(o) for o in pout] == [3] * 4
    assert pout == contig


def test_paged_gen_tokens_one_releases_blocks_at_admission():
    """gen_tokens=1 finishes a slot inside the admission scatter; its
    blocks must come back so follow-up requests are not starved."""
    cfg, model, params, spec = _setup()
    prompts = _prompts(spec, [16] * 6)
    contig = Engine(model, params, slots=2, cache_len=24,
                    k_steps=2).serve(prompts, gen_tokens=1)
    tight = Engine(model, params, slots=2, cache_len=24, k_steps=2,
                   paged=True, block_size=8, num_blocks=4)
    assert tight.serve(prompts, gen_tokens=1) == contig


# ---------------------------------------------------------------------------
# Chunked prefill: bit-identical to one-shot prefill across the families
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch, moe, chunk, lens, cache_len", [
    ("glm4-9b", False, 8, [10, 25, 6, 17], 40),      # dense
    ("mixtral-8x22b", True, 8, [9, 21, 9, 14], 34),  # SWA ring + MoE
    ("deepseek-v3", True, 8, [9, 21, 14], 34),       # MoE (no window)
    ("mamba2-780m", False, 32, [9, 40, 12], 48),     # pure SSM
    ("jamba-v0.1-52b", True, 32, [9, 40, 12], 48),   # hybrid
    ("granite-4.0-h-small", True, 32, [9, 40, 12], 48),  # hybrid, held
                                                         # expert share
], ids=lambda v: v if isinstance(v, str) else None)
def test_chunked_prefill_token_exact_matrix(arch, moe, chunk, lens,
                                            cache_len):
    """Chunked prefill (prompts streaming through the decode dispatch in
    chunk_size pieces) must be token-exact vs the one-shot-prefill
    contiguous engine on every family: dense, SWA-ring (chunks wrap the
    paged ring), MoE (dropless on every path: the expert layer counts its
    assignments), pure SSM and hybrid (state threaded chunk-to-chunk on the SSD grid).
    Prompts cross chunk AND block boundaries and mix with in-flight
    decode (slot churn: more requests than slots).

    Each case runs the paged+prefix engine TWICE: the cold pass pins
    chunked-vs-one-shot (and in-run sharing where content-sound), the warm
    pass pins prefix-hit-vs-cold-cache — bit-identical outputs in every
    direction.  SWA/SSM/hybrid run with matching disabled (position-keyed
    rings / recurrent state can't be shared), so their warm pass pins that
    the persistent cache stays exact with sharing inert."""
    cfg, model, params, spec = _setup(arch)
    prompts = _prompts(spec, lens)
    contig = Engine(model, params, slots=2, cache_len=cache_len,
                    k_steps=2).serve(prompts, gen_tokens=4)
    peng = Engine(model, params, slots=2, cache_len=cache_len,
                  k_steps=2, paged=True, block_size=8, chunk_size=chunk,
                  prefix_cache=True, check_invariants=True)
    outs, stats = peng.serve(prompts, gen_tokens=4, return_stats=True)
    assert outs == contig, arch                                  # cold
    assert (stats["counters"].get("moe_assigned", 0) > 0) == moe, arch
    assert peng.serve(prompts, gen_tokens=4) == contig, arch   # warm


def test_chunked_prefill_without_prefix_cache_exact():
    """Plain chunked prefill (no sharing, cow=False dispatch) on dense."""
    cfg, model, params, spec = _setup()
    prompts = _prompts(spec, [10, 25, 6, 17])
    contig = Engine(model, params, slots=2, cache_len=40,
                    k_steps=2).serve(prompts, gen_tokens=4)
    cout = Engine(model, params, slots=2, cache_len=40, k_steps=2,
                  paged=True, block_size=8, chunk_size=8,
                  check_invariants=True).serve(prompts, gen_tokens=4)
    assert cout == contig


def test_chunked_validation_errors():
    cfg, model, params, spec = _setup()
    with pytest.raises(ValueError, match="need paged"):
        Engine(model, params, slots=2, cache_len=32, chunk_size=8)
    with pytest.raises(ValueError, match="exceeds cache_len"):
        Engine(model, params, slots=2, cache_len=16, paged=True,
               block_size=8, chunk_size=8).serve(
                   _prompts(spec, [24]), gen_tokens=2)
    cfg, model, params, spec = _setup("jamba-v0.1-52b")
    with pytest.raises(ValueError, match="multiple of ssm_chunk"):
        Engine(model, params, slots=2, cache_len=64, paged=True,
               block_size=8, chunk_size=8)


# ---------------------------------------------------------------------------
# Randomized stress: hypothesis-seeded mixed lengths / arrivals / churn
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_paged_stress_randomized(seed):
    """Adversarial workload sweep: random prompt lengths (some crossing
    block boundaries, some below one block), random request count vs slot
    count (interleaved arrivals + slot churn), random k_steps/gen and a
    randomly tightened pool.  Paged output must be token-exact against
    BOTH the contiguous engine and the legacy host loop."""
    rng = np.random.RandomState(seed)
    cfg, model, params, spec = _setup()
    slots = int(rng.randint(2, 4))
    n_req = int(rng.randint(slots, slots + 4))
    lens = [int(rng.randint(4, 29)) for _ in range(n_req)]
    gen = int(rng.randint(2, 7))
    k_steps = int(rng.randint(1, 4))
    cache_len = max(lens) + gen + int(rng.randint(0, 6))
    prompts = _prompts(spec, lens, seed=seed % 997)
    lens = [int(p.shape[0]) for p in prompts]   # sample_batch adds a token

    legacy = serve_host_loop(model, params, prompts, batch=slots,
                             gen_tokens=gen, cache_len=cache_len)
    contig = Engine(model, params, slots=slots, cache_len=cache_len,
                    k_steps=k_steps).serve(prompts, gen_tokens=gen)
    mb = blocks_for(cache_len, 8)
    full = slots * mb
    lo = max(blocks_for(L + gen - 1, 8) for L in lens)
    num_blocks = int(rng.randint(lo, full + 1))  # sometimes starved pool
    pout = Engine(model, params, slots=slots, cache_len=cache_len,
                  k_steps=k_steps, paged=True, block_size=8,
                  num_blocks=num_blocks).serve(prompts, gen_tokens=gen)
    assert contig == legacy
    assert pout == contig


# ---------------------------------------------------------------------------
# Allocator unit invariants
# ---------------------------------------------------------------------------

def test_block_allocator_invariants():
    """alloc/release round-trips conserve the pool: the free stack plus the
    union of table entries is always a partition of the block ids."""
    B, MB, NB = 3, 4, 8
    bstate = init_block_state(B, MB, NB)
    lengths = jnp.asarray([0, 5, 16], jnp.int32)
    bstate["slot_active"] = jnp.asarray([True, True, True])

    def held(bs):
        t = np.asarray(bs["tbl"])
        return set(t[t >= 0].tolist())

    def free_set(bs):
        f = np.asarray(bs["free"])
        return set(f[: int(bs["n_free"])].tolist())

    # decode-time allocation: slot 0 -> block j=0, slot 1 -> j=1, slot 2 -> j=4>=MB? no: 16//8=2
    bstate, wblk, woff, _ = alloc_step(bstate, lengths, 8, MB * 8, False)
    assert int(bstate["n_free"]) == NB - 3
    assert held(bstate) & free_set(bstate) == set()
    assert held(bstate) | free_set(bstate) == set(range(NB))
    # every allocated block carries exactly one reference
    assert all(int(bstate["ref"][b]) == 1 for b in held(bstate))
    # write targets point at the allocated blocks, offsets are in-block
    assert np.all(np.asarray(wblk) < NB)
    np.testing.assert_array_equal(np.asarray(woff), [0, 5, 0])

    # inactive slots route to the trash block and never allocate
    bstate["slot_active"] = jnp.asarray([True, False, True])
    b2, wblk2, _, _ = alloc_step(bstate, lengths + 1, 8, MB * 8, False)
    assert int(b2["n_free"]) == int(bstate["n_free"])
    assert int(wblk2[1]) == NB                    # trash index

    # release returns every held block exactly once
    b3 = release_slots(b2, jnp.asarray([True, True, True]))
    assert int(b3["n_free"]) == NB
    assert free_set(b3) == set(range(NB))
    assert np.all(np.asarray(b3["tbl"]) == NEG)
    assert not np.any(np.asarray(b3["slot_active"]))


def test_alloc_admit_counts_and_trash_padding():
    B, MB, NB = 4, 6, 12
    bstate = init_block_state(B, MB, NB)
    slots = jnp.asarray([1, 3], jnp.int32)
    counts = jnp.asarray([2, 5], jnp.int32)
    bstate, wids = alloc_admit(bstate, slots, counts, nbl=5)
    assert wids.shape == (2, 5)
    w = np.asarray(wids)
    assert np.all(w[0, 2:] == NB)                 # padded with trash
    assert np.all(w[1] < NB)
    ids = np.concatenate([w[0, :2], w[1]])
    assert len(set(ids.tolist())) == 7            # all distinct
    assert int(bstate["n_free"]) == NB - 7
    tbl = np.asarray(bstate["tbl"])
    assert np.all(tbl[0] == NEG) and np.all(tbl[2] == NEG)
    assert set(tbl[1][tbl[1] >= 0].tolist()) == set(w[0, :2].tolist())


@pytest.mark.parametrize("layer", [0, 2])
def test_gather_blocks_reproduces_linear_layout(layer):
    """Blocks of the stacked pool's ``layer`` come out in table order."""
    NB, bs, Kv, hd = 5, 4, 2, 3
    stack = jnp.arange(3 * (NB + 1) * bs * Kv * hd,
                       dtype=jnp.float32).reshape(3, NB + 1, bs, Kv, hd)
    pool = stack[layer]
    tbl = jnp.asarray([[2, 0, NEG], [4, NEG, NEG]], jnp.int32)
    g = gather_blocks(stack, tbl, jnp.int32(layer))
    assert g.shape == (2, 3 * bs, Kv, hd)
    np.testing.assert_array_equal(np.asarray(g[0, :bs]), np.asarray(pool[2]))
    np.testing.assert_array_equal(np.asarray(g[0, bs:2 * bs]),
                                  np.asarray(pool[0]))
    # NEG wraps to the trash block (index NB) — masked by callers
    np.testing.assert_array_equal(np.asarray(g[1, bs:2 * bs]),
                                  np.asarray(pool[NB]))


# ---------------------------------------------------------------------------
# Validation
# ---------------------------------------------------------------------------

def test_paged_validation_errors():
    cfg, model, params, spec = _setup("mixtral-8x22b")
    with pytest.raises(ValueError, match="cache_len >= sliding_window"):
        Engine(model, params, slots=2, cache_len=8, k_steps=2, paged=True,
               block_size=8).serve(_prompts(spec, [4]), gen_tokens=2)
    with pytest.raises(ValueError, match="must divide the sliding window"):
        Engine(model, params, slots=2, cache_len=34, k_steps=2, paged=True,
               block_size=6).serve(_prompts(spec, [4]), gen_tokens=2)

    cfg, model, params, spec = _setup()
    eng = Engine(model, params, slots=2, cache_len=64, k_steps=2,
                 paged=True, block_size=8, num_blocks=2)
    with pytest.raises(ValueError, match="blocks but the pool"):
        eng.serve(_prompts(spec, [40]), gen_tokens=4)
