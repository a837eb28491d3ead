"""Paged KV-cache: global block pool, per-slot block tables, device free-list,
and **refcounted block sharing** (prefix caching / copy-on-write).

A contiguous cache reserves ``cache_len`` rows per slot per layer, so the
longest admissible request dictates the memory of every slot.  The paged
cache, the engine's only cache, replaces the per-slot rows with a **global
pool of fixed-size token blocks** shared by all slots:

* per attention layer: ``pk`` / ``pv`` pools of shape
  ``[num_blocks + 1, block_size, Kv, hd]`` — the extra last block is a
  **trash sink**: writes for inactive slots (the dispatch keeps decoding
  finished slots) and capacity overflows are
  routed there instead of corrupting live blocks;
* one **block table** ``tbl [slots, max_blocks]`` shared by every attention
  layer (all layers advance in lockstep, so one table serves the stack);
  ``-1`` marks an unallocated entry and — because jnp gathers wrap negative
  indices — conveniently gathers the trash block, whose garbage the length
  mask then discards;
* a **device-resident free-list** ``free [num_blocks]`` (a stack of block
  ids) with scalar stack pointer ``n_free``: blocks are popped inside the
  jitted decode step the moment a slot's length crosses a block boundary and
  pushed back inside the K-step scan the moment a slot's budget drains — so
  capacity recycles mid-dispatch, without a host round-trip;
* a **refcount array** ``ref [num_blocks]``: a block's count of owners —
  one per block-table entry referencing it, plus one while the host prefix
  index holds it as a cached prompt block.  Allocation sets ``ref = 1``
  (``2`` when the block is simultaneously retained for the prefix index),
  prefix-hit admission maps an existing block with ``ref += 1``, and every
  release path *decrements*; a block returns to the free stack only when
  its refcount reaches zero.  Conservation invariant (pinned in
  tests/test_engine_prefix.py)::

      n_free + |{b : ref[b] > 0}| == num_blocks

  and ``ref[b]`` equals the number of live table entries pointing at ``b``
  plus the host's index/pending hold (0 or 1).

**Copy-on-write**: a slot may only append KV rows to a block it owns
exclusively.  When a decode write lands in a block with ``ref > 1`` (a
partially-filled prompt block shared through the prefix cache),
``alloc_step`` pops a fresh block, rewires the slot's table entry to it,
decrements the shared block, and reports the old block as ``cow_src`` so
the step copies its rows, in every layer, before the layer scan appends
(``models.lm.cow_copy_blocks``).  Prefill-chunk writes never CoW: a
recomputed row whose target is shared is simply dropped (the cached row
already holds the identical value).

Everything here is shape-static jit-safe jnp; per-layer wiring lives in
``models/lm.py`` (``init_paged_cache`` / ``decode_step_paged`` /
``prefill_chunk_paged``), the host-side admission policy in ``engine.py``
and the host hash->block prefix index in ``prefix.py``.

SSM / Mamba layers keep their contiguous per-slot state (it has no sequence
axis to page) and are routed around: their cache leaves stay ``[n, B, ...]``
dense and only ``pk``/``pv`` leaves are pooled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1  # unallocated table entry; wraps to the trash block on gather

# allocator-state keys riding at the top level of a paged cache pytree
BSTATE_KEYS = ("tbl", "free", "n_free", "ref", "slot_active")


# ---------------------------------------------------------------------------
# Block-state construction
# ---------------------------------------------------------------------------

def init_block_state(slots: int, max_blocks: int, num_blocks: int) -> dict:
    """Zeroed allocator state: empty tables, fully-free stack, zero refs."""
    return {
        "tbl": jnp.full((slots, max_blocks), NEG, jnp.int32),
        "free": jnp.arange(num_blocks, dtype=jnp.int32),
        "n_free": jnp.int32(num_blocks),
        "ref": jnp.zeros((num_blocks,), jnp.int32),
        "slot_active": jnp.zeros((slots,), bool),
    }


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` cache rows."""
    return -(-n_tokens // block_size)


def _free_newly_zero(free, n_free, ref_old, ref_new):
    """Push blocks whose refcount just reached zero back on the free stack
    (ascending block-id order, deterministic)."""
    NB = free.shape[0]
    hit = (ref_old > 0) & (ref_new == 0)
    rank = jnp.cumsum(hit.astype(jnp.int32))        # 1-based push rank
    dest = jnp.where(hit, n_free + rank - 1, NB)    # out-of-range -> dropped
    free = free.at[dest].set(jnp.arange(NB, dtype=free.dtype), mode="drop")
    return free, n_free + jnp.sum(hit.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Decode-time allocation / release (jit-safe, called inside the dispatch)
# ---------------------------------------------------------------------------

def alloc_step(bstate: dict, lengths: jnp.ndarray, block_size: int,
               cap: int, ring: bool, cow: bool = False):
    """One decode step's allocation + write routing, fused.

    Pops a fresh block for every active slot whose write position lands in
    an unallocated table entry (one write per slot per step, so at most one
    block per slot); pool exhaustion leaves the entry unallocated and the
    write then lands in the trash block instead of corrupting the pool.

    With ``cow=True`` a write position landing in a block with ``ref > 1``
    (shared through the prefix cache) also pops a fresh block: the table
    entry is rewired to the copy, the shared block's refcount drops by one,
    and the old id is reported as ``cow_src`` so the step can copy the
    block's rows before appending.  ``cow_src == wblk`` marks "no copy"
    (the copy is then the identity).

    Returns ``(bstate, wblk [B], woff [B], cow_src [B])`` — the per-slot
    write target for this step's KV row.  ``cap`` is the logical per-slot
    capacity (``max_blocks * block_size``); ``ring`` maps positions modulo
    ``cap`` (SWA ring semantics).  Inactive slots and positions beyond
    capacity are routed to the trash block.
    """
    tbl, free, n_free = bstate["tbl"], bstate["free"], bstate["n_free"]
    ref = bstate["ref"]
    B, MB = tbl.shape
    trash = free.shape[0]                       # pool index num_blocks
    pos = lengths % cap if ring else lengths
    valid = bstate["slot_active"] & (pos < cap)
    j = jnp.clip(pos // block_size, 0, MB - 1)
    bidx = jnp.arange(B)
    cur = tbl[bidx, j]
    have = cur >= 0
    if cow:
        shared = valid & have & (ref[jnp.clip(cur, 0, trash - 1)] > 1)
    else:
        shared = jnp.zeros((B,), bool)
    need = valid & (~have | shared)
    k = jnp.cumsum(need.astype(jnp.int32))      # 1-based pop rank per slot
    ok = need & (k <= n_free)
    ids = free[jnp.clip(n_free - k, 0, trash - 1)]
    blk = jnp.where(ok, ids, cur)
    tbl = tbl.at[bidx, j].set(blk)
    ref = ref.at[jnp.where(ok, ids, trash)].set(1, mode="drop")
    # a successful CoW pop releases one reference on the shared source;
    # ref stays >= 1 there (the prefix index / other sharers still hold it)
    dec = shared & ok
    ref = ref.at[jnp.where(dec, cur, trash)].add(-1, mode="drop")
    n_free = n_free - jnp.sum(ok.astype(jnp.int32))
    # a shared target whose CoW pop failed (pool dry) must NOT be written:
    # route to trash rather than corrupting the other owners' rows.  The
    # engine's reservation ledger counts one spare block per potential CoW,
    # so this path is unreachable in normal operation.
    writable = valid & (blk >= 0) & ~(shared & ~ok)
    wblk = jnp.where(writable, blk, trash)
    woff = pos % block_size
    cow_src = jnp.where(dec, cur, wblk)
    return ({**bstate, "tbl": tbl, "ref": ref, "n_free": n_free},
            wblk, woff, cow_src)


def release_slots(bstate: dict, done: jnp.ndarray) -> dict:
    """Drop one reference on every block of the ``done`` slots' tables and
    push the blocks whose refcount reaches zero back on the free stack;
    clear the table rows + active flags.  Blocks still held elsewhere (other
    slots' tables, the host prefix index) survive with ``ref >= 1``.  Safe
    to call with slots that own nothing (idempotent)."""
    tbl, free, n_free = bstate["tbl"], bstate["free"], bstate["n_free"]
    ref = bstate["ref"]
    NB = free.shape[0]
    mask = done[:, None] & (tbl >= 0)
    ids = jnp.where(mask, tbl, NB).reshape(-1)
    new_ref = ref.at[ids].add(-1, mode="drop")
    free, n_free = _free_newly_zero(free, n_free, ref, new_ref)
    tbl = jnp.where(done[:, None], NEG, tbl)
    active = bstate["slot_active"] & ~done
    return {**bstate, "tbl": tbl, "free": free, "n_free": n_free,
            "ref": new_ref, "slot_active": active}


def release_refs(bstate: dict, ids: jnp.ndarray) -> dict:
    """Drop one host-side hold per id in ``ids`` (``-1`` entries ignored)
    and free blocks reaching refcount zero — the prefix-cache eviction path
    and the duplicate-registration unwind.  Duplicate ids accumulate."""
    free, n_free, ref = bstate["free"], bstate["n_free"], bstate["ref"]
    NB = free.shape[0]
    new_ref = ref.at[jnp.where(ids >= 0, ids, NB)].add(-1, mode="drop")
    free, n_free = _free_newly_zero(free, n_free, ref, new_ref)
    return {**bstate, "free": free, "n_free": n_free, "ref": new_ref}


# ---------------------------------------------------------------------------
# Admission-time allocation (jit-safe, called from the engine)
# ---------------------------------------------------------------------------

def admit_slot(bstate: dict, slot, shared_ids: jnp.ndarray, n_shared,
               n_new, n_retained, nbl: int):
    """Admit one request into ``slot`` for chunked / prefix-cached prefill.

    Builds the slot's table row as ``[shared_ids[:n_shared], <n_new popped
    blocks>, NEG...]`` — shared blocks (prefix hits) get ``ref += 1``
    without consuming pool capacity; popped blocks get ``ref = 1``, except
    the first ``n_retained`` which get ``ref = 2``: one table reference
    plus one **prospective prefix-index hold** (the host registers their
    content once the prompt finishes prefilling; pre-retaining at admission
    keeps them alive even if the slot drains inside the same dispatch).

    The slot stays ``slot_active = False`` (prefill phase: decode-step
    writes route to trash until the first token is sampled in-scan).
    Returns ``(bstate, new_ids [nbl])`` — popped ids, ``-1`` padded, in
    table order — for the host to register.
    """
    tbl, free, n_free = bstate["tbl"], bstate["free"], bstate["n_free"]
    ref = bstate["ref"]
    NB = free.shape[0]
    jj = jnp.arange(nbl)
    take = jj < n_new
    pop_ids = jnp.where(take, free[jnp.clip(n_free - 1 - jj, 0, NB - 1)], NEG)
    row = jnp.where(jj < n_shared, shared_ids, NEG)
    sel = jnp.clip(jj - n_shared, 0, nbl - 1)
    in_new = (jj >= n_shared) & (jj < n_shared + n_new)
    row = jnp.where(in_new, pop_ids[sel], row)
    tbl = tbl.at[slot].set(
        jnp.pad(row, (0, tbl.shape[1] - nbl), constant_values=NEG))
    ref = ref.at[jnp.where(jj < n_shared, shared_ids, NB)].add(
        1, mode="drop")
    ref = ref.at[jnp.where(take, pop_ids, NB)].set(
        jnp.where(jj < n_retained, 2, 1), mode="drop")
    n_free = n_free - n_new
    active = bstate["slot_active"].at[slot].set(False)
    return {**bstate, "tbl": tbl, "ref": ref, "n_free": n_free,
            "slot_active": active}, pop_ids


def alloc_span(bstate: dict, lengths: jnp.ndarray, width: int,
               block_size: int, cap: int, ring: bool, cow: bool = False):
    """Ensure each active slot's table covers rows ``[lengths[b],
    lengths[b] + width)`` — the speculative round's write span (engine/
    spec.py): the draft writes up to ``width - 1`` rows past the slot's
    length and the verify forward one more, so the blocks are popped *once*
    per round here and every write inside the round (draft ``alloc_step``
    calls included) then finds its entry allocated and pops nothing.

    With ``cow=True`` (prefix caching composed with speculation) the span's
    *first* entry — the block holding row ``lengths[b]`` — may be a
    partially-filled prompt block shared through the prefix cache
    (``ref > 1``).  Only that entry can ever be shared: later span entries
    cover rows past the slot's length, and shared blocks only enter a table
    at admission, covering prompt rows ``< lengths``.  A shared first entry
    gets the same treatment ``alloc_step`` gives a shared decode target:
    pop a private block, rewire the table, drop one reference on the
    source, and report the pair so the round copies the block's rows
    *before* any draft/verify write lands (models/lm.py
    ``cow_copy_blocks``).

    Rows at or beyond ``cap`` need no block (their writes trash-route, and
    the engine only emits tokens whose positions fit).  Ring (SWA) tables
    are fully allocated at admission, so the ring case pops and copies
    nothing.  Pool exhaustion leaves entries unallocated (writes then
    trash-route); the engine's reservation ledger counts the speculative
    span — and one CoW spare per partial prefix hit — into each slot's
    worst case, so that path is unreachable in normal operation.  Blocks
    stay in the slot's table after a rejection rolls the length back — the
    slot grows into them, and ``release_slots`` returns them when it
    drains.

    Returns ``(bstate, cow_src [B], cow_dst [B], blocked [B])``:
    ``cow_src != cow_dst`` marks a slot whose first span block must be
    copied ``src -> dst`` (both are the trash index when nothing CoWed);
    ``blocked`` marks slots whose shared first block could NOT be copied
    (pool dry) — their table still points at the shared block, so the
    caller must mask them out of the round entirely rather than let a
    draft/verify write corrupt rows other owners read.
    """
    B = bstate["tbl"].shape[0]
    trash = bstate["free"].shape[0]
    no_copy = jnp.full((B,), trash, jnp.int32)
    if ring:
        return bstate, no_copy, no_copy, jnp.zeros((B,), bool)
    tbl, free, n_free = bstate["tbl"], bstate["free"], bstate["n_free"]
    ref = bstate["ref"]
    MB = tbl.shape[1]
    nbl = width // block_size + 2            # static: span-straddle bound
    jj = jnp.arange(nbl)[None, :]            # [1, nbl]
    j = lengths[:, None] // block_size + jj  # candidate table entries
    jc = jnp.clip(j, 0, MB - 1)
    in_span = (j * block_size < jnp.minimum(lengths[:, None] + width, cap)) \
        & (j < MB)
    cur = jnp.take_along_axis(tbl, jc, axis=1)
    if cow:
        shared = (bstate["slot_active"][:, None] & in_span & (jj == 0)
                  & (cur >= 0)
                  & (ref[jnp.clip(cur, 0, trash - 1)] > 1))
    else:
        shared = jnp.zeros((B, nbl), bool)
    need = bstate["slot_active"][:, None] & in_span & ((cur < 0) | shared)
    k = jnp.cumsum(need.reshape(-1).astype(jnp.int32)).reshape(B, nbl)
    ok = need & (k <= n_free)
    ids = free[jnp.clip(n_free - k, 0, trash - 1)]
    new_rows = jnp.where(ok, ids, cur)
    # per-row candidate entries ``j`` are distinct, so the scatter has no
    # duplicate targets; out-of-table entries drop, untouched entries
    # rewrite their own value
    tbl = tbl.at[jnp.arange(B)[:, None], j].set(
        jnp.where(in_span, new_rows, cur), mode="drop")
    ref = ref.at[jnp.where(ok, ids, trash)].set(1, mode="drop")
    # a successful CoW pop releases one reference on the shared source;
    # ref stays >= 1 there (the prefix index / other sharers still hold it)
    dec = shared & ok
    ref = ref.at[jnp.where(dec, cur, trash)].add(-1, mode="drop")
    n_free = n_free - jnp.sum(ok.astype(jnp.int32))
    cow_src = jnp.where(dec[:, 0], cur[:, 0], no_copy)
    cow_dst = jnp.where(dec[:, 0], new_rows[:, 0], no_copy)
    blocked = shared[:, 0] & ~ok[:, 0]
    return ({**bstate, "tbl": tbl, "ref": ref, "n_free": n_free},
            cow_src, cow_dst, blocked)


# ---------------------------------------------------------------------------
# Prefill-chunk write routing (no allocation: admission preallocated)
# ---------------------------------------------------------------------------

def span_targets(bstate: dict, start: jnp.ndarray, valid: jnp.ndarray,
                 width: int, block_size: int, cap: int, ring: bool,
                 shared_until=None):
    """Write targets for a prefill chunk: rows ``start[b] .. start[b] +
    valid[b] - 1`` map through the slot's (preallocated) table row.

    Returns ``(wblk [B, width], woff [B, width])``.  Rows beyond ``valid``,
    beyond capacity, or in unallocated entries are routed to the trash
    block — as are rows below ``shared_until[b]``, the slot's prefix-hit
    watermark: those positions live in blocks *shared* through the prefix
    cache (a matched row recomputed only for its logits), and the cached
    row already holds the identical KV, so the write is dropped instead of
    mutating a block other owners read.
    """
    tbl = bstate["tbl"]
    B, MB = tbl.shape
    NB = bstate["ref"].shape[0]
    jj = jnp.arange(width)[None, :]
    pos = start[:, None] + jj
    rpos = pos % cap if ring else pos
    ok = (jj < valid[:, None]) & (rpos < cap)
    if shared_until is not None:
        ok = ok & (pos >= shared_until[:, None])
    j = jnp.clip(rpos // block_size, 0, MB - 1)
    blk = jnp.take_along_axis(tbl, j, axis=1)
    ok = ok & (blk >= 0)
    wblk = jnp.where(ok, blk, NB)
    woff = rpos % block_size
    return wblk, woff


# ---------------------------------------------------------------------------
# Gather
# ---------------------------------------------------------------------------

def gather_blocks(pool: jnp.ndarray, tbl: jnp.ndarray,
                  layer) -> jnp.ndarray:
    """Layer ``layer`` of the stacked pool [n, NB+1, bs, Kv, hd] through
    the block table ``tbl`` [B, MB] -> [B, MB*bs, Kv, hd].

    The layer index is folded into the one gather, so the layer's pool is
    never sliced out.  Unallocated entries (-1) wrap to the trash block;
    callers mask by length, so its garbage never reaches the softmax.
    """
    B, MB = tbl.shape
    bs = pool.shape[2]
    g = pool[layer, tbl]                        # [B, MB, bs, Kv, hd]
    return g.reshape(B, MB * bs, *pool.shape[3:])
