"""Device-resident engine counters: an int32 pytree riding the scan carry.

The counters live in the slot-state tree (``state["ctr"]``, created by
``scheduler.init_slot_state``) and are bumped *inside* the jitted dispatch
at the point each event happens — the scan body in ``scheduler.py``, the
speculative round in ``spec.py``, and the jitted admission/eviction
entries in ``engine.py`` (allocator pops/releases are measured as
``n_free`` / ``ref`` deltas around the ``paged.py`` primitives).  Because
the state tree is already returned — and donated — at every dispatch
boundary, the host reads the counters in the same ``device_get`` that
drains the token grid: **zero** additional host syncs, and the only
compile-side effect is the state tree growing a few scalar leaves (a
deliberate, manifest-updated fingerprint change).

Counters are cumulative int32 scalars, zeroed at the start of each
``Engine.serve`` call; the engine exposes them as ``stats["counters"]``
and derives per-dispatch deltas host-side (the DepthController's
drafted/accepted feed).

Conservation identities (asserted under ``check_invariants=True`` and in
the hypothesis stress sweeps):

* ``drafted == accepted + rejected`` — every drafted position is either
  part of the verifier-agreement prefix or rolled back;
* ``blocks_popped - blocks_released == num_blocks - n_free`` — pops and
  releases account for every block currently out of the free stack
  ("popped == released + live").
"""
from __future__ import annotations

import jax.numpy as jnp

# One int32 scalar per key.  Every mutation site is listed next to its key.
COUNTER_KEYS = (
    "tokens",            # tokens emitted through the dispatch grids
                         # (decode emissions, speculative emissions, and
                         # first tokens of in-scan prefill completions;
                         # batched-prefill first tokens are host-side)
    "drafted",           # spec: draft positions proposed (depth x active)
    "accepted",          # spec: verifier-agreement prefix lengths summed
    "rejected",          # spec: drafted - accepted (rolled-back positions)
    "blocks_popped",     # pool blocks popped (decode alloc, span alloc,
                         # admission alloc — includes CoW pops)
    "blocks_released",   # pool blocks pushed back on the free stack
                         # (slot drains, zero-budget releases, eviction)
    "cow_copies",        # copy-on-write pops (a write into a shared block
                         # popped a private copy first)
    "prefix_hit_tokens", # prompt tokens served from the prefix cache —
                         # counted at admission as pf_start, the tokens
                         # actually skipped (== host stats["prefix_hits"])
    "chunk_pieces",      # in-scan prefill chunk pieces run
    "chunks_completed",  # prompts that finished in-scan prefill
    "blocked_retries",   # spec slots masked out of a round (CoW pop
                         # failed, pool dry) — they retry next round
    "kv_rows_read",      # pool K/V rows decode attention read, summed
                         # over attention layers and decode steps
                         # (lm.decode_rows_read: whole blocks up to each
                         # slot's length on the kernel path, the whole
                         # table on the gather path)
)

# Only for models with MoE layers (``init_counters(moe=True)``): bumped in
# the decode and chunked-prefill sub-steps of ``scheduler.py`` from the
# paged cache's ``moe_counts`` (``lm.run_stack_paged``).
MOE_COUNTER_KEYS = (
    "moe_assigned",      # (token, held expert) assignments, every layer
    "moe_rows",          # rows the grouped expert matmuls computed for
                         # them, tile padding included (moe.row_tile)
)


# Only for models with Mamba-2 layers (``init_counters(ssm=True)``): bumped
# in the decode sub-step of ``scheduler.py`` (lm.decode_ssm_states).
SSM_COUNTER_KEYS = (
    "ssm_kernel_states", # (slot, Mamba-2 layer) states the decode kernel
                         # updated (kernels.ssm_decode), summed over decode
                         # steps; 0 on the XLA path
)


def init_counters(moe: bool = False, ssm: bool = False) -> dict:
    """Zeroed counter pytree — strong int32 scalars (a weak-typed literal
    here would retrace every dispatch; see staticcheck weak-type rule)."""
    keys = (COUNTER_KEYS + (MOE_COUNTER_KEYS if moe else ())
            + (SSM_COUNTER_KEYS if ssm else ()))
    return {k: jnp.zeros((), jnp.int32) for k in keys}


def bump(ctr: dict, **deltas) -> dict:
    """Counters with ``deltas`` added (jit-safe; values are cast to int32
    so bool sums and traced scalars accumulate without dtype drift)."""
    out = dict(ctr)
    for k, d in deltas.items():
        out[k] = out[k] + jnp.asarray(d, jnp.int32)
    return out


def counter_totals(ctr_host: dict) -> dict:
    """Host-side view of a fetched counter tree as plain ints, in
    COUNTER_KEYS (then MOE_COUNTER_KEYS, SSM_COUNTER_KEYS) order (stable
    for snapshots and stats)."""
    return {k: int(ctr_host[k])
            for k in COUNTER_KEYS + MOE_COUNTER_KEYS + SSM_COUNTER_KEYS
            if k in ctr_host}
