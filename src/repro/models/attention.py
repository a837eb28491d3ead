"""Attention: GQA self-attention (train/prefill/decode), cross-attention.

The train/prefill path uses **chunked online-softmax attention** (a pure-JAX
flash-attention formulation): scores are computed per (q-chunk, kv-chunk)
tile with a running (max, denom, acc) carry, so peak memory is
O(B * H * q_chunk * kv_chunk) instead of O(B * H * S^2).  Fully-masked kv
chunks are skipped with ``lax.cond`` (causal upper triangle, sliding-window
lower band), recovering the ~2x causal FLOP saving inside the scan.

GQA is computed in grouped form — q is reshaped to [B, S, Kv, G, hd] and
contracted against un-repeated k/v [B, S, Kv, hd] — so KV heads are never
materialized H/Kv times.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import ops as PA
from repro.models.common import apply_rope, dense_init, rope_cos_sin, split
from repro.quant_runtime import qlinear

NEG_INF = -1e30
# Largest float32 score tile [B, heads, q_chunk, kv_chunk] of a paged
# prefill chunk: a wider batch of slots takes narrower kv tiles.
TILE_BYTES = 640 << 20


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attn(key, cfg: ModelConfig, dtype) -> dict:
    D, H, Kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, H * hd, dtype),
        "wk": dense_init(ks[1], D, Kv * hd, dtype),
        "wv": dense_init(ks[2], D, Kv * hd, dtype),
        "wo": dense_init(ks[3], H * hd, D, dtype),
    }
    if cfg.qkv_bias:
        p["bias_q"] = jnp.zeros((H * hd,), dtype)
        p["bias_k"] = jnp.zeros((Kv * hd,), dtype)
        p["bias_v"] = jnp.zeros((Kv * hd,), dtype)
    return p


def qkv_proj(p: dict, x: jnp.ndarray, cfg: ModelConfig):
    """x [B, S, D] -> q [B,S,H,hd], k/v [B,S,Kv,hd].

    A softmax scale other than 1/sqrt(hd) (``cfg.attn_scale``) is folded
    into q here, so that every attention path, which scales its scores by
    1/sqrt(hd), applies it."""
    B, S, _ = x.shape
    H, Kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = qlinear.matmul(x, p["wq"])
    k = qlinear.matmul(x, p["wk"])
    v = qlinear.matmul(x, p["wv"])
    if "bias_q" in p:
        q = q + p["bias_q"].astype(q.dtype)
        k = k + p["bias_k"].astype(k.dtype)
        v = v + p["bias_v"].astype(v.dtype)
    if cfg.attn_scale:
        q = (q.astype(jnp.float32) * (cfg.attn_scale * hd ** 0.5)
             ).astype(q.dtype)
    return (q.reshape(B, S, H, hd), k.reshape(B, S, Kv, hd),
            v.reshape(B, S, Kv, hd))


# ---------------------------------------------------------------------------
# Core tile: grouped-GQA scores + online softmax update
# ---------------------------------------------------------------------------

def _tile_scores(q, k, softcap: float):
    """q [B,cq,Kv,G,hd], k [B,ck,Kv,hd] -> scores fp32 [B,Kv,G,cq,ck]."""
    hd = q.shape[-1]
    s = jnp.einsum("bqkgh,bckh->bkgqc", q, k,
                   preferred_element_type=jnp.float32)
    s = s * (hd ** -0.5)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    return s


# ---------------------------------------------------------------------------
# Chunked attention (train / prefill)
# ---------------------------------------------------------------------------

def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      softcap: float = 0.0, q_offsets=None,
                      kv_offsets=None, kv_lengths=None, q_chunk: int = 0,
                      kv_chunk: int = 0) -> jnp.ndarray:
    """Flash attention (custom-VJP online softmax, models/flash.py).

    q [B,Sq,H,hd]; k,v [B,Skv,Kv,hd].  ``kv_lengths`` [B] masks kv padding.
    ``q_offsets`` / ``kv_offsets`` [B] place the rows at global positions
    ``off + i`` for the causal / sliding-window masks (paged prefill-chunk
    path); None means position 0.  Returns [B, Sq, H, hd] in q.dtype.
    Padding to the tile grid and the grouped-GQA reshape happen here;
    masking of padded kv rows rides the same mask row as ``kv_lengths``.
    """
    from repro.runtime import flags
    from repro.models.flash import flash_attention
    B, Sq, H, hd = q.shape
    Skv, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    cq = min(q_chunk or flags["q_chunk"], Sq)
    ck = min(kv_chunk or flags["kv_chunk"], Skv)
    nq, nk = -(-Sq // cq), -(-Skv // ck)
    pq, pk = nq * cq - Sq, nk * ck - Skv
    qg = q.reshape(B, Sq, Kv, G, hd)
    if pq:
        qg = jnp.pad(qg, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    valid = jnp.full((B,), Skv, jnp.int32) if kv_lengths is None \
        else kv_lengths.astype(jnp.int32)
    mask = (jnp.arange(nk * ck)[None, :] < valid[:, None]).astype(jnp.float32)
    # Head sharding: tile tensors inside flash inherit from q/k/v layouts.
    from repro.runtime import (_mesh_axes, attn_shard_specs, constrain,
                               kv_repeat_factor)
    r = kv_repeat_factor(Kv, G)
    if r > 1:  # repeat KV heads so the head axis divides the model axis
        # gather the sequence FIRST so the repeat stays local; resharding
        # seq-sharded -> head-sharded THROUGH the broadcast triggers
        # GSPMD "involuntary full rematerialization" (llama-vision train)
        from jax.sharding import PartitionSpec as P
        _, dp, msz = _mesh_axes()
        if msz and msz > 1:
            k = constrain(k, P(dp, None, None, None))
            v = constrain(v, P(dp, None, None, None))
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
        qg = qg.reshape(B, qg.shape[1], Kv * r, G // r, hd)
        Kv, G = Kv * r, G // r
    q_spec, kv_spec = attn_shard_specs(Kv, G)
    qg = constrain(qg, q_spec)
    k, v = constrain(k, kv_spec), constrain(v, kv_spec)
    q_off = (jnp.zeros((B,), jnp.int32) if q_offsets is None
             else q_offsets.astype(jnp.int32))
    kv_off = (jnp.zeros((B,), jnp.int32) if kv_offsets is None
              else kv_offsets.astype(jnp.int32))
    out = flash_attention(qg, k, v, mask, q_off, kv_off, causal, window,
                          softcap, cq, ck)
    out = constrain(out, q_spec)
    return out.reshape(B, nq * cq, H, hd)[:, :Sq]


# ---------------------------------------------------------------------------
# Decode attention (one new token against a cache)
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def decode_attention(q, k_cache, v_cache, lengths, *, window: int = 0,
                     softcap: float = 0.0) -> jnp.ndarray:
    """q [B,1,H,hd]; caches [B,S,Kv,hd]; lengths [B] = #valid entries
    (including the token just written).  Returns [B,1,H,hd]."""
    B, _, H, hd = q.shape
    S, Kv = k_cache.shape[1], k_cache.shape[2]
    if k_cache.dtype.itemsize == 1:  # fp8 cache: upcast at the dot input
        k_cache = k_cache.astype(jnp.bfloat16)
        v_cache = v_cache.astype(jnp.bfloat16)
    G = H // Kv
    qg = q.reshape(B, 1, Kv, G, hd)
    s = _tile_scores(qg, k_cache, softcap)[..., 0, :]   # [B,Kv,G,S]
    kv_pos = jnp.arange(S)[None]                         # [1, S]
    mask = kv_pos < lengths[:, None]
    if window > 0:
        mask = mask & (kv_pos > (lengths[:, None] - 1 - window))
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgc,bckh->bkgh", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def write_paged_kv(pk, pv, k_new, v_new, layer, wblk, woff):
    """Scatter new kv rows into layer ``layer`` of the stacked block pools
    ``[n, NB+1, bs, Kv, hd]``, in place: one decode row per slot (``k_new``
    [B,Kv,hd], targets ``wblk``/``woff`` [B] from
    ``engine.paged.alloc_step``) or a chunk's rows (``k_new`` [B,C,Kv,hd],
    targets [B,C] from ``engine.paged.span_targets``).  Inactive slots, pad
    rows, capacity overflows and shared-block rows point at the trash
    block."""
    pk = pk.at[layer, wblk, woff].set(k_new.astype(pk.dtype))
    pv = pv.at[layer, wblk, woff].set(v_new.astype(pv.dtype))
    return pk, pv


@jax.named_scope("attention")
def paged_prefill_attention(q, pk, pv, layer, k_new, v_new, tbl, start,
                            valid, *, sliding_window=0,
                            softcap=0.0) -> jnp.ndarray:
    """Prefill-chunk attention against the paged cache.

    q/k_new/v_new [B,C,{H|Kv},hd] are this chunk's rows at global positions
    ``start[b] + j`` (rows ``j >= valid[b]`` are pads); pools/tbl are the
    paged cache *before* the chunk's rows are written.  The kv buffer is
    assembled as gather(cache) overlaid with the fresh rows, so each query
    row attends exactly the prefix ``[0, pos]`` (window-banded for SWA) —
    and because flash's per-row online softmax treats trailing masked rows
    and omitted fully-masked leading tiles as exact identities, the result
    is **bitwise equal** to the corresponding rows of a one-shot prefill
    (given a same-dtype cache; fp8 caches trade that for memory).

    Rings gather **before** the write on purpose: a chunk's writes wrap the
    ring and would evict rows its own early queries still need.
    """
    from repro.engine.paged import gather_blocks
    B, C = k_new.shape[:2]
    bs = pk.shape[2]
    MB = tbl.shape[1]
    cap = MB * bs
    ring = bool(sliding_window) and cap == sliding_window
    if ring:
        W = sliding_window
        Wb = W + C
        base = jnp.maximum(start - (W - 1), 0)              # [B]
        pos = base[:, None] + jnp.arange(Wb)[None]          # [B, Wb]
        blk = jnp.take_along_axis(tbl, (pos % W) // bs, axis=1)
        off = (pos % W) % bs
        gk = pk[layer, blk, off]                            # [B,Wb,Kv,hd]
        gv = pv[layer, blk, off]
        kv_off = base
    else:
        pos = jnp.broadcast_to(jnp.arange(cap)[None], (B, cap))
        gk = gather_blocks(pk, tbl, layer)
        gv = gather_blocks(pv, tbl, layer)
        kv_off = jnp.zeros((B,), jnp.int32)
    rel = pos - start[:, None]                              # [B, Wb|cap]
    fresh = (rel >= 0) & (rel < C)
    idx = jnp.clip(rel, 0, C - 1)[..., None, None]
    fm = fresh[..., None, None]
    gk = jnp.where(fm, jnp.take_along_axis(k_new, idx, axis=1),
                   gk.astype(k_new.dtype))
    gv = jnp.where(fm, jnp.take_along_axis(v_new, idx, axis=1),
                   gv.astype(v_new.dtype))
    n_valid = start + valid - kv_off                        # local kv count
    from repro.runtime import flags
    ck = flags["kv_chunk"]
    while ck > 128 and B * q.shape[2] * C * ck * 4 > TILE_BYTES:
        ck //= 2
    return chunked_attention(q, gk, gv, causal=True, window=sliding_window,
                             softcap=softcap, q_offsets=start,
                             kv_offsets=kv_off, kv_lengths=n_valid,
                             kv_chunk=ck)


@jax.named_scope("attention")
def paged_verify_attention(q, pk, pv, layer, k_new, v_new, tbl, start,
                           valid, *, sliding_window=0,
                           softcap=0.0) -> jnp.ndarray:
    """Multi-token *verify* attention against the paged cache: row ``j`` of
    the chunk reproduces :func:`decode_attention` at position ``start + j``
    **operation for operation**.

    The speculative decoder (engine/spec.py) accepts a drafted token only
    when the verifier's logits agree with what the non-speculative engine
    would have computed at the same position.  On a TPU the linear layout
    runs the paged attention kernel that decode runs, whose row ``j`` is
    its one-row call at ``start + j`` bit for bit.  Elsewhere, and for
    rings, this path assembles the same kv buffer a decode step would see
    (block-table gather in slot order, fresh rows overlaid in pool dtype,
    the exact length/ring masks) and runs the plain-softmax decode math
    batched over the ``C`` chunk rows.  Either way greedy speculative
    serving stays token-exact against per-token decode.  The gather's
    memory is O(C * cap) per head group (ring: O(C * W) buffers); the
    chunk is ``n_spec + 1`` rows, so this stays small.
    """
    B, C = k_new.shape[:2]
    bs = pk.shape[2]
    MB = tbl.shape[1]
    cap = MB * bs
    H, hd = q.shape[2], q.shape[3]
    Kv = k_new.shape[2]
    G = H // Kv
    qg = q.reshape(B, C, Kv, G, hd)
    k_new = k_new.astype(pk.dtype)       # decode writes land in pool dtype
    v_new = v_new.astype(pv.dtype)
    ring = bool(sliding_window) and cap == sliding_window
    rows = jnp.arange(C)[None, :, None]                     # [1, C, 1]
    if ring:
        W = sliding_window
        slots = jnp.arange(W)[None, :]                      # [1, W]
        blk = tbl[:, (slots // bs)[0]]                      # [B, W]
        off = (slots % bs)[0]
        gk, gv = pk[layer, blk, off], pv[layer, blk, off]
        i_s = (slots - start[:, None]) % W                  # writing row
        fresh = (i_s[:, None, :] <= rows) & (i_s[:, None, :] < C)
        idx = jnp.clip(i_s, 0, C - 1)
        kf = jnp.take_along_axis(k_new, idx[..., None, None], axis=1)
        vf = jnp.take_along_axis(v_new, idx[..., None, None], axis=1)
        fm = fresh[..., None, None]                         # [B, C, W, 1, 1]
        kbuf = jnp.where(fm, kf[:, None], gk[:, None])      # [B,C,W,Kv,hd]
        vbuf = jnp.where(fm, vf[:, None], gv[:, None])
        eff = jnp.minimum(start[:, None] + rows[0, :, 0][None] + 1, W)
        mask = slots[:, None, :] < eff[..., None]           # [B, C, W]
        if kbuf.dtype.itemsize == 1:                        # fp8 cache
            kbuf = kbuf.astype(jnp.bfloat16)
            vbuf = vbuf.astype(jnp.bfloat16)
        s = jnp.einsum("bqkgh,bqckh->bkgqc", qg, kbuf,
                       preferred_element_type=jnp.float32) * (hd ** -0.5)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqc,bqckh->bqkgh", p.astype(vbuf.dtype), vbuf,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, C, H, hd).astype(q.dtype)
    # linear layout: the kernel on a TPU, else one shared buffer gathered
    # through the table, a row's own/later positions overlaid fresh, under
    # the per-row length mask (the exact mask decode_attention applies)
    return PA.paged_attention(q, k_new, v_new, pk, pv, layer, tbl, start,
                              window=sliding_window, softcap=softcap)


@jax.named_scope("attention")
def paged_decode_attention(q, pk, pv, layer, tbl, lengths, k_new, v_new, *,
                           sliding_window=0, softcap=0.0) -> jnp.ndarray:
    """Decode attention against layer ``layer`` of the stacked K/V pools.

    q [B,1,H,hd]; pools [n, NB+1, bs, Kv, hd], holding the step's new row
    ``k_new``/``v_new`` [B,1,Kv,hd] (pool dtype) at ``lengths``; ``tbl``
    [B, MB] block table.  On a TPU the kernel reads each slot's blocks up
    to its length and takes the new row as its fresh row
    (:mod:`repro.kernels.paged_attention`).  Elsewhere, and for the layouts
    the kernel does not serve, the gather reproduces the dense cache
    layout (linear positions, or ring positions when the table spans
    exactly the sliding window), so this is value-identical to
    :func:`decode_attention` on a contiguous cache.
    """
    def gather():
        from repro.engine.paged import gather_blocks
        gk = gather_blocks(pk, tbl, layer)
        gv = gather_blocks(pv, tbl, layer)
        cap = gk.shape[1]
        if sliding_window and cap == sliding_window:   # ring layout
            eff_len = jnp.minimum(lengths + 1, cap)
            return decode_attention(q, gk, gv, eff_len, softcap=softcap)
        return decode_attention(q, gk, gv, lengths + 1,
                                window=sliding_window, softcap=softcap)

    return PA.paged_attention(q, k_new, v_new, pk, pv, layer, tbl, lengths,
                              window=sliding_window, softcap=softcap,
                              ref=gather)


# ---------------------------------------------------------------------------
# Layer-level wrappers
# ---------------------------------------------------------------------------

def self_attn_train(p: dict, x: jnp.ndarray, cfg: ModelConfig, *,
                    causal: bool = True, positions=None) -> jnp.ndarray:
    """Full self-attention sublayer for train/prefill (no cache)."""
    B, S, D = x.shape
    q, k, v = qkv_proj(p, x, cfg)
    if positions is None:
        positions = jnp.arange(S)[None]
    if cfg.rope_theta > 0 and causal:  # RoPE for decoder stacks
        cos, sin = rope_cos_sin(positions, cfg.resolved_head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    out = chunked_attention(q, k, v, causal=causal, window=cfg.sliding_window,
                            softcap=cfg.attn_logit_softcap)
    return qlinear.matmul(out.reshape(B, S, -1), p["wo"])


def write_cache(cache_k, cache_v, k_new, v_new, lengths):
    """Scatter one new kv [B,1,Kv,hd] into caches at per-sample ``lengths``."""
    B = k_new.shape[0]
    bidx = jnp.arange(B)
    cache_k = cache_k.at[bidx, lengths].set(k_new[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[bidx, lengths].set(v_new[:, 0].astype(cache_v.dtype))
    return cache_k, cache_v


def self_attn_decode(p: dict, x: jnp.ndarray, cache: dict, cfg: ModelConfig):
    """One-token decode.  x [B,1,D]; cache {"k","v"} [B,S,Kv,hd] + lengths."""
    B = x.shape[0]
    q, k, v = qkv_proj(p, x, cfg)
    lengths = cache["lengths"]
    if cfg.rope_theta > 0:
        cos, sin = rope_cos_sin(lengths[:, None], cfg.resolved_head_dim,
                                cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    ck, cv = write_cache(cache["k"], cache["v"], k, v, lengths)
    out = decode_attention(q, ck, cv, lengths + 1,
                           window=cfg.sliding_window,
                           softcap=cfg.attn_logit_softcap)
    y = qlinear.matmul(out.reshape(B, 1, -1), p["wo"])
    return y, {"k": ck, "v": cv, "lengths": lengths}


# ---------------------------------------------------------------------------
# Cross-attention (VLM image layers, enc-dec decoder)
# ---------------------------------------------------------------------------

def cross_attn(p: dict, x: jnp.ndarray, memory: jnp.ndarray, cfg: ModelConfig,
               mem_lengths=None) -> jnp.ndarray:
    """x [B,Sq,D] attends to memory [B,Sm,D] (no causal mask, no RoPE)."""
    B, Sq, _ = x.shape
    Sm = memory.shape[1]
    H, Kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = qlinear.matmul(x, p["wq"]).reshape(B, Sq, H, hd)
    k = qlinear.matmul(memory, p["wk"]).reshape(B, Sm, Kv, hd)
    v = qlinear.matmul(memory, p["wv"]).reshape(B, Sm, Kv, hd)
    out = chunked_attention(q, k, v, causal=False, kv_lengths=mem_lengths)
    return qlinear.matmul(out.reshape(B, Sq, -1), p["wo"])


def cross_attn_cached(p: dict, x: jnp.ndarray, mem_k, mem_v, cfg: ModelConfig,
                      mem_lengths=None) -> jnp.ndarray:
    """Decode-time cross-attention against precomputed memory K/V."""
    B, Sq, _ = x.shape
    H, hd = cfg.n_heads, cfg.resolved_head_dim
    q = qlinear.matmul(x, p["wq"]).reshape(B, Sq, H, hd)
    Sm = mem_k.shape[1]
    lens = jnp.full((B,), Sm, jnp.int32) if mem_lengths is None else mem_lengths
    out = decode_attention(q, mem_k, mem_v, lens)
    return qlinear.matmul(out.reshape(B, Sq, -1), p["wo"])


def precompute_cross_kv(p: dict, memory: jnp.ndarray, cfg: ModelConfig):
    B, Sm, _ = memory.shape
    Kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    k = qlinear.matmul(memory, p["wk"]).reshape(B, Sm, Kv, hd)
    v = qlinear.matmul(memory, p["wv"]).reshape(B, Sm, Kv, hd)
    return k, v
