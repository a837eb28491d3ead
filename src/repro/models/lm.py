"""Model assembly for all six families (dense / moe / ssm / hybrid / encdec /
vlm) behind one functional API.

Layer stacking: every architecture is decomposed into a repeating **period**
of layer specs (the smallest repeating group — 1 layer for dense, 8 for
Jamba's 1:7 attn:mamba interleave, 5 for Llama-Vision's cross-attn cadence).
Params for each position in the period are stacked on a leading
``[n_periods, ...]`` axis and the forward pass ``lax.scan``s over periods, so
HLO size is O(period), not O(depth) — a 95-layer model lowers as fast as a
5-layer one.

The same layer code serves train, prefill and decode; decode threads a cache
pytree (stacked the same way) through the scan.  All matmuls route through
``qlinear`` so fp8-quantized parameter trees run the identical code path.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ssm_decode.ops import states_updated
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.common import (ACT_DTYPE, apply_mlp, apply_norm,
                                 chunked_xent, embed_tokens, init_embed,
                                 init_mlp, init_norm, last_token_logits,
                                 lm_logits, split)

LayerSpec = tuple[str, str]  # (mixer, ffn)


# ---------------------------------------------------------------------------
# Period layout per family
# ---------------------------------------------------------------------------

def layer_plan(cfg: ModelConfig):
    """Returns (prefix_specs, n_prefix, period_specs, n_periods)."""
    fam = cfg.family
    if fam == "dense":
        return [], 0, [("attn", "mlp")], cfg.n_layers
    if fam == "moe":
        k = cfg.first_k_dense
        prefix = [("attn", "mlp_dense")] if k else []
        return prefix, k, [("attn", "moe")], cfg.n_layers - k
    if fam == "ssm":
        return [], 0, [("mamba", "none")], cfg.n_layers
    if fam == "hybrid":
        per = cfg.attn_every
        specs = []
        for i in range(per):
            mixer = "attn" if i == per // 2 else "mamba"
            ffn = "moe" if (cfg.moe_every and i % cfg.moe_every == cfg.moe_offset) \
                else "mlp"
            specs.append((mixer, ffn))
        return [], 0, specs, cfg.n_layers // per
    if fam == "vlm":
        ce = cfg.cross_attn_every
        specs = [("attn", "mlp")] * (ce - 1) + [("cross", "mlp")]
        return [], 0, specs, cfg.n_layers // ce
    if fam == "encdec":
        # handled specially (two stacks); expose the decoder period here
        return [], 0, [("attn_cross", "mlp")], cfg.n_dec_layers
    raise ValueError(f"unknown family {cfg.family!r}")


def has_moe(cfg: ModelConfig) -> bool:
    """Whether any layer of the model has an MoE FFN."""
    prefix_specs, _, specs, _ = layer_plan(cfg)
    return any(ffn == "moe" for _, ffn in prefix_specs + specs)


def has_ssm(cfg: ModelConfig) -> bool:
    """Whether any layer of the model has a Mamba-2 mixer."""
    prefix_specs, _, specs, _ = layer_plan(cfg)
    return any(mixer == "mamba" for mixer, _ in prefix_specs + specs)


def _scaled(y, m: float):
    """``m * y`` in ``y``'s dtype: a muP multiplier (1 leaves ``y``)."""
    if m == 1.0:
        return y
    return (y.astype(jnp.float32) * m).astype(y.dtype)


def _embed(params, tokens, cfg: ModelConfig):
    return _scaled(embed_tokens(params["embed"], tokens),
                   cfg.embedding_multiplier)


def _final(params, x, cfg: ModelConfig):
    """Final norm, divided by ``logits_scaling``: the head's input, so that
    every head (tied, untied, chunked loss) gives logits / logits_scaling."""
    return _scaled(apply_norm(params["final_norm"], x, cfg.norm_eps),
                   1.0 / cfg.logits_scaling)


# ---------------------------------------------------------------------------
# Single-layer init / apply
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, spec: LayerSpec, dtype) -> dict:
    mixer, ffn = spec
    ks = split(key, 4)
    p: dict = {}
    if mixer in ("attn", "enc_attn"):
        p["ln1"] = init_norm(cfg, dtype)
        p["attn"] = A.init_attn(ks[0], cfg, dtype)
    elif mixer == "mamba":
        p["ln1"] = init_norm(cfg, dtype)
        p["mamba"] = SSM.init_mamba(ks[0], cfg, dtype)
    elif mixer == "cross":
        p["ln1"] = init_norm(cfg, dtype)
        p["cross"] = A.init_attn(ks[0], cfg, dtype)
    elif mixer == "attn_cross":
        p["ln1"] = init_norm(cfg, dtype)
        p["attn"] = A.init_attn(ks[0], cfg, dtype)
        p["ln_x"] = init_norm(cfg, dtype)
        p["xattn"] = A.init_attn(ks[3], cfg, dtype)
    else:
        raise ValueError(mixer)
    if ffn == "mlp":
        p["ln2"] = init_norm(cfg, dtype)
        p["mlp"] = init_mlp(ks[1], cfg, cfg.d_ff, dtype)
    elif ffn == "mlp_dense":
        p["ln2"] = init_norm(cfg, dtype)
        p["mlp"] = init_mlp(ks[1], cfg, cfg.d_ff_dense or cfg.d_ff, dtype)
    elif ffn == "moe":
        p["ln2"] = init_norm(cfg, dtype)
        p["moe"] = MOE.init_moe(ks[2], cfg, dtype)
    elif ffn != "none":
        raise ValueError(ffn)
    return p


def apply_layer_train(p: dict, x, cfg: ModelConfig, spec: LayerSpec, *,
                      memory=None):
    """Pre-norm residual layer.  Returns (x, aux_loss).

    Every sublayer output is constrained to the (dp, seq-sharded) residual
    layout BEFORE the residual add: the row-parallel out-projections then
    lower to reduce-scatter instead of all-reduce+slice (Megatron SP) —
    without this GSPMD all-reduces full [B,S,D] partials per sublayer
    (llama-vision train: 3.1 TB/chip of all-reduce observed)."""
    from repro.runtime import residual_constraint

    def rc(y):
        return residual_constraint(_scaled(y, cfg.residual_multiplier))
    mixer, ffn = spec
    aux = jnp.float32(0.0)
    h = apply_norm(p["ln1"], x, cfg.norm_eps)
    if mixer == "attn":
        x = x + rc(A.self_attn_train(p["attn"], h, cfg, causal=True))
    elif mixer == "enc_attn":
        x = x + rc(A.self_attn_train(p["attn"], h, cfg, causal=False))
    elif mixer == "mamba":
        x = x + rc(SSM.mamba_train(p["mamba"], h, cfg))
    elif mixer == "cross":
        x = x + rc(A.cross_attn(p["cross"], h, memory, cfg))
    elif mixer == "attn_cross":
        x = x + rc(A.self_attn_train(p["attn"], h, cfg, causal=True))
        hx = apply_norm(p["ln_x"], x, cfg.norm_eps)
        x = x + rc(A.cross_attn(p["xattn"], hx, memory, cfg))
    if ffn != "none":
        h2 = apply_norm(p["ln2"], x, cfg.norm_eps)
        if ffn == "moe":
            y, _ = MOE.apply_moe_dropless(p["moe"], h2, cfg)
            aux = cfg.router_aux_loss * MOE.load_balance_loss(
                p["moe"]["router"], h2)
            x = x + rc(y)
        else:
            x = x + rc(apply_mlp(p["mlp"], h2))
    return x, aux


# leaves of an attention layer's paged cache that are block pools
# ``[n_periods, num_blocks + 1, bs, Kv, hd]``; every other leaf is per slot
POOL_KEYS = ("pk", "pv")
# the leaf of a Mamba-2 layer's cache that decode updates at the layer in
# place (ssm.mamba_decode with ``layer``); prefill and verify slice it out
SSM_STATE_KEYS = ("h",)


def _kv_eff(cfg: ModelConfig) -> int:
    """Effective KV heads in decode caches (GQA repeat-sharding — see
    runtime.kv_repeat_factor): Kv*r so the cache head axis shards over
    `model` instead of replicating."""
    from repro.runtime import kv_repeat_factor
    Kv = cfg.n_kv_heads
    if not Kv:
        return 0
    return Kv * kv_repeat_factor(Kv, cfg.n_heads // Kv, for_cache=True)


def _cache_dtype():
    from repro.runtime import flags
    return jnp.dtype(flags["kv_cache_dtype"])


def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     cache_len: int, mem_len: int = 0) -> dict:
    """Zero decode cache for one layer.  SWA layers use a ring of window size."""
    mixer, _ = spec
    Kv, hd = _kv_eff(cfg), cfg.resolved_head_dim
    cdt = _cache_dtype()
    if mixer in ("attn", "enc_attn"):
        sc = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
        return {"k": jnp.zeros((batch, sc, Kv, hd), cdt),
                "v": jnp.zeros((batch, sc, Kv, hd), cdt)}
    if mixer == "mamba":
        return SSM.init_mamba_cache(cfg, batch)
    if mixer == "cross":
        return {"mk": jnp.zeros((batch, mem_len, Kv, hd), cdt),
                "mv": jnp.zeros((batch, mem_len, Kv, hd), cdt)}
    if mixer == "attn_cross":
        sc = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
        return {"k": jnp.zeros((batch, sc, Kv, hd), cdt),
                "v": jnp.zeros((batch, sc, Kv, hd), cdt),
                "mk": jnp.zeros((batch, mem_len, Kv, hd), cdt),
                "mv": jnp.zeros((batch, mem_len, Kv, hd), cdt)}
    raise ValueError(mixer)


def init_layer_cache_paged(cfg: ModelConfig, spec: LayerSpec, batch: int,
                           num_blocks: int, block_size: int) -> dict:
    """Paged decode cache for one layer: attention K/V become global block
    pools ``pk``/``pv`` ``[num_blocks + 1, bs, Kv, hd]`` (the last block is
    the trash sink — engine/paged.py); SSM state has no sequence axis to
    page and stays per-slot dense."""
    mixer, _ = spec
    Kv, hd = _kv_eff(cfg), cfg.resolved_head_dim
    cdt = _cache_dtype()
    if mixer in ("attn", "enc_attn"):
        return {"pk": jnp.zeros((num_blocks + 1, block_size, Kv, hd), cdt),
                "pv": jnp.zeros((num_blocks + 1, block_size, Kv, hd), cdt)}
    if mixer == "mamba":
        return SSM.init_mamba_cache(cfg, batch)
    raise ValueError(f"paged cache not supported for mixer {mixer!r}")


def _ring_write(cache_k, cache_v, k_new, v_new, lengths):
    """Write one kv into a ring cache at slot lengths % capacity."""
    cap = cache_k.shape[1]
    bidx = jnp.arange(k_new.shape[0])
    slot = lengths % cap
    ck = cache_k.at[bidx, slot].set(k_new[:, 0].astype(cache_k.dtype))
    cv = cache_v.at[bidx, slot].set(v_new[:, 0].astype(cache_v.dtype))
    return ck, cv


def _attn_decode(p, x, cache, lengths, cfg: ModelConfig):
    """Self-attn decode honoring ring (SWA) vs full caches."""
    B = x.shape[0]
    q, k, v = A.qkv_proj(p, x, cfg)
    if cfg.rope_theta > 0:
        cos, sin = A.rope_cos_sin(lengths[:, None], cfg.resolved_head_dim,
                                  cfg.rope_theta)
        q = A.apply_rope(q, cos, sin)
        k = A.apply_rope(k, cos, sin)
    r = _kv_eff(cfg) // cfg.n_kv_heads
    if r > 1:  # repeat-sharded cache (see _kv_eff)
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
    cap = cache["k"].shape[1]
    if cfg.sliding_window and cap == cfg.sliding_window:
        ck, cv = _ring_write(cache["k"], cache["v"], k, v, lengths)
        eff_len = jnp.minimum(lengths + 1, cap)
        out = A.decode_attention(q, ck, cv, eff_len, softcap=cfg.attn_logit_softcap)
    else:
        ck, cv = A.write_cache(cache["k"], cache["v"], k, v, lengths)
        out = A.decode_attention(q, ck, cv, lengths + 1,
                                 window=cfg.sliding_window,
                                 softcap=cfg.attn_logit_softcap)
    from repro.quant_runtime import qlinear
    y = qlinear.matmul(out.reshape(B, 1, -1), p["wo"])
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = ck, cv
    return y, new_cache


def cow_copy_blocks(pcache: dict, src, dst):
    """Copy-on-write for the paged pools: copy block ``src[b]`` into block
    ``dst[b]`` in every attention layer's ``pk``/``pv`` pool (``stack`` and
    ``prefix`` groups; pool leaves are ``[n_periods, num_blocks + 1, bs,
    Kv, hd]``), all layers of a pool at once.

    This materializes the private copy that ``alloc_step(..., cow=True)``
    (a decode step) or ``alloc_span(..., cow=True)`` (a speculative round,
    engine/spec.py) rewired a slot's shared block to; it must land before
    the step's or round's first write into the block, so callers run it
    ahead of the layer scan.  Slots without a CoW carry ``src == dst`` (the
    slot's own write block, or the trash block), whose copy would be the
    identity, so only the copying slots are visited: a loop of in-place
    block updates whose trip count is their number (none on most steps).
    A ``lax.cond`` over the pools would copy them whole to pass them
    through, and one scatter of every slot's block makes the TPU relayout
    the pools around it.  The copying slots' ``src`` blocks are shared
    (``ref > 1``) and their ``dst`` blocks fresh pops, so no copy reads
    another's destination and the order is immaterial.
    """
    cow = src != dst
    order = jnp.argsort(~cow, stable=True)          # the copying slots first
    src, dst = src[order], dst[order]

    def copy_one(i, groups):
        def copy(leaf):
            blk = jax.lax.dynamic_slice_in_dim(leaf, src[i], 1, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(leaf, blk, dst[i],
                                                       axis=1)
        return {g: {lk: {n: copy(l) if n in POOL_KEYS else l
                         for n, l in lv.items()}
                    for lk, lv in grp.items()}
                for g, grp in groups.items()}

    groups = {g: pcache[g] for g in ("stack", "prefix") if g in pcache}
    return {**pcache,
            **jax.lax.fori_loop(0, jnp.sum(cow), copy_one, groups)}


def decode_rows_read(pcache: dict, cfg: ModelConfig):
    """Pool rows (K and V alike, int32) one paged decode step's attention
    reads over all its attention layers and slots, at the step's
    ``pcache["lengths"]``; 0 without pools."""
    from repro.kernels.paged_attention.ops import rows_read
    total = jnp.zeros((), jnp.int32)
    for g in ("prefix", "stack"):
        for lc in pcache.get(g, {}).values():
            if "pk" in lc:
                total += lc["pk"].shape[0] * rows_read(
                    pcache["lengths"], lc["pk"], pcache["tbl"],
                    window=cfg.sliding_window)
    return total


def decode_ssm_states(pcache: dict, keep):
    """(slot, Mamba-2 layer) states one paged decode step updates through
    the decode kernel (int32; ``kernels.ssm_decode.ops.states_updated``):
    the layers times the slots in ``keep`` on the kernel path, 0 on the
    XLA path."""
    total = jnp.zeros((), jnp.int32)
    for g in ("prefix", "stack"):
        for lc in pcache.get(g, {}).values():
            if "h" in lc:
                total += states_updated(lc["h"], keep)
    return total


def _attn_decode_paged(p, x, cache, pctx, cfg: ModelConfig):
    """Self-attn decode against the paged block pool.

    Mirrors :func:`_attn_decode` exactly for active slots: the new K/V lands
    at the slot's write target (``pctx["wblk"]/["woff"]``, precomputed once
    per step — trash block for inactive slots), and attention reads the
    slot's rows through the block table (:func:`A.paged_decode_attention`:
    on a TPU the kernel, up to the slot's length; else the gather, which
    reproduces the dense cache layout value-for-value).  ``cache["pk"]``
    / ``["pv"]`` are the whole stacked pools; the layer's rows are
    addressed at ``pctx["layer"]`` (:func:`run_stack_paged`)."""
    B = x.shape[0]
    lengths, layer = pctx["lengths"], pctx["layer"]
    q, k, v = A.qkv_proj(p, x, cfg)
    if cfg.rope_theta > 0:
        cos, sin = A.rope_cos_sin(lengths[:, None], cfg.resolved_head_dim,
                                  cfg.rope_theta)
        q = A.apply_rope(q, cos, sin)
        k = A.apply_rope(k, cos, sin)
    r = _kv_eff(cfg) // cfg.n_kv_heads
    if r > 1:  # repeat-sharded cache (see _kv_eff)
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
    pk, pv = A.write_paged_kv(cache["pk"], cache["pv"], k[:, 0], v[:, 0],
                              layer, pctx["wblk"], pctx["woff"])
    out = A.paged_decode_attention(q, pk, pv, layer, pctx["tbl"], lengths,
                                   k.astype(pk.dtype), v.astype(pv.dtype),
                                   sliding_window=cfg.sliding_window,
                                   softcap=cfg.attn_logit_softcap)
    from repro.quant_runtime import qlinear
    y = qlinear.matmul(out.reshape(B, 1, -1), p["wo"])
    return y, {**cache, "pk": pk, "pv": pv}


def _attn_prefill_paged(p, x, cache, pctx, cfg: ModelConfig):
    """Self-attn over one prefill chunk against the paged pool: the chunk's
    rows (global positions ``start[b] + j``) attend the slot's cached
    prefix plus themselves, then land in the pool at the precomputed span
    targets (pads / overflows / shared blocks route to trash)."""
    B, C, _ = x.shape
    q, k, v = A.qkv_proj(p, x, cfg)
    if cfg.rope_theta > 0:
        pos = pctx["start"][:, None] + jnp.arange(C)[None]
        cos, sin = A.rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)
        q = A.apply_rope(q, cos, sin)
        k = A.apply_rope(k, cos, sin)
    r = _kv_eff(cfg) // cfg.n_kv_heads
    if r > 1:  # repeat-sharded cache (see _kv_eff)
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
    out = A.paged_prefill_attention(q, cache["pk"], cache["pv"],
                                    pctx["layer"], k, v, pctx["tbl"],
                                    pctx["start"], pctx["valid"],
                                    sliding_window=cfg.sliding_window,
                                    softcap=cfg.attn_logit_softcap)
    pk, pv = A.write_paged_kv(cache["pk"], cache["pv"], k, v, pctx["layer"],
                              pctx["wblk"], pctx["woff"])
    from repro.quant_runtime import qlinear
    y = qlinear.matmul(out.reshape(B, C, -1), p["wo"])
    return y, {**cache, "pk": pk, "pv": pv}


def _ffn_serve(p: dict, x, cfg: ModelConfig, ffn: str, valid=None):
    """The FFN half of a serving layer: (x, MoE counts or None).  MoE runs
    dropless over the held experts (moe.apply_moe_dropless), so a token's
    output never depends on the other rows of the batch; ``valid`` marks
    the rows that are routed (pads and idle slots are not)."""
    if ffn == "none":
        return x, None
    h2 = apply_norm(p["ln2"], x, cfg.norm_eps)
    counts = None
    if ffn == "moe":
        y, counts = MOE.apply_moe_dropless(p["moe"], h2, cfg, valid)
    else:
        y = apply_mlp(p["mlp"], h2)
    return x + _scaled(y, cfg.residual_multiplier), counts


def _state_barrier(x, cache):
    """A prefill layer's recurrent state is computed before the next layer
    starts: left to the end of the layer scan's body, every layer's [B, C,
    ...] inputs to it would stay live together.  The one-shot and the
    chunked prefill both place it, so the compiler fuses the two alike
    (chunked prefill stays bit-exact with one-shot)."""
    return jax.lax.optimization_barrier((x, cache))


def _rows(pctx: dict, C: int):
    """[B, C] real rows of a chunk (``valid`` per slot)."""
    return jnp.arange(C)[None, :] < pctx["valid"][:, None]


def apply_layer_prefill_paged(p: dict, x, cache: dict, pctx: dict,
                              cfg: ModelConfig, spec: LayerSpec):
    """Prefill-chunk variant of :func:`apply_layer_decode_paged`: attention
    writes the chunk's rows into the pool, Mamba/SSM layers thread their
    per-slot recurrent state chunk-to-chunk.  Returns (x, cache, MoE
    counts or None)."""
    mixer, ffn = spec
    h = apply_norm(p["ln1"], x, cfg.norm_eps)
    if mixer in ("attn", "enc_attn"):
        y, cache = _attn_prefill_paged(p["attn"], h, cache, pctx, cfg)
    elif mixer == "mamba":
        y, cache = SSM.mamba_prefill_chunk(p["mamba"], x, h, cfg, cache,
                                           pctx["valid"])
    else:
        raise ValueError(f"paged prefill not supported for mixer {mixer!r}")
    x = x + _scaled(y, cfg.residual_multiplier)
    x, counts = _ffn_serve(p, x, cfg, ffn, _rows(pctx, x.shape[1]))
    if mixer == "mamba":
        x, cache = _state_barrier(x, cache)
    return x, cache, counts


def _attn_verify_paged(p, x, cache, pctx, cfg: ModelConfig):
    """Self-attn over one speculative-verify chunk: row ``j`` mirrors a
    decode step at position ``start[b] + j`` operation-for-operation
    (attention.paged_verify_attention), then the chunk's rows land at the
    precomputed span targets (pads / overflows route to trash)."""
    B, C, _ = x.shape
    q, k, v = A.qkv_proj(p, x, cfg)
    if cfg.rope_theta > 0:
        pos = pctx["start"][:, None] + jnp.arange(C)[None]
        cos, sin = A.rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)
        q = A.apply_rope(q, cos, sin)
        k = A.apply_rope(k, cos, sin)
    r = _kv_eff(cfg) // cfg.n_kv_heads
    if r > 1:  # repeat-sharded cache (see _kv_eff)
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
    out = A.paged_verify_attention(q, cache["pk"], cache["pv"],
                                   pctx["layer"], k, v, pctx["tbl"],
                                   pctx["start"], pctx["valid"],
                                   sliding_window=cfg.sliding_window,
                                   softcap=cfg.attn_logit_softcap)
    pk, pv = A.write_paged_kv(cache["pk"], cache["pv"], k, v, pctx["layer"],
                              pctx["wblk"], pctx["woff"])
    from repro.quant_runtime import qlinear
    y = qlinear.matmul(out.reshape(B, C, -1), p["wo"])
    return y, {**cache, "pk": pk, "pv": pv}


def apply_layer_verify_paged(p: dict, x, cache: dict, pctx: dict,
                             cfg: ModelConfig, spec: LayerSpec):
    """Speculative-verify variant of :func:`apply_layer_decode_paged`: each
    chunk row reproduces per-token decode bitwise — attention mirrors the
    decode softmax over the gathered-and-overlaid table, Mamba/SSM layers
    step the exact recurrence (ssm.mamba_verify_chunk), and MoE is dropless
    as in decode."""
    mixer, ffn = spec
    h = apply_norm(p["ln1"], x, cfg.norm_eps)
    if mixer in ("attn", "enc_attn"):
        y, cache = _attn_verify_paged(p["attn"], h, cache, pctx, cfg)
    elif mixer == "mamba":
        y, cache = SSM.mamba_verify_chunk(p["mamba"], x, h, cfg, cache,
                                          pctx["valid"])
    else:
        raise ValueError(f"speculative verify not supported for mixer "
                         f"{mixer!r}")
    x = x + _scaled(y, cfg.residual_multiplier)
    x, counts = _ffn_serve(p, x, cfg, ffn, _rows(pctx, x.shape[1]))
    return x, cache, counts


def apply_layer_decode_paged(p: dict, x, cache: dict, pctx: dict,
                             cfg: ModelConfig, spec: LayerSpec):
    """Paged variant of :func:`apply_layer_decode`; Mamba/SSM layers keep
    their contiguous per-slot state and are routed around the pool: they
    update the stacked state at ``pctx["layer"]`` in place.  Only the slots
    in ``pctx["keep"]`` (all when absent) are routed to experts and step
    their state.  Returns (x, cache, MoE counts or None)."""
    mixer, ffn = spec
    h = apply_norm(p["ln1"], x, cfg.norm_eps)
    if mixer in ("attn", "enc_attn"):
        y, cache = _attn_decode_paged(p["attn"], h, cache, pctx, cfg)
    elif mixer == "mamba":
        y, cache = SSM.mamba_decode(p["mamba"], h, cache, cfg,
                                    layer=pctx["layer"], keep=pctx.get("keep"))
    else:
        raise ValueError(f"paged decode not supported for mixer {mixer!r}")
    x = x + _scaled(y, cfg.residual_multiplier)
    keep = pctx.get("keep")
    x, counts = _ffn_serve(p, x, cfg, ffn,
                           None if keep is None else keep[:, None])
    return x, cache, counts


def apply_layer_decode(p: dict, x, cache: dict, lengths, cfg: ModelConfig,
                       spec: LayerSpec):
    mixer, ffn = spec
    h = apply_norm(p["ln1"], x, cfg.norm_eps)
    if mixer in ("attn", "enc_attn"):
        y, cache = _attn_decode(p["attn"], h, cache, lengths, cfg)
        x = x + _scaled(y, cfg.residual_multiplier)
    elif mixer == "mamba":
        y, cache = SSM.mamba_decode(p["mamba"], h, cache, cfg)
        x = x + _scaled(y, cfg.residual_multiplier)
    elif mixer == "cross":
        x = x + A.cross_attn_cached(p["cross"], h, cache["mk"], cache["mv"], cfg)
    elif mixer == "attn_cross":
        sub = {"k": cache["k"], "v": cache["v"]}
        y, sub = _attn_decode(p["attn"], h, sub, lengths, cfg)
        x = x + y
        cache = {**cache, "k": sub["k"], "v": sub["v"]}
        hx = apply_norm(p["ln_x"], x, cfg.norm_eps)
        x = x + A.cross_attn_cached(p["xattn"], hx, cache["mk"], cache["mv"], cfg)
    x, _ = _ffn_serve(p, x, cfg, ffn)
    return x, cache


def apply_layer_prefill(p: dict, x, cfg: ModelConfig, spec: LayerSpec, *,
                        memory=None, cache_len: int = 0):
    """Like train, but also returns the layer's decode cache."""
    mixer, ffn = spec
    B, S, _ = x.shape
    h = apply_norm(p["ln1"], x, cfg.norm_eps)
    cache: dict = {}
    if mixer in ("attn", "enc_attn", "attn_cross"):
        causal = mixer != "enc_attn"
        q, k, v = A.qkv_proj(p["attn"], h, cfg)
        if cfg.rope_theta > 0 and causal:
            pos = jnp.arange(S)[None]
            cos, sin = A.rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)
            q, k = A.apply_rope(q, cos, sin), A.apply_rope(k, cos, sin)
        out = A.chunked_attention(q, k, v, causal=causal,
                                  window=cfg.sliding_window,
                                  softcap=cfg.attn_logit_softcap)
        from repro.quant_runtime import qlinear
        x = x + _scaled(qlinear.matmul(out.reshape(B, S, -1), p["attn"]["wo"]),
                        cfg.residual_multiplier)
        r = _kv_eff(cfg) // cfg.n_kv_heads
        if r > 1:  # repeat-sharded cache layout
            k = jnp.repeat(k, r, axis=2)
            v = jnp.repeat(v, r, axis=2)
        cdt = _cache_dtype()
        sc = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
        ck = jnp.zeros((B, sc, _kv_eff(cfg), cfg.resolved_head_dim), cdt)
        cv = jnp.zeros_like(ck)
        if cfg.sliding_window and sc == cfg.sliding_window:
            n = min(S, sc)
            positions = jnp.arange(S - n, S)
            slots = positions % sc
            ck = ck.at[:, slots].set(k[:, S - n:].astype(cdt))
            cv = cv.at[:, slots].set(v[:, S - n:].astype(cdt))
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k[:, :sc].astype(cdt), 0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v[:, :sc].astype(cdt), 0, axis=1)
        cache["k"], cache["v"] = ck, cv
        if mixer == "attn_cross":
            hx = apply_norm(p["ln_x"], x, cfg.norm_eps)
            x = x + A.cross_attn(p["xattn"], hx, memory, cfg)
            mk, mv = A.precompute_cross_kv(p["xattn"], memory, cfg)
            if r > 1:
                mk = jnp.repeat(mk, r, axis=2)
                mv = jnp.repeat(mv, r, axis=2)
            cache["mk"], cache["mv"] = (mk.astype(_cache_dtype()),
                                        mv.astype(_cache_dtype()))
    elif mixer == "mamba":
        y, cache = SSM.mamba_forward(p["mamba"], x, h, cfg, cache=None)
        x = x + _scaled(y, cfg.residual_multiplier)
    elif mixer == "cross":
        x = x + A.cross_attn(p["cross"], h, memory, cfg)
        mk, mv = A.precompute_cross_kv(p["cross"], memory, cfg)
        rx = _kv_eff(cfg) // cfg.n_kv_heads
        if rx > 1:
            mk = jnp.repeat(mk, rx, axis=2)
            mv = jnp.repeat(mv, rx, axis=2)
        cache = {"mk": mk.astype(_cache_dtype()),
                 "mv": mv.astype(_cache_dtype())}
    x, _ = _ffn_serve(p, x, cfg, ffn)
    if mixer == "mamba":
        x, cache = _state_barrier(x, cache)
    return x, cache


# ---------------------------------------------------------------------------
# Stacked-period scans
# ---------------------------------------------------------------------------

def _init_stack(key, cfg, specs, n: int, dtype):
    """Stacked params: {"L{i}": leaf[n, ...]} via vmapped per-layer init."""
    def one_period(k):
        kk = split(k, len(specs))
        return {f"L{i}": init_layer(kk[i], cfg, specs[i], dtype)
                for i in range(len(specs))}
    return jax.vmap(one_period)(jax.random.split(key, n))


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots_saveable":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn)  # "full"


def run_stack_train(stack, x, cfg, specs, *, memory=None, remat="full"):
    from repro.runtime import flags, residual_constraint

    def body(carry, lp):
        h, aux = carry
        h = residual_constraint(h)
        for i, spec in enumerate(specs):
            h, a = apply_layer_train(lp[f"L{i}"], h, cfg, spec, memory=memory)
            aux = aux + a
        h = residual_constraint(h)
        return (h, aux), None

    if flags["unroll_layers"]:  # eager per-layer walk (calibration/debug)
        n = jax.tree.leaves(stack)[0].shape[0]
        carry = (x, jnp.float32(0.0))
        for t in range(n):
            carry, _ = body(carry, jax.tree.map(lambda l: l[t], stack))
        return carry
    (x, aux), _ = jax.lax.scan(_remat(body, remat), (x, jnp.float32(0.0)), stack)
    return x, aux


def run_stack_decode(stack, cache, x, lengths, cfg, specs):
    def body(h, xs):
        lp, lc = xs
        nc = {}
        for i, spec in enumerate(specs):
            h, nci = apply_layer_decode(lp[f"L{i}"], h, lc[f"L{i}"], lengths,
                                        cfg, spec)
            nc[f"L{i}"] = nci
        return h, nc
    x, new_cache = jax.lax.scan(body, x, (stack, cache))
    return x, new_cache


def run_stack_paged(apply_layer, stack, cache, x, pctx, cfg, specs,
                    at_layer=POOL_KEYS):
    """Layer scan of a paged forward pass: ``apply_layer`` is
    :func:`apply_layer_decode_paged`, :func:`apply_layer_prefill_paged` or
    :func:`apply_layer_verify_paged`.  Returns (x, cache, MoE counts
    int32 [2]: assignments and grouped-matmul rows, summed over layers;
    None without MoE layers).

    The whole stacked cache rides the scan's carry and the layer index is
    scanned, so no leaf is sliced out of the scan's inputs or written back
    through its outputs, and the donated buffers alias through the
    dispatch.  The leaves named in ``at_layer`` are addressed at the layer
    inside the mixer: the K/V pools (``pk``/``pv``, ``[n_periods, NB+1,
    bs, Kv, hd]``) in attention, where each layer scatters its rows into
    ``pool[layer]`` and gathers ``pool[layer][tbl]`` with the index folded
    into the gather, and, in decode, the SSM state ``h``
    (:data:`SSM_STATE_KEYS`), which Mamba-2 steps at the layer.  Every
    other per-slot leaf ``[n_periods, B, ...]`` is read at the layer and
    written back there in place; slots outside ``pctx["keep"]`` (when
    given) keep their state.  The write targets / block table in ``pctx``
    are shared by every layer (all layers advance in lockstep) and ride
    the closure."""
    n = jax.tree.leaves(stack)[0].shape[0]
    keep = pctx.get("keep")
    moe = any(ffn == "moe" for _, ffn in specs)

    def body(carry, xs):
        h, cache, counts = carry
        lp, layer = xs
        lctx = {**pctx, "layer": layer}
        new_cache = {}
        for i, spec in enumerate(specs):
            lk = f"L{i}"
            state = {k: jax.lax.dynamic_index_in_dim(v, layer, 0, False)
                     for k, v in cache[lk].items() if k not in at_layer}
            h, nc, cnt = apply_layer(lp[lk], h, {**cache[lk], **state},
                                     lctx, cfg, spec)
            new = {k: nc[k].astype(v.dtype) for k, v in state.items()}
            if keep is not None:
                new = {k: jnp.where(
                    keep.reshape(keep.shape + (1,) * (v.ndim - 1)), v,
                    state[k]) for k, v in new.items()}
            new_cache[lk] = {
                k: (nc[k] if k in at_layer else
                    jax.lax.dynamic_update_index_in_dim(v, new[k], layer, 0))
                for k, v in cache[lk].items()}
            if cnt is not None:
                counts = counts + cnt
        return (h, new_cache, counts), None

    (x, cache, counts), _ = jax.lax.scan(
        body, (x, cache, jnp.zeros((2,), jnp.int32) if moe else None),
        (stack, jnp.arange(n, dtype=jnp.int32)))
    return x, cache, counts


def run_stack_prefill(stack, x, cfg, specs, *, memory=None, cache_len=0):
    def body(h, lp):
        caches = {}
        for i, spec in enumerate(specs):
            h, c = apply_layer_prefill(lp[f"L{i}"], h, cfg, spec,
                                       memory=memory, cache_len=cache_len)
            caches[f"L{i}"] = c
        return h, caches
    x, cache = jax.lax.scan(body, x, stack)
    return x, cache


def _stack_cache(cfg, specs, n, batch, cache_len, mem_len=0):
    one = {f"L{i}": init_layer_cache(cfg, specs[i], batch, cache_len, mem_len)
           for i in range(len(specs))}
    return jax.tree.map(lambda l: jnp.broadcast_to(l, (n,) + l.shape), one)


def _stack_cache_paged(cfg, specs, n, batch, num_blocks, block_size):
    one = {f"L{i}": init_layer_cache_paged(cfg, specs[i], batch, num_blocks,
                                           block_size)
           for i in range(len(specs))}
    return jax.tree.map(lambda l: jnp.broadcast_to(l, (n,) + l.shape), one)


# ---------------------------------------------------------------------------
# Model facade
# ---------------------------------------------------------------------------

@dataclass
class Model:
    cfg: ModelConfig
    init: Callable
    loss_fn: Callable            # (params, batch, remat=) -> (loss, metrics)
    init_cache: Callable         # (batch, cache_len, **kw) -> cache
    prefill: Callable            # (params, batch, cache_len=, lengths=) ->
                                 #   (logits_last, cache); ``lengths`` [B]
                                 #   marks per-row true lengths of a
                                 #   right-padded batch (engine prefill)
    decode_step: Callable        # (params, tokens, cache) -> (logits, cache)
    init_paged_cache: Callable | None = None
                                 # (batch, cache_len, block_size=,
                                 #  num_blocks=) -> paged cache
    decode_step_paged: Callable | None = None
                                 # (params, tokens, paged cache, cow=) ->
                                 #   (logits, paged cache)
    prefill_chunk_paged: Callable | None = None
                                 # (params, tokens [B,C], paged cache,
                                 #  start [B], valid [B]) ->
                                 #   (last-valid-row logits [B,V], cache)
    verify_chunk_paged: Callable | None = None
                                 # (params, tokens [B,C], paged cache,
                                 #  start [B], valid [B]) ->
                                 #   (all-row logits [B,C,V], cache);
                                 #   row j bitwise-mirrors a decode step at
                                 #   position start+j (speculative verify)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "encdec":
        return _build_encdec(cfg)
    dtype = jnp.dtype(cfg.dtype)
    prefix_specs, n_prefix, specs, n_periods = layer_plan(cfg)

    def init(key):
        ks = split(key, 4)
        p = {"embed": init_embed(ks[0], cfg, dtype),
             "stack": _init_stack(ks[1], cfg, specs, n_periods, dtype),
             "final_norm": init_norm(cfg, dtype)}
        if n_prefix:
            p["prefix"] = _init_stack(ks[2], cfg, prefix_specs, n_prefix, dtype)
        return p

    def _memory(params, batch):
        if cfg.family == "vlm":
            return batch["image_embeds"].astype(ACT_DTYPE)
        return None

    def loss_fn(params, batch, remat: str = "full"):
        x = _embed(params, batch["tokens"], cfg)
        mem = _memory(params, batch)
        aux = jnp.float32(0.0)
        if n_prefix:
            x, a = run_stack_train(params["prefix"], x, cfg, prefix_specs,
                                   memory=mem, remat=remat)
            aux = aux + a
        x, a = run_stack_train(params["stack"], x, cfg, specs, memory=mem,
                               remat=remat)
        aux = aux + a
        x = _final(params, x, cfg)
        loss, acc, n_tok = chunked_xent(params["embed"], x, batch["labels"])
        total = loss + aux
        return total, {"loss": loss, "aux_loss": aux, "accuracy": acc,
                       "n_tokens": n_tok}

    def init_cache(batch, cache_len, mem_len: int = 0):
        if cfg.family == "vlm":
            mem_len = mem_len or cfg.n_image_tokens
        c = {"stack": _stack_cache(cfg, specs, n_periods, batch, cache_len,
                                   mem_len),
             "lengths": jnp.zeros((batch,), jnp.int32)}
        if n_prefix:
            c["prefix"] = _stack_cache(cfg, prefix_specs, n_prefix, batch,
                                       cache_len, mem_len)
        return c

    def prefill(params, batch, cache_len: int | None = None, lengths=None):
        """``lengths`` [B]: per-row true lengths of a right-padded batch.
        Exact for dense causal-attention stacks (pad rows never feed real
        rows); ring (SWA) caches and Mamba state are position-keyed, so
        callers must pass equal-length batches there (the engine does)."""
        tokens = batch["tokens"]
        B, S = tokens.shape
        cache_len = cache_len or S
        x = _embed(params, tokens, cfg)
        mem = _memory(params, batch)
        cache: dict = {}
        if n_prefix:
            x, cache["prefix"] = run_stack_prefill(
                params["prefix"], x, cfg, prefix_specs, memory=mem,
                cache_len=cache_len)
        x, cache["stack"] = run_stack_prefill(
            params["stack"], x, cfg, specs, memory=mem, cache_len=cache_len)
        x = _final(params, x, cfg)
        logits = last_token_logits(params["embed"], x, lengths)
        cache["lengths"] = (jnp.full((B,), S, jnp.int32) if lengths is None
                            else lengths.astype(jnp.int32))
        return logits, cache

    def decode_step(params, tokens, cache):
        """tokens [B, 1] -> (logits [B, V], new cache)."""
        x = _embed(params, tokens, cfg)
        lengths = cache["lengths"]
        new_cache = dict(cache)
        if n_prefix:
            x, new_cache["prefix"] = run_stack_decode(
                params["prefix"], cache["prefix"], x, lengths, cfg,
                prefix_specs)
        x, new_cache["stack"] = run_stack_decode(
            params["stack"], cache["stack"], x, lengths, cfg, specs)
        x = _final(params, x, cfg)
        logits = lm_logits(params["embed"], x)[:, 0]
        new_cache["lengths"] = lengths + 1
        return logits, new_cache

    # first attention position in the period (None for pure-SSM stacks);
    # families with a prefix stack (moe first_k_dense) always have attn in
    # the period too, so the stack leaf is a sufficient geometry probe
    _attn_idx = next((i for i, s in enumerate(specs)
                      if s[0] in ("attn", "enc_attn")), None)
    _moe = has_moe(cfg)

    def init_paged_cache(batch, cache_len, *, block_size: int,
                         num_blocks: int):
        """Paged decode cache: block pools + shared table + free-list.

        SWA stacks page the *ring* (capacity = window), so ``cache_len``
        must cover the window and ``block_size`` must divide it — otherwise
        ring positions (``pos % window``) would straddle the block grid.
        """
        from repro.engine.paged import init_block_state
        window = cfg.sliding_window
        if window:
            if cache_len < window:
                raise ValueError(
                    f"paged SWA cache needs cache_len >= sliding_window "
                    f"({cache_len} < {window})")
            if window % block_size:
                raise ValueError(
                    f"block_size {block_size} must divide the sliding "
                    f"window {window} (ring positions are block-mapped)")
            mb = window // block_size
        else:
            mb = -(-cache_len // block_size)
        c = {"stack": _stack_cache_paged(cfg, specs, n_periods, batch,
                                         num_blocks, block_size),
             "lengths": jnp.zeros((batch,), jnp.int32),
             **init_block_state(batch, mb, num_blocks)}
        if _moe:  # the last forward's MoE counts (run_stack_paged)
            c["moe_counts"] = jnp.zeros((2,), jnp.int32)
        if n_prefix:
            c["prefix"] = _stack_cache_paged(cfg, prefix_specs, n_prefix,
                                             batch, num_blocks, block_size)
        return c

    def _run_stacks_paged(apply_layer, params, new_cache, x, pctx,
                          at_layer=POOL_KEYS):
        """The prefix group (if any), then the period stack, through
        :func:`run_stack_paged`; each group's cache is replaced in
        ``new_cache``, and so are the MoE counts.  Returns the final
        norm's output, the head's input."""
        counts = []
        if n_prefix:
            x, new_cache["prefix"], c = run_stack_paged(
                apply_layer, params["prefix"], new_cache["prefix"], x, pctx,
                cfg, prefix_specs, at_layer)
            counts.append(c)
        x, new_cache["stack"], c = run_stack_paged(
            apply_layer, params["stack"], new_cache["stack"], x, pctx, cfg,
            specs, at_layer)
        counts = [c for c in counts + [c] if c is not None]
        if counts:
            new_cache["moe_counts"] = sum(counts[1:], counts[0])
        return _final(params, x, cfg)

    def decode_step_paged(params, tokens, pcache, cow: bool = False,
                          keep=None):
        """tokens [B, 1] -> (logits [B, V], new paged cache).  Block
        allocation and write targets are computed once per step and shared
        by every attention layer (the stack advances in lockstep).  With
        ``cow=True`` (refcounted prefix caching) a write landing in a
        shared block pops a private copy, copied for every layer before the
        layer scan (:func:`cow_copy_blocks`) — see engine/paged.py.
        ``keep`` [B] (optional): the slots that decode; the others keep
        their per-slot state and route no token to an expert."""
        from repro.engine.paged import BSTATE_KEYS, alloc_step
        x = _embed(params, tokens, cfg)
        lengths = pcache["lengths"]
        new_cache = dict(pcache)
        if _attn_idx is not None:
            leaf = pcache["stack"][f"L{_attn_idx}"]["pk"]
            bs = leaf.shape[2]
            cap = pcache["tbl"].shape[1] * bs
            ring = bool(cfg.sliding_window) and cap == cfg.sliding_window
            bstate = {k: pcache[k] for k in BSTATE_KEYS}
            bstate, wblk, woff, cow_src = alloc_step(bstate, lengths, bs,
                                                     cap, ring, cow=cow)
            pctx = {"lengths": lengths, "tbl": bstate["tbl"],
                    "wblk": wblk, "woff": woff}
            new_cache.update(bstate)
            if cow:  # the private copies land before the step's writes
                new_cache = cow_copy_blocks(new_cache, cow_src, wblk)
        else:  # pure-SSM stack: contiguous state, no pools to manage
            pctx = {"lengths": lengths}
        if keep is not None:
            pctx["keep"] = keep
        x = _run_stacks_paged(apply_layer_decode_paged, params, new_cache, x,
                              pctx, POOL_KEYS + SSM_STATE_KEYS)
        logits = lm_logits(params["embed"], x)[:, 0]
        new_cache["lengths"] = lengths + 1
        return logits, new_cache

    def prefill_chunk_paged(params, tokens, pcache, start, valid,
                            shared_until=None):
        """One prefill chunk through the paged cache (chunked prefill /
        prefix-hit tail recompute).  ``tokens`` [B, C] are rows
        ``start[b]..start[b]+valid[b]-1`` of each slot's prompt (``valid[b]
        == 0`` passes the slot through untouched); ``shared_until`` [B]
        marks each slot's prefix-hit watermark (rows below it write into
        shared blocks and are dropped — the cached rows are identical).
        Returns the logits of each slot's last valid row (garbage where
        ``valid == 0``) and the cache with the chunk's KV written and
        per-slot lengths advanced to ``start + valid``."""
        from repro.engine.paged import BSTATE_KEYS, span_targets
        B, C = tokens.shape
        x = _embed(params, tokens, cfg)
        start = start.astype(jnp.int32)
        valid = valid.astype(jnp.int32)
        pctx = {"start": start, "valid": valid}
        new_cache = dict(pcache)
        if _attn_idx is not None:
            leaf = pcache["stack"][f"L{_attn_idx}"]["pk"]
            bs = leaf.shape[2]
            cap = pcache["tbl"].shape[1] * bs
            ring = bool(cfg.sliding_window) and cap == cfg.sliding_window
            bstate = {k: pcache[k] for k in BSTATE_KEYS}
            wblk, woff = span_targets(bstate, start, valid, C, bs, cap,
                                      ring, shared_until)
            pctx.update(tbl=bstate["tbl"], wblk=wblk, woff=woff)
        x = _run_stacks_paged(apply_layer_prefill_paged, params, new_cache, x,
                              pctx)
        idx = jnp.clip(valid - 1, 0, C - 1)
        xg = x[jnp.arange(B), idx][:, None]
        logits = lm_logits(params["embed"], xg)[:, 0]
        new_cache["lengths"] = jnp.where(valid > 0, start + valid,
                                         pcache["lengths"])
        return logits, new_cache

    def verify_chunk_paged(params, tokens, pcache, start, valid):
        """Speculative-verify forward: consume ``tokens`` [B, C] (rows
        ``start[b]..start[b]+valid[b]-1`` of each slot's continuation) and
        return the logits of **every** row [B, C, V] plus the cache with
        the rows' KV written and SSM state advanced by ``valid[b]`` steps.
        Row ``j``'s logits bitwise-mirror what ``decode_step_paged`` would
        have produced after consuming rows ``< j`` (attention runs the
        decode softmax over the gathered table, SSM the exact per-token
        recurrence), which is what makes greedy speculative decoding
        token-exact against the non-speculative engine (engine/spec.py).
        ``valid[b] == 0`` passes the slot through untouched; rows at or
        beyond ``valid[b]`` are state no-ops with garbage logits."""
        from repro.engine.paged import BSTATE_KEYS, span_targets
        B, C = tokens.shape
        x = _embed(params, tokens, cfg)
        start = start.astype(jnp.int32)
        valid = valid.astype(jnp.int32)
        pctx = {"start": start, "valid": valid}
        new_cache = dict(pcache)
        if _attn_idx is not None:
            leaf = pcache["stack"][f"L{_attn_idx}"]["pk"]
            bs = leaf.shape[2]
            cap = pcache["tbl"].shape[1] * bs
            ring = bool(cfg.sliding_window) and cap == cfg.sliding_window
            bstate = {k: pcache[k] for k in BSTATE_KEYS}
            wblk, woff = span_targets(bstate, start, valid, C, bs, cap,
                                      ring)
            pctx.update(tbl=bstate["tbl"], wblk=wblk, woff=woff)
        x = _run_stacks_paged(apply_layer_verify_paged, params, new_cache, x,
                              pctx)
        logits = lm_logits(params["embed"], x)
        new_cache["lengths"] = jnp.where(valid > 0, start + valid,
                                         pcache["lengths"])
        return logits, new_cache

    return Model(cfg, init, loss_fn, init_cache, prefill, decode_step,
                 init_paged_cache=init_paged_cache,
                 decode_step_paged=decode_step_paged,
                 prefill_chunk_paged=prefill_chunk_paged,
                 verify_chunk_paged=verify_chunk_paged)


# ---------------------------------------------------------------------------
# Encoder-decoder (seamless-m4t): frames (stub frontend) -> text
# ---------------------------------------------------------------------------

def _build_encdec(cfg: ModelConfig) -> Model:
    dtype = jnp.dtype(cfg.dtype)
    enc_specs = [("enc_attn", "mlp")]
    dec_specs = [("attn_cross", "mlp")]

    def init(key):
        ks = split(key, 5)
        return {
            "embed": init_embed(ks[0], cfg, dtype),
            "enc_stack": _init_stack(ks[1], cfg, enc_specs, cfg.n_enc_layers,
                                     dtype),
            "enc_norm": init_norm(cfg, dtype),
            "stack": _init_stack(ks[2], cfg, dec_specs, cfg.n_dec_layers,
                                 dtype),
            "final_norm": init_norm(cfg, dtype),
        }

    def encode(params, frames, remat="full"):
        x = frames.astype(ACT_DTYPE)
        x, _ = run_stack_train(params["enc_stack"], x, cfg, enc_specs,
                               remat=remat)
        return apply_norm(params["enc_norm"], x, cfg.norm_eps)

    def loss_fn(params, batch, remat: str = "full"):
        mem = encode(params, batch["frames"], remat)
        x = embed_tokens(params["embed"], batch["tokens"])
        x, _ = run_stack_train(params["stack"], x, cfg, dec_specs,
                               memory=mem, remat=remat)
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        loss, acc, n_tok = chunked_xent(params["embed"], x, batch["labels"])
        return loss, {"loss": loss, "aux_loss": jnp.float32(0.0),
                      "accuracy": acc, "n_tokens": n_tok}

    def init_cache(batch, cache_len, mem_len: int = 0):
        mem_len = mem_len or cfg.enc_frames_cap
        return {"stack": _stack_cache(cfg, dec_specs, cfg.n_dec_layers, batch,
                                      cache_len, mem_len),
                "lengths": jnp.zeros((batch,), jnp.int32)}

    def prefill(params, batch, cache_len: int | None = None, lengths=None):
        mem = encode(params, batch["frames"])
        tokens = batch["tokens"]
        B, S = tokens.shape
        cache_len = cache_len or S
        x = embed_tokens(params["embed"], tokens)
        x, cache = run_stack_prefill(params["stack"], x, cfg, dec_specs,
                                     memory=mem, cache_len=cache_len)
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = last_token_logits(params["embed"], x, lengths)
        return logits, {"stack": cache,
                        "lengths": (jnp.full((B,), S, jnp.int32)
                                    if lengths is None
                                    else lengths.astype(jnp.int32))}

    def decode_step(params, tokens, cache):
        x = embed_tokens(params["embed"], tokens)
        lengths = cache["lengths"]
        x, new_stack = run_stack_decode(params["stack"], cache["stack"], x,
                                        lengths, cfg, dec_specs)
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = lm_logits(params["embed"], x)[:, 0]
        return logits, {"stack": new_stack, "lengths": lengths + 1}

    return Model(cfg, init, loss_fn, init_cache, prefill, decode_step)
