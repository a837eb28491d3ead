"""Mixture-of-Experts FFN: the expert layer of a chip that holds a share of
the experts, dropless on every path (training, prefill, decode, chunked
prefill, speculative verify): :func:`apply_moe_dropless`.

The router scores all ``n_experts``; the chip holds experts
``[expert_first, expert_first + n_experts_held)`` and computes their part
of the result only, for every token routed to them: the (token, held
expert) pairs are sorted by expert and run through one grouped matmul per
projection (``jax.lax.ragged_dot``).  No token is ever dropped, and a
token's output depends on its own hidden state alone.  What the absent
experts would add is left to the chips that hold them (expert parallelism:
their exchange is not part of this layer).

Top-k gates are renormalized over the selected experts (Mixtral
convention).  Training adds the Switch/GShard load-balancing loss
(:func:`load_balance_loss`): E * sum_e f_e * p_e.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, split
from repro.quant_runtime import qlinear


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    """Router over all ``n_experts``; expert stacks of the held ones."""
    D, F, E = cfg.d_model, cfg.d_ff, cfg.resolved_experts_held
    ks = split(key, 5)

    def expert_stack(k, din, dout):
        kk = jax.random.split(k, E)
        return jax.vmap(lambda kx: dense_init(kx, din, dout, dtype))(kk)

    p = {
        "router": dense_init(ks[0], D, cfg.n_experts, jnp.float32),  # fp32
        "w_gate": expert_stack(ks[1], D, F),             # [E, D, F]
        "w_up": expert_stack(ks[2], D, F),
        "w_down": expert_stack(ks[3], F, D),             # [E, F, D]
    }
    if cfg.n_shared_experts:
        Fs = cfg.resolved_d_ff_shared
        kk = split(ks[4], 3)
        p["shared"] = {"w_gate": dense_init(kk[0], D, Fs, dtype),
                       "w_up": dense_init(kk[1], D, Fs, dtype),
                       "w_down": dense_init(kk[2], Fs, D, dtype)}
    return p


# ---------------------------------------------------------------------------
# Dropless expert layer over the held experts
# ---------------------------------------------------------------------------

# Tokens per block of the dropless layer (apply_moe_dropless).
TOKEN_BLOCK = 1024


def route(router, x2: jnp.ndarray, top_k: int):
    """Top-k routing of tokens ``x2`` [T, D] over the router's experts, in
    float32: (expert ids [T, k], gates [T, k]).  The gates are a softmax
    over the k chosen logits, which equals a softmax over all experts
    renormalized over the chosen ones.  The logits are float32 products
    (``HIGHEST``: the TPU's default takes bf16 passes), since the choice
    of experts is discrete: a near-tie that rounding flips moves a whole
    expert's output."""
    logits = jnp.matmul(x2.astype(jnp.float32),
                        qlinear.resolve(router).astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    vals, idx = jax.lax.top_k(logits, top_k)
    return idx, jax.nn.softmax(vals, axis=-1)


def load_balance_loss(router, x: jnp.ndarray) -> jnp.ndarray:
    """Switch/GShard load-balancing loss of the router over ``x`` [B, S,
    D]: E * sum_e f_e * p_e, with f_e the share of tokens whose first
    choice is expert e and p_e the mean router probability of e."""
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    probs = jax.nn.softmax(x2 @ qlinear.resolve(router).astype(jnp.float32),
                           axis=-1)
    E = probs.shape[-1]
    first = jax.nn.one_hot(jnp.argmax(probs, axis=-1), E, dtype=jnp.float32)
    return E * jnp.sum(jnp.mean(first, axis=0) * jnp.mean(probs, axis=0))


def row_tile(m: int) -> int:
    """Row tile of the TPU's grouped matmul for an ``m``-row operand: the
    largest power of two up to 512 that divides ``m`` (read from the
    v5e kernel that ``jax.lax.ragged_dot`` compiles to).  A tile holds one
    group's rows; a tile that two groups share is computed once for each."""
    t = 1
    while t < 512 and m % (2 * t) == 0:
        t *= 2
    return t


def _rows_computed(group_sizes: jnp.ndarray, tile: int) -> jnp.ndarray:
    """Rows the grouped matmul computes, padding included: every tile that
    a group's rows touch, once per group."""
    end = jnp.cumsum(group_sizes)
    start = end - group_sizes
    tiles = jnp.where(group_sizes > 0,
                      (end - 1) // tile - start // tile + 1, 0)
    return jnp.sum(tiles) * tile


def apply_moe_dropless(p: dict, x: jnp.ndarray, cfg: ModelConfig,
                       valid=None):
    """x [B, S, D] -> (y [B, S, D], counts int32 [2]).

    ``y`` is the held experts' part of the MoE output plus the shared
    expert; ``valid`` [B, S] (optional) marks real rows, and the others
    (pads, idle slots) are routed to no expert.  ``counts`` are the
    (token, held expert) assignments and the rows the grouped matmuls
    computed for them, padding included (:func:`row_tile`).  Tokens go
    through in blocks of at most ``TOKEN_BLOCK``, which bounds the sorted
    rows a block holds (``min(top_k, held)`` per token)."""
    B, S, D = x.shape
    T = B * S
    x2 = x.reshape(T, D)
    ok = (jnp.ones((T,), bool) if valid is None
          else jnp.broadcast_to(valid, (B, S)).reshape(T))
    tb = T
    while tb > TOKEN_BLOCK or T % tb:
        tb -= 1
    with jax.named_scope("moe"):
        if tb == T:
            y, counts = _dropless_block(p, x2, ok, cfg)
        else:
            y, counts = jax.lax.map(
                lambda a: _dropless_block(p, *a, cfg),
                (x2.reshape(T // tb, tb, D), ok.reshape(T // tb, tb)))
            counts = jnp.sum(counts, axis=0)
    y = y.astype(x.dtype).reshape(B, S, D)
    if "shared" in p:
        from repro.models.common import apply_mlp
        y = y + apply_mlp(p["shared"], x)
    return y, counts


def _dropless_block(p: dict, x2, ok, cfg: ModelConfig):
    """One block of tokens ``x2`` [T, D] (``ok`` [T]: routed rows) through
    the held experts: (y [T, D] float32, counts int32 [2])."""
    T, D = x2.shape
    K, H = cfg.top_k, cfg.resolved_experts_held
    idx, gates = route(p["router"], x2, K)
    local = idx - cfg.expert_first
    held = (local >= 0) & (local < H) & ok[:, None]
    key = jnp.where(held, local, H).reshape(-1)              # [T * K]
    # at most min(K, H) of a token's pairs are held: the rows needed when
    # every token routes all it can here
    M = T * min(K, H)
    order = jnp.argsort(key, stable=True)[:M]                # held first
    group_sizes = jnp.sum(key[:, None] == jnp.arange(H)[None], axis=0,
                          dtype=jnp.int32)
    tok = order // K
    rows = jnp.take(x2, tok, axis=0)                         # [M, D]
    grp = jnp.minimum(jnp.take(key, order), H - 1)

    def gmm(a, w):
        return _ragged_matmul(a, w, group_sizes, grp)

    h = (jax.nn.silu(gmm(rows, p["w_gate"]).astype(jnp.float32))
         * gmm(rows, p["w_up"]).astype(jnp.float32))
    out = gmm(h.astype(x2.dtype), p["w_down"]).astype(jnp.float32)
    # rows past the groups are not written by the TPU's grouped matmul
    # (whatever the buffer held, NaN too): selected away, not multiplied
    real = jnp.take(held.reshape(-1), order)
    w = jnp.where(real, jnp.take(gates.reshape(-1), order), 0.0)
    y = jnp.zeros((T, D), jnp.float32).at[tok].add(
        jnp.where(real[:, None], out, 0.0) * w[:, None])
    counts = jnp.stack([jnp.sum(group_sizes),
                        _rows_computed(group_sizes, row_tile(M))])
    return y, counts


def _ragged_matmul(a, w, group_sizes, grp):
    """Rows of ``a`` [M, in] grouped by ``group_sizes`` times the stacked
    expert weights ``w`` [H, in, out], in ``a``'s dtype.  A channel-
    quantized stack multiplies its codes directly and scales the result's
    columns by each row's expert (``grp`` [M]), so the stack is never
    dequantized whole."""
    from repro.quant_runtime.qparams import QuantizedTensor
    if (isinstance(w, QuantizedTensor) and w.granularity == "channel"
            and w.eq_scale is None):
        out = jax.lax.ragged_dot(a, w.data, group_sizes,
                                 preferred_element_type=jnp.float32)
        scale = jnp.take(w.scale[:, 0].astype(jnp.float32), grp, axis=0)
        return (out * scale).astype(a.dtype)
    return jax.lax.ragged_dot(a, qlinear.resolve(w).astype(a.dtype),
                              group_sizes, preferred_element_type=jnp.float32
                              ).astype(a.dtype)
