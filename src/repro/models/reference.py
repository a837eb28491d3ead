"""Plain float32 reference forward of the hybrid family, as Granite 4.0-H
writes its block (hf:ibm-granite/granite-4.0-h-small, ``granitemoehybrid``):

    x0 = m_emb * E[tok]
    for each layer l:
      x <- x + m_res * mixer_l(RMSNorm(x))  # attn at l % P == P // 2, else
                                            # mamba2
      h  = RMSNorm(x)
      x <- x + m_res * (MoE(h) + SwiGLU_shared(h))   (or a SwiGLU MLP)
    logits = RMSNorm(x) @ head / logits_scaling

    MoE(h)    = sum over held experts e of g_e(h) * SwiGLU_e(h), where g is a
                softmax over the top-k of the router's logits (all experts)
    attn(h)   = causal softmax(q k^T * scale) v, GQA, RoPE when rope_theta > 0
    mamba2(h) = W_out RMSNormGated(y, z): [z | x | B | C | dt] = h W_in,
                xBC <- silu(conv4(xBC) + b), dt <- softplus(dt + dt_bias),
                S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,
                y_t = S_t C_t + D x_t

It reads the program's parameter tree, one sequence at a time, and shares
no code with the program's forward: no kernels, cache, batching or
chunking.  The Mamba-2 recurrence runs token by token, so it checks the
program's chunked SSD scan rather than copying it, and the MoE computes
every held expert for every token, independent of the program's sort.

Departures from the published model, shared with the program: the
expert layer gives the part of the held experts only (one chip's share of
an expert-parallel deployment; ``n_experts_held`` / ``expert_first``), and
the Mamba projections are separate matrices (``in_z``, ``in_x``, ``in_bc``,
``in_dt``) where the published model fuses them into one ``in_proj``.

``state_dtype`` and ``shared_mlp`` are controls for the tests: the SSM
state rounded to bfloat16 after every step, and the shared expert left
out, each one step away from the published computation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def _f32(w):
    if hasattr(w, "dequantize"):
        w = w.dequantize()
    return jnp.asarray(w, jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w))


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(scale)


def _swiglu(p, h):
    return _mm(jax.nn.silu(_mm(h, p["w_gate"])) * _mm(h, p["w_up"]),
               p["w_down"])


def _rope(x, theta):
    """Rotate the two halves of each head; x [T, heads, hd]."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(p, h, cfg: ModelConfig):
    T = h.shape[0]
    H, Kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = _mm(h, p["wq"]).reshape(T, H, hd)
    k = _mm(h, p["wk"]).reshape(T, Kv, hd)
    v = _mm(h, p["wv"]).reshape(T, Kv, hd)
    if "bias_q" in p:
        q = q + _f32(p["bias_q"]).reshape(H, hd)
        k = k + _f32(p["bias_k"]).reshape(Kv, hd)
        v = v + _f32(p["bias_v"]).reshape(Kv, hd)
    if cfg.rope_theta > 0:
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    k = jnp.repeat(k, H // Kv, axis=1)
    v = jnp.repeat(v, H // Kv, axis=1)
    scale = cfg.attn_scale or hd ** -0.5
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return _mm(o.reshape(T, H * hd), p["wo"])


def _conv(x, w, b):
    """Depthwise causal conv over time, x [T, C], w [K, C], then silu."""
    K = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1])), x])
    out = sum(xp[k:k + x.shape[0]] * _f32(w)[k] for k in range(K))
    return jax.nn.silu(out + _f32(b))


def mamba2(p, h, cfg: ModelConfig, state_dtype=jnp.float32):
    """(out [T, D], final state [nh, P, N])."""
    T = h.shape[0]
    N, nh, P = cfg.ssm_state, cfg.n_ssm_heads, cfg.ssm_head_dim
    z = _mm(h, p["in_z"])
    xs = _conv(_mm(h, p["in_x"]), p["conv_x_w"], p["conv_x_b"])
    bc = _conv(_mm(h, p["in_bc"]), p["conv_bc_w"], p["conv_bc_b"])
    dt = jax.nn.softplus(_mm(h, p["in_dt"]) + _f32(p["dt_bias"]))   # [T, nh]
    A = -jnp.exp(_f32(p["a_log"]))
    xh = xs.reshape(T, nh, P)

    def step(S, inp):
        x_t, b_t, c_t, dt_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + dt_t[:, None, None] * x_t[:, :, None] * b_t[None, None, :])
        S = S.astype(state_dtype).astype(jnp.float32)
        return S, jnp.einsum("hpn,n->hp", S, c_t)

    S0 = jnp.zeros((nh, P, N), jnp.float32)
    S, y = jax.lax.scan(step, S0, (xh, bc[:, :N], bc[:, N:], dt))
    y = y + _f32(p["d_skip"])[None, :, None] * xh
    g = y.reshape(T, nh * P) * jax.nn.silu(z)
    g = _rmsnorm(g, p["norm_scale"], cfg.norm_eps)
    return _mm(g, p["out_proj"]), S


def moe(p, h, cfg: ModelConfig, shared_mlp: bool = True):
    """The held experts' part of the MoE output, plus the shared expert."""
    logits = _mm(h, p["router"])                       # [T, n_experts]
    top, idx = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(top, axis=-1)               # [T, k]
    held = cfg.expert_first + jnp.arange(cfg.resolved_experts_held)
    weight = jnp.sum(gates[:, :, None] * (idx[:, :, None] == held), axis=1)
    g = jnp.einsum("td,edf->tef", h, _f32(p["w_gate"]))
    u = jnp.einsum("td,edf->tef", h, _f32(p["w_up"]))
    out = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, _f32(p["w_down"]))
    y = jnp.einsum("te,ted->td", weight, out)
    if shared_mlp and "shared" in p:
        y = y + _swiglu(p["shared"], h)
    return y


def layer_kind(cfg: ModelConfig, l: int) -> tuple[str, str]:
    """(mixer, ffn) of layer ``l`` of a hybrid stack."""
    i = l % cfg.attn_every
    mixer = "attn" if i == cfg.attn_every // 2 else "mamba"
    ffn = "moe" if cfg.moe_every and i % cfg.moe_every == cfg.moe_offset \
        else "mlp"
    return mixer, ffn


def forward(params, tokens, cfg: ModelConfig, *, state_dtype=jnp.float32,
            shared_mlp: bool = True):
    """(logits [T, V], final SSM states) in float32 of one sequence
    ``tokens`` [T]; the states are ``{layer: [nh, P, N]}`` of the Mamba-2
    layers after the last token."""
    if cfg.family != "hybrid":
        raise ValueError(f"reference forward of the hybrid family, not "
                         f"{cfg.family!r}")
    with jax.default_matmul_precision("highest"):
        eps, m_res = cfg.norm_eps, cfg.residual_multiplier
        table = _f32(params["embed"]["embed"])
        x = cfg.embedding_multiplier * table[jnp.asarray(tokens)]
        P = cfg.attn_every
        states = {}
        for l in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[l // P],
                              params["stack"][f"L{l % P}"])
            mixer, ffn = layer_kind(cfg, l)
            h = _rmsnorm(x, lp["ln1"]["norm_scale"], eps)
            if mixer == "attn":
                x = x + m_res * attention(lp["attn"], h, cfg)
            else:
                y, states[l] = mamba2(lp["mamba"], h, cfg, state_dtype)
                x = x + m_res * y
            h = _rmsnorm(x, lp["ln2"]["norm_scale"], eps)
            y = (moe(lp["moe"], h, cfg, shared_mlp) if ffn == "moe"
                 else _swiglu(lp["mlp"], h))
            x = x + m_res * y
        x = _rmsnorm(x, params["final_norm"]["norm_scale"], eps)
        head = (_f32(params["embed"]["w_head"]) if "w_head" in params["embed"]
                else table.T)
        return jnp.matmul(x, head) / cfg.logits_scaling, states
