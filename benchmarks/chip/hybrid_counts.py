"""Model FLOPs of the hybrid block (Granite 4.0-H), from shapes alone, as
``counts.py`` counts them for the dense decoder: matmuls at 2 FLOPs per
multiply-add, attention as QK^T and PV over each token's context, the
Mamba-2 recurrence as its state update and read-out (4 FLOPs per state
element and token), the LM head only where logits are used.  The routed
experts are counted per (token, held expert) assignment, from the expert
layer's device counter: which experts a token is routed to is data.
"""
from __future__ import annotations


def _plan(cfg: dict) -> tuple[int, int]:
    """(attention layers, Mamba-2 layers)."""
    n_attn = cfg["n_layers"] // cfg["attn_every"]
    return n_attn, cfg["n_layers"] - n_attn


def token_flops(cfg: dict) -> int:
    """FLOPs of one token in every layer, without attention's context term
    and the routed experts."""
    D, H, Kv, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    di, N, K = cfg["d_inner"], cfg["ssm_state"], cfg["conv_kernel"]
    nh = di // cfg["ssm_head_dim"]
    n_attn, n_mamba = _plan(cfg)
    mamba = (2 * (D * (2 * di + 2 * N + nh) + di * D)   # in / out projections
             + 2 * K * (di + 2 * N)                      # depthwise conv
             + 4 * di * N)                               # state update + read
    attn = 2 * (D * H * hd + 2 * D * Kv * hd + H * hd * D)
    ffn = 2 * D * cfg["n_experts_routed"] + 6 * D * cfg["d_ff_shared"]
    return n_mamba * mamba + n_attn * attn + cfg["n_layers"] * ffn


def request_flops(cfg: dict, prompt_len: int, gen: int) -> int:
    """Prefill of the prompt, then ``gen - 1`` decode steps (the first
    generated token comes from the prefill), routed experts excluded."""
    H, hd = cfg["n_heads"], cfg["head_dim"]
    n_attn, _ = _plan(cfg)
    T = prompt_len + gen - 1                 # tokens through the layers
    ctx = T * (T + 1) // 2                   # sum over positions of pos + 1
    return (T * token_flops(cfg) + n_attn * 4 * H * hd * ctx
            + gen * 2 * cfg["d_model"] * cfg["vocab_size"])


def expert_flops(cfg: dict, assigned: int) -> int:
    """FLOPs of ``assigned`` (token, held expert) assignments: a SwiGLU of
    width ``d_ff`` each."""
    return assigned * 6 * cfg["d_model"] * cfg["d_ff"]
