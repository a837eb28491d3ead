"""Operations and bytes that the work of a cell needs, from shapes alone.

Model FLOPs count what the mathematics needs, not what the program runs:
matmuls at 2 FLOPs per multiply-add, attention as QK^T and PV over each
token's context, the LM head only where logits are used (the last prompt
position and every decode step), and prefix-cache hits not at all.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    D, H, Kv, hd, F = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    return D * H * hd + 2 * D * Kv * hd + H * hd * D + 3 * D * F


def token_flops(cfg: dict, pos: int, logits: bool) -> int:
    """FLOPs of one token at position ``pos`` (it attends pos + 1 keys)."""
    L, H, hd = cfg["n_layers"], cfg["n_heads"], cfg["head_dim"]
    f = L * (2 * matmul_params(cfg) + 4 * H * hd * (pos + 1))
    if logits:
        f += 2 * cfg["d_model"] * cfg["vocab_size"]
    return f


def request_flops(cfg: dict, prompt_len: int, hit: int, gen: int) -> int:
    """Prefill of positions [hit, prompt_len), then ``gen - 1`` decode
    steps (the first generated token comes from the prefill)."""
    L, H, hd = cfg["n_layers"], cfg["n_heads"], cfg["head_dim"]
    n_pf = prompt_len - hit
    mm = 2 * matmul_params(cfg) * L
    # sum over pos in [hit, prompt_len) of (pos + 1)
    ctx = (prompt_len * (prompt_len + 1) - hit * (hit + 1)) // 2
    f = n_pf * mm + L * 4 * H * hd * ctx + 2 * cfg["d_model"] * cfg["vocab_size"]
    for j in range(gen - 1):
        f += token_flops(cfg, prompt_len + j, True)
    return f


def daq_bytes(n_params: int, n_scales: int, in_bytes: int = 2) -> int:
    """HBM bytes Alg. 1 must move for one quantize: three dependent passes
    over post and base (coarse grid, fine grid, finalize), plus writing the
    fp8 codes (1 B each) and the float32 scales."""
    return 3 * 2 * in_bytes * n_params + n_params + 4 * n_scales
