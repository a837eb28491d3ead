"""Architecture registry: ``--arch <id>`` resolution + reduced smoke configs."""
from __future__ import annotations

import dataclasses

from repro.configs.base import ModelConfig, ShapeConfig, LM_SHAPES, SHAPES_BY_NAME

from repro.configs.glm4_9b import CONFIG as _glm4
from repro.configs.command_r_35b import CONFIG as _commandr
from repro.configs.phi3_medium_14b import CONFIG as _phi3
from repro.configs.deepseek_67b import CONFIG as _ds67
from repro.configs.mamba2_780m import CONFIG as _mamba2
from repro.configs.jamba_v0_1_52b import CONFIG as _jamba
from repro.configs.mixtral_8x22b import CONFIG as _mixtral
from repro.configs.kimi_k2_1t_a32b import CONFIG as _kimi
from repro.configs.seamless_m4t_medium import CONFIG as _seamless
from repro.configs.llama_3_2_vision_90b import CONFIG as _llamav
from repro.configs.deepseek_v3 import CONFIG as _dsv3
from repro.configs.granite_4_0_h_small import CONFIG as _granite

# The 10 assigned architectures (order matters: it is the report order).
ASSIGNED: tuple[ModelConfig, ...] = (
    _glm4, _commandr, _phi3, _ds67, _mamba2,
    _jamba, _mixtral, _kimi, _seamless, _llamav,
)

# Available but not part of the 40-cell table: the paper's own model, and
# the benchmark's hybrid configuration.
EXTRA: tuple[ModelConfig, ...] = (_dsv3, _granite)

ARCHS: dict[str, ModelConfig] = {c.name: c for c in ASSIGNED + EXTRA}


def get_arch(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES_BY_NAME:
        raise KeyError(f"unknown shape {name!r}; available: {sorted(SHAPES_BY_NAME)}")
    return SHAPES_BY_NAME[name]


def all_cells() -> list[tuple[ModelConfig, ShapeConfig]]:
    """All 40 (assigned arch x shape) cells, including inapplicable ones."""
    return [(a, s) for a in ASSIGNED for s in LM_SHAPES]


# ---------------------------------------------------------------------------
# Reduced configs for CPU smoke tests: same family/code path, tiny dims.
# ---------------------------------------------------------------------------

def reduced(cfg: ModelConfig, *, vocab: int = 512) -> ModelConfig:
    """A tiny config of the same family exercising every structural feature."""
    kw: dict = dict(
        name=cfg.name + "-smoke",
        n_layers=max(2, min(4, cfg.n_layers)),
        d_model=64,
        vocab_size=vocab,
        d_ff=128 if cfg.d_ff else 0,
        head_dim=16,
        notes="reduced smoke config",
    )
    if cfg.n_heads:
        kw["n_heads"] = 4
        kw["n_kv_heads"] = max(1, min(2, cfg.n_kv_heads))
    if cfg.family in ("moe", "hybrid"):
        kw["n_experts"] = min(8, cfg.n_experts)
        kw["top_k"] = min(2, cfg.top_k)
        if cfg.n_experts_held:  # keep a share: half of the experts here
            kw["n_experts_held"] = kw["n_experts"] // 2
        if cfg.d_ff_shared:
            kw["d_ff_shared"] = 96
        if cfg.n_shared_experts:
            kw["n_shared_experts"] = 1
        if cfg.first_k_dense:
            kw["first_k_dense"] = 1
            kw["d_ff_dense"] = 192
    if cfg.family in ("ssm", "hybrid"):
        kw["ssm_state"] = 16
        kw["d_inner"] = 128
        kw["ssm_head_dim"] = 16
        kw["ssm_chunk"] = 32
    if cfg.family == "hybrid":
        kw["n_layers"] = cfg.attn_every  # one full interleave group
    if cfg.family == "encdec":
        kw["n_enc_layers"] = 2
        kw["n_dec_layers"] = 2
        kw["n_layers"] = 4
        kw["enc_frames_cap"] = 64
    if cfg.family == "vlm":
        kw["n_layers"] = max(4, cfg.cross_attn_every)
        kw["cross_attn_every"] = min(2, cfg.cross_attn_every)
        kw["n_image_tokens"] = 17
    if cfg.sliding_window:
        kw["sliding_window"] = 16
    return dataclasses.replace(cfg, **kw)
