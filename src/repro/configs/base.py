"""Configuration system for the DAQ reproduction framework.

Every model architecture is described by a single frozen dataclass
(`ModelConfig`).  Input shapes are described by `ShapeConfig`.  Quantization
settings by `QuantConfig`, training by `TrainConfig`, and meshes/launch by
`RunConfig`.  All configs are plain dataclasses so they can be constructed
from CLI flags, python, or JSON without any framework dependency.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters.

    ``family`` selects the assembly code path:
      dense   -- decoder-only transformer (GQA + RoPE + SwiGLU)
      moe     -- decoder-only transformer with mixture-of-experts FFN
      ssm     -- attention-free Mamba-2 (SSD) stack
      hybrid  -- Jamba-style interleave of Mamba + attention + MoE
      encdec  -- encoder-decoder transformer (speech/text, frontend stubbed)
      vlm     -- decoder-only transformer with interleaved cross-attention
                 layers attending to precomputed image patch embeddings
    """

    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0        # DeepSeek-V3 style shared expert(s)
    d_ff_shared: int = 0             # shared width (0 -> d_ff * n_shared)
    n_experts_held: int = 0          # experts this chip holds (0 -> all):
    expert_first: int = 0            # experts [first, first + held) of the
                                     # router's n_experts (expert parallelism)
    first_k_dense: int = 0           # first k layers use dense FFN
    d_ff_dense: int = 0              # dense FFN width when first_k_dense > 0
    router_aux_loss: float = 0.001

    # --- SSM (Mamba-2 / SSD) ---
    ssm_state: int = 0               # N: state dimension
    d_inner: int = 0                 # expanded inner width (0 -> 2*d_model)
    ssm_head_dim: int = 64           # P: SSD head dim
    ssm_chunk: int = 256             # SSD chunk length
    conv_kernel: int = 4

    # --- hybrid (Jamba) ---
    attn_every: int = 0              # one attention layer per this many layers
    moe_every: int = 0               # MoE FFN on layers where (idx % moe_every)==moe_offset
    moe_offset: int = 1

    # --- attention ---
    rope_theta: float = 10000.0
    sliding_window: int = 0          # >0 -> sliding-window attention (Mixtral)
    attn_logit_softcap: float = 0.0
    qkv_bias: bool = False
    attn_scale: float = 0.0          # softmax scale (0 -> 1 / sqrt(head_dim))

    # --- encoder-decoder ---
    n_enc_layers: int = 0
    n_dec_layers: int = 0
    enc_frames_cap: int = 4096       # max encoder memory length used in decode shapes

    # --- VLM ---
    cross_attn_every: int = 0        # one cross-attn layer per this many layers
    n_image_tokens: int = 1601       # patch embeddings per image (stub frontend)

    # --- misc ---
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    act: str = "swiglu"              # swiglu | gelu
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    norm_eps: float = 1e-5
    # muP multipliers (Granite): x0 = m_emb * E[tok]; each sublayer adds
    # m_res * f(x); logits = head(x) / logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # --- provenance ---
    source: str = ""                 # citation from the assignment table
    subquadratic: bool = False       # can run long_500k decode
    notes: str = ""

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def resolved_d_inner(self) -> int:
        return self.d_inner if self.d_inner else 2 * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.resolved_d_inner // self.ssm_head_dim

    @property
    def resolved_experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def resolved_d_ff_shared(self) -> int:
        return self.d_ff_shared or self.d_ff * self.n_shared_experts

    def param_count(self, held: bool = False) -> int:
        """Analytic total parameter count (embedding included): the whole
        model, or with ``held`` only the experts this chip holds."""
        D, F, V = self.d_model, self.d_ff, self.vocab_size
        H, Kv, hd = self.n_heads, self.n_kv_heads, self.resolved_head_dim
        embed = V * D
        head = 0 if self.tie_embeddings else D * V
        attn = D * H * hd + 2 * D * Kv * hd + H * hd * D
        E = self.resolved_experts_held if held else self.n_experts
        moe_ffn = (E * 3 * D * F + D * self.n_experts
                   + 3 * D * self.resolved_d_ff_shared)

        def dense_ffn(width: int) -> int:
            return 3 * D * width  # SwiGLU: gate + up + down

        total = embed + head + D  # final norm
        if self.family in ("dense", "vlm"):
            per_layer = attn + dense_ffn(F) + 2 * D
            total += self.n_layers * per_layer
            if self.family == "vlm" and self.cross_attn_every:
                n_cross = self.n_layers // self.cross_attn_every
                total += n_cross * (attn + 2 * D)  # cross-attn projections+norms
        elif self.family == "moe":
            n_moe = self.n_layers - self.first_k_dense
            total += n_moe * (attn + moe_ffn + 2 * D)
            total += self.first_k_dense * (attn + dense_ffn(self.d_ff_dense or F) + 2 * D)
        elif self.family == "ssm":
            di, N = self.resolved_d_inner, self.ssm_state
            nh = self.n_ssm_heads
            # in_proj -> [z, x, B, C, dt], conv over (x,B,C), out_proj
            per_layer = (D * (2 * di + 2 * N + nh) + self.conv_kernel * (di + 2 * N)
                         + 2 * nh + di + di * D + 2 * D)
            total += self.n_layers * per_layer
        elif self.family == "hybrid":
            di, N = self.resolved_d_inner, self.ssm_state
            nh = self.n_ssm_heads
            # in_z, in_x, in_bc, in_dt; conv weights and biases over
            # (x, B, C); a_log, dt_bias, d_skip; gated norm; out_proj
            mamba_l = (D * (2 * di + 2 * N + nh)
                       + (self.conv_kernel + 1) * (di + 2 * N)
                       + 3 * nh + di + di * D)
            for idx in range(self.n_layers):
                is_attn = self.attn_every and (idx % self.attn_every == self.attn_every // 2)
                total += attn if is_attn else mamba_l
                is_moe = self.moe_every and (idx % self.moe_every == self.moe_offset)
                total += moe_ffn if is_moe else dense_ffn(F)
                total += 2 * D  # mixer and ffn norms
        elif self.family == "encdec":
            enc = self.n_enc_layers * (attn + dense_ffn(F) + 2 * D)
            dec = self.n_dec_layers * (2 * attn + dense_ffn(F) + 3 * D)
            total += enc + dec
        return total

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE top-k counting)."""
        if self.family not in ("moe", "hybrid") or not self.n_experts:
            return self.param_count()
        D, F = self.d_model, self.d_ff
        per_expert = 3 * D * F
        inactive = (self.n_experts - self.top_k) * per_expert
        if self.family == "moe":
            n_moe = self.n_layers - self.first_k_dense
        else:
            n_moe = sum(1 for idx in range(self.n_layers)
                        if self.moe_every and idx % self.moe_every == self.moe_offset)
        return self.param_count() - n_moe * inactive


# ---------------------------------------------------------------------------
# Input shapes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str                         # train | prefill | decode


LM_SHAPES: tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4096, 256, "train"),
    ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    ShapeConfig("decode_32k", 32768, 128, "decode"),
    ShapeConfig("long_500k", 524288, 1, "decode"),
)

SHAPES_BY_NAME = {s.name: s for s in LM_SHAPES}


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether a (arch x shape) cell should be run, and why not if skipped."""
    if shape.name == "long_500k" and not model.subquadratic:
        return False, ("pure full-attention architecture: 512k decode KV-cache "
                       "attention is quadratic-cost at prefill and the cache itself "
                       "is O(L*S); skipped per assignment, see DESIGN.md")
    return True, ""


# ---------------------------------------------------------------------------
# Quantization configuration (the paper's technique)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class QuantConfig:
    """Quantization settings (paper Sec. 2.2-2.4 plus baselines).

    ``method`` selects the algorithm from the ``repro.quantize`` registry:
    "daq" (paper Alg. 1, objective = ``metric``), "daq-per-block",
    "absmax", "smoothquant", "awq".
    """

    method: str = "daq"              # registry key (repro.quantize)
    fmt: str = "fp8_e4m3"            # fp8_e4m3 | fp8_e5m2 | int8 | int4
    granularity: str = "block"       # tensor | channel | block
    block_size: int = 128
    metric: str = "sign"             # sign | cosine | mse | hybrid
    alpha_min: float = 0.8
    alpha_max: float = 1.25
    n_coarse: int = 5
    n_fine: int = 10
    fine_delta: float = 0.0          # 0 -> one coarse grid step
    per_block_alpha: bool = False    # beyond-paper: independent alpha per block/channel
    use_fused_kernel: bool = False   # Pallas one-pass candidate sweep (block fp8)
    hybrid_lambda: float = 0.5       # hybrid = lambda*sign + (1-lambda)*cosine
    skip_patterns: tuple[str, ...] = ("norm", "bias", "router", "a_log", "ssm_dt", "conv")

    def resolved_fine_delta(self) -> float:
        if self.fine_delta:
            return self.fine_delta
        return (self.alpha_max - self.alpha_min) / max(self.n_coarse - 1, 1)


# ---------------------------------------------------------------------------
# Training / run configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    microbatch: int = 0               # 0 -> no gradient accumulation
    remat: str = "full"               # none | full | dots_saveable
    opt_state_dtype: str = "float32"  # float32 | bfloat16 | int8 (8-bit Adam)
    grad_compress: str = "none"       # none | int8_ef (error-feedback int8 all-reduce)
    seed: int = 0


@dataclass(frozen=True)
class RunConfig:
    arch: str = "glm4-9b"
    shape: str = "train_4k"
    multi_pod: bool = False
    fsdp: bool = True                 # shard params over the data axis too (ZeRO-3)
    use_quantized_weights: bool = False  # serve path with fp8 weights
    checkpoint_dir: str = "/tmp/repro_ckpt"
    save_every: int = 100
    keep_last: int = 3


def asdict(cfg: Any) -> dict:
    return dataclasses.asdict(cfg)


def replace(cfg: Any, **kw) -> Any:
    return dataclasses.replace(cfg, **kw)
