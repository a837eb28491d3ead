"""Process-wide runtime/perf flags (no arch semantics — see QuantConfig /
ModelConfig for those), the ambient-mesh sharding helpers, and the
persistent compilation cache.

The flags are read at *trace time*; callers that flip them must
rebuild/re-lower (build_model returns fresh closures, so a fresh Model + jit
is enough).  They let structural choices be toggled without threading knobs
through every layer signature.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

flags: dict = {
    # Megatron-style sequence parallelism: residual stream is sharded over
    # the `model` axis between layers (all-gather at QKV/MLP-in,
    # reduce-scatter after out-proj — GSPMD derives the pair from the carry
    # constraint).  Cuts per-layer saved activations by model_size.
    "seq_shard": True,
    # constrain the residual batch dim over the dp axes between layers
    "batch_constraint": True,
    # attention q/kv chunk sizes for the online-softmax scan
    "q_chunk": 1024,
    "kv_chunk": 1024,
    # debug/calibration: python-loop over layers instead of lax.scan, so an
    # eager forward sees concrete per-layer values (SmoothQuant/AWQ stats)
    "unroll_layers": False,
    # KV-cache storage dtype: "bfloat16" | "float8_e4m3fn".  fp8 halves the
    # cache-read traffic that dominates long-context decode (k/v values are
    # O(1-10) so the unscaled E4M3 range is safe; ~6% relative noise on
    # attention scores — beyond-paper optimization)
    "kv_cache_dtype": "bfloat16",
    # MoE expert-weight layout (launch/sharding.py):
    #   "ep_model"          E over `model`, D over `data` (FSDP) — weights
    #                       all-gathered over data at every use
    #   "ep_data_tp_model"  E over `data`, F over `model` — weights fully
    #                       local; REFUTED for the GShard einsum-dispatch
    #                       formulation this repo used to train with (kimi
    #                       train collective 160s -> 526s: routing tensors
    #                       blow up when E shards the batch axis); not
    #                       measured for the dropless sort-based layer.
    "moe_sharding": "ep_model",
}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at a fixed path,
    ``<repo>/.jax_cache``: the path is part of the cache key, so a moving
    directory would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _ambient_mesh():
    """The mesh set with ``jax.set_mesh`` around the current trace, or None
    (plain single-device runs, and Engine serving, which shards through
    its arguments' placements instead)."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _mesh_axes():
    mesh = _ambient_mesh()
    if mesh is None:
        return None, None, 0
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names) or None
    msz = mesh.shape["model"] if "model" in names else 0
    return names, dp, msz


def attn_shard_specs(kv_heads: int, q_groups: int):
    """(q_spec, kv_spec) for grouped-GQA tensors q [B,S,Kv,G,hd],
    k/v [B,S,Kv,hd] — tiered: shard KV heads over `model` when divisible,
    else shard the q groups (kv replicated over model), else no constraint.
    Returns (None, None) when no mesh/model axis is available."""
    from jax.sharding import PartitionSpec as P

    names, dp, msz = _mesh_axes()
    if not msz or msz == 1:
        return None, None
    if kv_heads % msz == 0:
        return (P(dp, None, "model", None, None), P(dp, None, "model", None))
    if q_groups % msz == 0:
        return (P(dp, None, None, "model", None), P(dp, None, None, None))
    return None, None


def kv_repeat_factor(kv_heads: int, q_groups: int,
                     for_cache: bool = False) -> int:
    """GQA KV-repeat sharding: when Kv < model_size but Kv*r divides it,
    repeating each KV head r times makes the head axis model-shardable —
    per-device KV memory becomes (r/msz) of the original instead of a full
    replica (e.g. Kv=8, msz=16: r=2 -> 1/8 per device vs 1x replicated).

    ``for_cache``: train/prefill attention prefers the q-group sharding
    tier when G % msz == 0 (repeat there multiplies k/v activation compute
    — measured 2x collective regression on glm4 train), but the DECODE
    CACHE always wants the repeat: a replicated cache costs msz-times the
    memory and read traffic (glm4 decode: peak 10.1 -> 5.1 GiB,
    collectives 461 -> 33 ms).  Returns 1 when not applicable."""
    if flags.get("force_kv_repeat", 0):
        return int(flags["force_kv_repeat"])
    _, _, msz = _mesh_axes()
    if not msz or msz <= 1 or kv_heads % msz == 0:
        return 1
    if not for_cache and q_groups % msz == 0:
        return 1  # q-group sharding tier already covers this case
    if msz % kv_heads == 0:
        r = msz // kv_heads
        if q_groups % r == 0:
            return r
    return 1


def constrain(x, spec):
    """Sharding constraint under the ambient mesh (no-op without one)."""
    if spec is None or x is None or _ambient_mesh() is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def residual_constraint(x):
    """Apply the configured residual-stream sharding constraint (no-op when
    there is no ambient mesh, e.g. plain CPU tests)."""
    from jax.sharding import PartitionSpec as P

    names, dp, _ = _mesh_axes()
    if names is None:
        return x
    seq = "model" if (flags["seq_shard"] and "model" in names) else None
    if not flags["batch_constraint"]:
        dp = None
    if dp is None and seq is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, P(dp, seq, *([None] * (x.ndim - 2))))
