"""Sharding rules: parameter, optimizer-state, batch and cache PartitionSpecs.

Conventions (DESIGN.md §5):

* **TP over ``model``** (Megatron): attention q/k/v out-dim and MLP up/gate
  out-dim are column-parallel; attention out-proj and MLP down-proj are
  row-parallel.  MoE expert stacks shard their expert axis over ``model``
  (EP) when divisible, else fall back to per-expert TP.  Mamba components
  are head-structured (z/x/dt) -> ``model``; head-shared (B/C) -> replicated.
* **FSDP over ``data``** (ZeRO-3): the non-TP matrix dim of every weight is
  sharded over ``data``; GSPMD all-gathers per layer at use and
  reduce-scatters gradients.
* **``pod`` is pure DP**: params replicate across pods (a cross-DCN ZeRO
  would serialize every layer on the slow link); only the gradient
  all-reduce crosses pods.
* KV heads shard over ``model`` only when ``n_kv_heads % model_size == 0``
  (GQA caps KV TP); otherwise k/v projections and the KV cache replicate
  over ``model`` and GSPMD inserts the cheap gathers.

All rules key off the parameter's tree path, so quantized trees
(``.../wq/data``, ``.../wq/scale``) inherit the dense weight's layout.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.policy import path_str
from repro.launch.mesh import dp_axes, model_size

STACK_PREFIXES = ("stack", "prefix", "enc_stack")


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _base_param_spec(parts: list[str], shape: tuple[int, ...],
                     cfg: ModelConfig, mesh) -> P:
    """Spec for the *unstacked* weight (trailing dims of the leaf)."""
    name = parts[-1]
    msz = model_size(mesh)
    kv_tp = cfg.n_kv_heads and cfg.n_kv_heads % msz == 0
    is_moe_expert = "moe" in parts and "shared" not in parts

    if name in ("embed",):
        return P("model", "data")
    if name == "w_head":
        return P("data", "model")
    if name == "wq":
        return P("data", "model")
    if name in ("wk", "wv"):
        return P("data", "model") if kv_tp else P("data", None)
    if name == "wo":
        return P("model", "data")
    if name == "bias_q":
        return P("model")
    if name in ("bias_k", "bias_v"):
        return P("model") if kv_tp else P(None)
    if name in ("w_gate", "w_up"):
        if is_moe_expert:                      # [E, D, F]
            from repro.runtime import flags
            if flags["moe_sharding"] == "ep_data_tp_model":
                return P("data", None, "model")
            if cfg.n_experts % msz == 0:
                return P("model", "data", None)
            return P(None, "data", "model")
        return P("data", "model")              # [D, F]
    if name == "w_down":
        if is_moe_expert:                      # [E, F, D]
            from repro.runtime import flags
            if flags["moe_sharding"] == "ep_data_tp_model":
                return P("data", "model", None)
            if cfg.n_experts % msz == 0:
                return P("model", None, "data")
            return P(None, "model", "data")
        return P("model", "data")              # [F, D]
    if name == "router":
        return P(None, None)
    if name in ("in_z", "in_x"):
        return P("data", "model")
    if name in ("in_bc", "in_dt"):
        return P("data", None)
    if name == "out_proj":
        return P("model", "data")
    if name == "conv_x_w":
        return P(None, "model")
    if name == "conv_x_b":
        return P("model")
    if name == "norm_scale" and "mamba" in parts:
        return P("model")
    # norms, small biases, conv_bc, a_log/dt_bias/d_skip: replicate
    return P(*([None] * len(shape)))


def _fit(spec: tuple, shape: tuple[int, ...], mesh) -> tuple:
    """Drop axis assignments whose size does not divide the dim (jit rejects
    non-divisible input shardings; GQA/vocab oddities fall back to
    replication on that dim)."""
    out = []
    for dim, entry in zip(shape, spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        out.append(entry if dim % n == 0 else None)
    return tuple(out)


def param_spec(path, shape: tuple[int, ...], cfg: ModelConfig, mesh) -> P:
    """Spec for a parameter leaf (handles period stacking and quantized
    storage/scale leaves)."""
    parts = path_str(path).split("/")
    quant_field = None
    if parts[-1] in ("data", "scale"):        # QuantizedTensor fields
        quant_field = parts[-1]
        parts = parts[:-1]
    lead = 1 if parts[0] in STACK_PREFIXES else 0

    if quant_field == "scale":
        # scale shapes: block [.., I/bs, 1, O/bs, 1]; channel [.., 1, O]; tensor []
        w_spec = tuple(_base_param_spec(parts, shape, cfg, mesh))
        body = len(shape) - lead
        if body <= 0:
            return P()
        if body == 4:                          # block granularity
            s = (w_spec[0] if len(w_spec) > 0 else None,
                 None,
                 w_spec[1] if len(w_spec) > 1 else None,
                 None)
        elif body == 2:                        # channel granularity
            s = (None, w_spec[1] if len(w_spec) > 1 else None)
        else:
            s = tuple([None] * body)
        s = _fit(s, shape[lead:], mesh)
        return P(*([None] * lead), *s)

    body_shape = shape[lead:]
    spec = tuple(_base_param_spec(parts, body_shape, cfg, mesh))
    spec = spec + (None,) * (len(body_shape) - len(spec))
    spec = _fit(spec[: len(body_shape)], body_shape, mesh)
    return P(*([None] * lead), *spec)


def params_shardings(params_shape: Any, cfg: ModelConfig, mesh) -> Any:
    """NamedSharding tree matching an (abstract) params tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    out = [NamedSharding(mesh, param_spec(p, tuple(l.shape), cfg, mesh))
           for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


def init_params(model, key, mesh=None) -> Any:
    """``model.init(key)``; with a mesh, built straight into
    :func:`params_shardings` (a jit with ``out_shardings``), so no device
    ever holds the whole tree."""
    if mesh is None:
        return model.init(key)
    shard = params_shardings(jax.eval_shape(model.init, key), model.cfg,
                             mesh)
    return jax.jit(model.init, out_shardings=shard)(key)


# ---------------------------------------------------------------------------
# Optimizer-state specs (mirror the param layout)
# ---------------------------------------------------------------------------

def opt_state_shardings(opt_shape: Any, params_shape: Any, cfg: ModelConfig,
                        mesh) -> Any:
    """Shardings for the optimizer state produced by ``init_opt_state``.

    fp32/bf16 moments share the param spec.  int8 moments are blocked along
    the last axis: shape = param.shape[:-1] + (nb, 256); scales
    param.shape[:-1] + (nb, 1) — both inherit the param spec with the last
    axis split (blocks keep the axis sharding, the intra-block dim is
    replicated).
    """
    p_flat, _ = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = {path_str(p): param_spec(p, tuple(l.shape), cfg, mesh)
             for p, l in p_flat}

    def one(path, leaf):
        name = path_str(path)                 # "mu/<param path>/m" etc.
        parts = name.split("/")
        if parts[0] == "step":
            return NamedSharding(mesh, P())
        if parts[0] == "master":              # fp32 master copy: param spec
            pkey = "/".join(parts[1:])
        else:
            pkey = "/".join(parts[1:-1])
        base = specs[pkey]
        p_shape = None
        for pp, ll in p_flat:
            if path_str(pp) == pkey:
                p_shape = tuple(ll.shape)
                break
        if len(leaf.shape) == len(p_shape):       # fp32/bf16 moment
            spec = tuple(base)
        else:                                     # int8 blocked (+1 dim)
            spec = tuple(base) + (None,)
        spec = spec + (None,) * (len(leaf.shape) - len(spec))
        spec = _fit(spec[: len(leaf.shape)], tuple(leaf.shape), mesh)
        return NamedSharding(mesh, P(*spec))

    flat, treedef = jax.tree_util.tree_flatten_with_path(opt_shape)
    return jax.tree_util.tree_unflatten(
        treedef, [one(p, l) for p, l in flat])


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------

def _dp(mesh, batch: int):
    axes = dp_axes(mesh)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return axes if batch % n == 0 else None


def batch_shardings(batch_shape: dict, mesh) -> dict:
    """Shard every batch leaf's leading (batch) dim over the dp axes."""
    def one(leaf):
        b = leaf.shape[0] if leaf.ndim else 1
        dp = _dp(mesh, b)
        spec = P(dp, *([None] * (leaf.ndim - 1))) if dp else \
            P(*([None] * leaf.ndim))
        return NamedSharding(mesh, spec)
    return jax.tree.map(one, batch_shape)


def cache_shardings(cache_shape: Any, cfg: ModelConfig, mesh) -> Any:
    """Decode-cache layout: [n_periods, B, ...] leaves -> batch over dp,
    KV-head / SSM-head dims over model when divisible."""
    msz = model_size(mesh)
    ssm_tp = cfg.family in ("ssm", "hybrid") and cfg.n_ssm_heads % msz == 0

    def one(path, leaf):
        name = path_str(path).split("/")[-1]
        if name == "lengths":
            dp = _dp(mesh, leaf.shape[0])
            return NamedSharding(mesh, P(dp))
        from repro.engine.paged import BSTATE_KEYS
        if name in BSTATE_KEYS or name == "moe_counts":
            # paged-cache allocator state and the MoE counts: tiny int/bool
            # arrays, replicated
            return NamedSharding(mesh, P(*([None] * leaf.ndim)))
        if name in ("pk", "pv"):              # [n, NB+1, bs, Kv_eff, hd]
            # block pool: same head-axis layout as the dense cache; the
            # block axis is shared by every slot, so it replicates over dp
            kv_tp = leaf.shape[3] % msz == 0
            spec = P(None, None, None, "model" if kv_tp else None, None)
            return NamedSharding(
                mesh, P(*_fit(tuple(spec), tuple(leaf.shape), mesh)))
        dp = _dp(mesh, leaf.shape[1])
        if name in ("k", "v", "mk", "mv"):    # [n, B, S, Kv_eff, hd]
            kv_tp = leaf.shape[3] % msz == 0  # repeat-sharded layout (lm.py)
            spec = P(None, dp, None, "model" if kv_tp else None, None)
        elif name == "h":                      # [n, B, nh, P, N]
            spec = P(None, dp, "model" if ssm_tp else None, None, None)
        elif name == "conv_x":                 # [n, B, K-1, di]
            spec = P(None, dp, None, "model" if ssm_tp else None)
        elif name == "conv_bc":
            spec = P(None, dp, None, None)
        else:
            spec = P(*([None] * leaf.ndim))
        spec = P(*_fit(tuple(spec) + (None,) * (leaf.ndim - len(tuple(spec))),
                       tuple(leaf.shape), mesh))
        return NamedSharding(mesh, spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(
        treedef, [one(p, l) for p, l in flat])
