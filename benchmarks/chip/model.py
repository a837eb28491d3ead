"""A configuration file -> the program's model, and its seeded weights.

The weights are the benchmark's own: every leaf of the post/base pair is
drawn from the seed by :func:`leaf_pair`, one layer at a time, so the
reference can draw any single layer again without the program.  Only the
tree's layout (leaf paths and shapes) comes from the program, since that
is the interface it serves from.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# The configuration's quantization policy, handed to the program: matrices
# (>= 2-D, both trailing sizes >= 16) whose path names none of these are
# quantized.
SKIP = ("norm", "bias", "router", "a_log", "ssm_dt", "conv")
MODEL_KEYS = ("family", "n_layers", "d_model", "d_ff", "head_dim", "n_heads",
              "n_kv_heads", "vocab_size", "qkv_bias", "norm_eps",
              "rope_theta", "tie_embeddings")
BIAS_STD = 0.1


def load_config(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def model_config(cfg: dict, n_layers: int | None = None):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    kw = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    if n_layers is not None:
        kw["n_layers"] = n_layers
    return ModelConfig(name=cfg["name"], **kw)


def quant_config(cfg: dict):
    from repro.configs.base import QuantConfig
    return QuantConfig(**cfg["quant"], skip_patterns=SKIP)


def seed_key(seed: int):
    """A PRNG key for any non-negative seed (wider than 32 bits too)."""
    key = jax.random.PRNGKey(0)
    for part in (seed % 2**32, seed // 2**32 % 2**32):
        key = jax.random.fold_in(key, np.uint32(part))
    return key


def leaf_kind(path: str, shape) -> str:
    """'norm' (ones), 'bias' (small normal) or 'weight' (fan-in normal)."""
    if "norm" in path:
        return "norm"
    if "bias" in path:
        return "bias"
    return "weight"


def leaf_pair(key, path: str, shape, rel_delta: float):
    """(post, base) of one 2-D weight, or one layer of a stacked leaf, in
    float32: base ~ N(0, 1/fan_in) (N(0, 1) for the embedding table),
    post = base + rel_delta * an independent draw of the same scale."""
    kind = leaf_kind(path, shape)
    if kind == "norm":
        one = jnp.ones(shape, jnp.float32)
        return one, one
    kb, kd = jax.random.split(key)
    if kind == "bias":
        base = BIAS_STD * jax.random.normal(kb, shape, jnp.float32)
        return base, base
    std = 1.0 if path.endswith("embed/embed") else shape[0] ** -0.5
    base = std * jax.random.normal(kb, shape, jnp.float32)
    post = base + rel_delta * std * jax.random.normal(kd, shape, jnp.float32)
    return post, base


def layout(mcfg):
    """[(path, shape, dtype, stacked)] of the program's parameter tree."""
    from repro.core.policy import path_str
    from repro.models import build_model
    shapes = jax.eval_shape(build_model(mcfg).init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for p, s in leaves:
        name = path_str(p)
        out.append((name, s.shape, s.dtype, name.startswith("stack/")))
    return out, treedef


def layer_key(key, leaf_index: int, layer: int):
    return jax.random.fold_in(jax.random.fold_in(key, leaf_index), layer)


def make_pair(cfg: dict, seed: int):
    """The seeded (post, base) trees of ``distinct_layers`` layers, in the
    program's layout and dtype, made on the device in one jitted call."""
    mcfg = model_config(cfg, cfg["distinct_layers"])
    specs, treedef = layout(mcfg)
    rel = cfg["rel_delta"]

    def build(key):
        posts, bases = [], []
        for i, (path, shape, dtype, stacked) in enumerate(specs):
            if stacked:
                ks = jax.vmap(lambda l: layer_key(key, i, l))(
                    jnp.arange(shape[0]))
                p, b = jax.vmap(lambda k: leaf_pair(k, path, shape[1:],
                                                    rel))(ks)
            else:
                p, b = leaf_pair(layer_key(key, i, 0), path, shape, rel)
            posts.append(p.astype(dtype))
            bases.append(b.astype(dtype))
        return (jax.tree_util.tree_unflatten(treedef, posts),
                jax.tree_util.tree_unflatten(treedef, bases))

    return jax.jit(build)(seed_key(seed))


def repeat_stack(params, times: int):
    """The quantized tree with its stacked layers repeated ``times`` times
    (layer l of the result is distinct layer l % distinct_layers)."""
    def rep(path, x):
        if path and getattr(path[0], "key", None) == "stack":
            return jnp.concatenate([x] * times, axis=0)
        return x
    return jax.jit(lambda t: jax.tree_util.tree_map_with_path(rep, t))(params)
