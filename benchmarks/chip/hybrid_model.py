"""A hybrid configuration file (Mamba-2 + attention + MoE, Granite 4.0-H's
block) -> the program's model, and its seeded weights.

As in ``model.py``, the weights are the benchmark's own and only the
tree's layout comes from the program.  What differs from the dense draw:

* an expert stack ``[held, D, F]`` of one layer is ``held`` matrices, each
  drawn from its own key with fan-in D (not ``held``);
* the Mamba-2 leaves are drawn as Mamba-2 initializes them (the
  configuration's ``assumed``): A = exp(a_log) uniform in [1, 16], dt =
  softplus(dt_bias) log-uniform in [1e-3, 0.1], D (``d_skip``) = 1, the
  same for post and base;
* conv biases are biases (N(0, 0.1^2)); conv weights have fan-in K;
* the embedding table has std ``embed_std`` (the configuration's
  ``assumed``).  The head is the table itself, so the input token's own
  logit stands out by about 12 sqrt(D) embed_std over the layers' RMS; at
  unit-scale rows it would be the greedy token at every position, and a
  comparison of served tokens would compare nothing.

The configuration's file keeps the published ``config.json`` keys as
they are; the program's keys sit beside them.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import model as M

HYBRID_KEYS = ("family", "n_layers", "d_model", "d_ff", "d_ff_shared",
               "head_dim", "n_heads", "n_kv_heads", "vocab_size", "top_k",
               "expert_first", "ssm_state", "d_inner", "ssm_head_dim",
               "ssm_chunk", "conv_kernel", "attn_every", "moe_every",
               "moe_offset", "attn_scale", "embedding_multiplier",
               "residual_multiplier", "logits_scaling", "norm_eps",
               "tie_embeddings", "qkv_bias")


def model_config(cfg: dict, n_layers: int | None = None):
    """The program's ``ModelConfig``: the router scores all
    ``n_experts_routed`` experts and this chip holds ``n_experts`` of them;
    ``position_embedding_type`` nope is ``rope_theta`` 0."""
    from repro.configs.base import ModelConfig
    kw = {k: cfg[k] for k in HYBRID_KEYS if k in cfg}
    kw.update(n_experts=cfg["n_experts_routed"],
              n_experts_held=cfg["n_experts"], n_shared_experts=1,
              rope_theta=(0.0 if cfg.get("position_embedding_type") == "nope"
                          else float(cfg["rope_theta"])))
    if n_layers is not None:
        kw["n_layers"] = n_layers
    return ModelConfig(name=cfg["name"], **kw)


def leaf_kind(path: str) -> str:
    """How a leaf is drawn: 'embed', 'a_log', 'dt_bias', 'd_skip',
    'conv_bias', or ``model.leaf_kind``'s 'norm', 'bias', 'weight'."""
    name = path.split("/")[-1]
    if path.endswith("embed/embed"):
        return "embed"
    if name in ("a_log", "dt_bias", "d_skip"):
        return name
    if name in ("conv_x_b", "conv_bc_b"):
        return "conv_bias"
    return M.leaf_kind(path, None)


def leaf_pair(key, kind: str, shape, rel_delta: float, embed_std: float):
    """(post, base) in float32 of one layer of a leaf of the hybrid tree
    (or of an unstacked leaf) of kind ``kind``.  A weight [I, O] is
    base ~ N(0, 1/I), post = base + rel_delta * an independent draw of the
    same scale; an expert stack [held, I, O] is ``held`` such matrices,
    each from its own key."""
    if kind == "a_log":
        A = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
        return jnp.log(A), jnp.log(A)
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(0.1)))
        inv = dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1
        return inv, inv
    if kind in ("d_skip", "norm"):
        one = jnp.ones(shape, jnp.float32)
        return one, one
    if kind in ("bias", "conv_bias"):
        b = M.BIAS_STD * jax.random.normal(key, shape, jnp.float32)
        return b, b
    if len(shape) == 3:       # an expert stack: one matrix per expert
        keys = jax.vmap(lambda e: jax.random.fold_in(key, e))(
            jnp.arange(shape[0]))
        return jax.vmap(lambda k: leaf_pair(k, kind, shape[1:], rel_delta,
                                            embed_std))(keys)
    std = embed_std if kind == "embed" else shape[0] ** -0.5
    kb, kd = jax.random.split(key)
    base = std * jax.random.normal(kb, shape, jnp.float32)
    post = base + rel_delta * std * jax.random.normal(kd, shape, jnp.float32)
    return post, base


@partial(jax.jit, static_argnames=("kind", "shape", "dtype", "rel", "std"))
def draw_layer(key, leaf: int, layer: int, kind: str, shape, dtype,
               rel: float, std: float):
    """(post, base) in ``dtype`` of layer ``layer`` of leaf number ``leaf``
    (layer 0 for an unstacked leaf).  Leaves of one kind and shape share
    the compiled draw."""
    p, b = leaf_pair(M.layer_key(key, leaf, layer), kind, shape, rel, std)
    return p.astype(dtype), b.astype(dtype)


@partial(jax.jit, static_argnames=("kind", "shape", "dtype", "rel", "std",
                                   "layers"))
def draw_stacked(key, leaf: int, kind: str, shape, dtype, rel: float,
                 std: float, layers: int):
    """:func:`draw_layer` of layers ``0 .. layers - 1``, stacked."""
    return jax.vmap(lambda l: draw_layer(key, leaf, l, kind, shape, dtype,
                                         rel, std))(jnp.arange(layers))


def make_pair(cfg: dict, seed: int):
    """The seeded (post, base) trees of ``distinct_layers`` layers, in the
    program's layout and dtype, made on the device leaf by leaf."""
    mcfg = model_config(cfg, cfg["distinct_layers"])
    specs, treedef = M.layout(mcfg)
    key = M.seed_key(seed)
    posts, bases = [], []
    rel, std = cfg["rel_delta"], cfg["embed_std"]
    for i, (path, shape, dtype, stacked) in enumerate(specs):
        kind = leaf_kind(path)
        if stacked:
            p, b = draw_stacked(key, i, kind, tuple(shape[1:]), dtype, rel,
                                std, shape[0])
        else:
            p, b = draw_layer(key, i, 0, kind, tuple(shape), dtype, rel, std)
        posts.append(p)
        bases.append(b)
    return (jax.tree_util.tree_unflatten(treedef, posts),
            jax.tree_util.tree_unflatten(treedef, bases))
