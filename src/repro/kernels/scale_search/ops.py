"""Jitted wrapper: DAQ coarse/fine candidate sweep over one weight tensor.

``sweep(wp, wb, alphas, qcfg-ish args)`` pads to the block grid, runs the
fused kernel (or its jnp oracle with ``use_kernel=False``), and reduces the
per-block partials to the per-candidate / per-block objective values the
search needs.  Slot layout matches core.metrics.partial_sums.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.granularity import pad_to_blocks
from repro.kernels.scale_search.kernel import sweep_partials_pallas
from repro.kernels.scale_search.ref import sweep_partials_ref

EPS = 1e-12


@partial(jax.jit, static_argnames=("block_size", "qmax", "use_kernel"))
def sweep(wp: jnp.ndarray, wb: jnp.ndarray, alphas: jnp.ndarray, *,
          block_size: int = 128, qmax: float = 448.0,
          use_kernel: bool = True) -> dict:
    """Returns dict of [n_cand] tensor-level partials + [n_cand, nbi, nbo]
    block-level partials for per-block alpha selection."""
    wp32 = wp.astype(jnp.float32)
    wb32 = wb.astype(jnp.float32)
    wp_p, orig = pad_to_blocks(wp32, block_size)
    wb_p, _ = pad_to_blocks(wb32, block_size)
    nbi, nbo = wp_p.shape[0] // block_size, wp_p.shape[1] // block_size
    amax = jnp.max(jnp.abs(wp_p.reshape(nbi, block_size, nbo, block_size)),
                   axis=(1, 3))
    s0 = jnp.maximum(amax, EPS) / qmax
    fn = sweep_partials_pallas if use_kernel else sweep_partials_ref
    parts = fn(wp_p, wb_p, s0, alphas.astype(jnp.float32),
               block_size=block_size, qmax=qmax)

    # [n_cand, nbi, nbo, 8] -> block + tensor reductions
    block = {
        "sq_err": parts[..., 0], "n_sign_match": parts[..., 1],
        "dot": parts[..., 2], "dp_sq": parts[..., 3], "dq_sq": parts[..., 4],
    }
    tensor = {k: jnp.sum(v, axis=(1, 2)) for k, v in block.items()}
    n = wp.shape[0] * wp.shape[1]  # padding contributes zeros to sums; the
    # sign-match count over padding is a constant (sign(0)==sign(0)) per
    # block — subtract it exactly:
    pad_elems = wp_p.size - n
    tensor["n_sign_match"] = tensor["n_sign_match"] - pad_elems
    tensor["count"] = jnp.full(alphas.shape, float(n), jnp.float32)
    return {"tensor": tensor, "block": block, "s0": s0, "grid": (nbi, nbo)}


def objective_values(parts: dict, metric: str,
                     hybrid_lambda: float = 0.5) -> jnp.ndarray:
    """[n_cand] objective values from sweep() tensor partials."""
    t = parts["tensor"]
    n = jnp.maximum(t["count"], 1.0)
    if metric == "mse":
        return -t["sq_err"] / n
    if metric == "sign":
        return t["n_sign_match"] / n
    cos = t["dot"] / jnp.maximum(
        jnp.sqrt(t["dp_sq"]) * jnp.sqrt(t["dq_sq"]), EPS)
    if metric == "cosine":
        return cos
    if metric == "hybrid":
        return hybrid_lambda * t["n_sign_match"] / n + (1 - hybrid_lambda) * cos
    raise ValueError(metric)
