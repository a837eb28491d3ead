"""Pure-jnp oracle for the scale-search sweep kernel."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.formats import FP8_E4M3, qdq
from repro.kernels.scale_search.kernel import N_STATS


def _qdq_e4m3(w, scale, qmax: float = 448.0):
    return qdq(w, scale, dataclasses.replace(FP8_E4M3, qmax=qmax))


def sweep_partials_ref(wp: jnp.ndarray, wb: jnp.ndarray, s0: jnp.ndarray,
                       alphas: jnp.ndarray, *, block_size: int = 128,
                       qmax: float = 448.0) -> jnp.ndarray:
    """Same contract as kernel.sweep_partials_pallas, via plain jnp."""
    I, O = wp.shape
    bs = block_size
    nbi, nbo = I // bs, O // bs
    wp32 = wp.astype(jnp.float32).reshape(nbi, bs, nbo, bs)
    wb32 = wb.astype(jnp.float32).reshape(nbi, bs, nbo, bs)
    dp = wp32 - wb32

    def per_cand(alpha):
        scale = (alpha * s0)[:, None, :, None]
        wq = _qdq_e4m3(wp32, scale, qmax)
        dq = wq - wb32
        diff = dq - dp
        red = lambda x: jnp.sum(x, axis=(1, 3))
        stats = jnp.stack([
            red(diff * diff),
            red((jnp.sign(dp) == jnp.sign(dq)).astype(jnp.float32)),
            red(dp * dq),
            red(dp * dp),
            red(dq * dq),
            jnp.zeros((nbi, nbo)), jnp.zeros((nbi, nbo)),
            jnp.zeros((nbi, nbo)),
        ], axis=-1)                                   # [nbi, nbo, 8]
        return stats

    return jax.vmap(per_cand)(alphas)                 # [n_cand, nbi, nbo, 8]
