"""Delta-aware metrics (paper Sec. 2.3).

All metrics take the post-training delta ``dp = W_post - W_base`` and the
quantized delta ``dq = Q_s(W_post) - W_base`` (paper Eqs. 1-2) and return a
scalar.  ``objective`` returns the maximization objective used by the scale
search (``-MSE`` for the reconstruction metric, per paper Table 1 footnote).

The metrics are also exposed in partial-sum form so that block-wise /
channel-wise variants (beyond-paper per-block alpha search) and the Pallas
fused-search kernel can accumulate them in one pass over the weights.
"""
from __future__ import annotations

import jax.numpy as jnp

EPS = 1e-12

# Keys of the partial-sum form (see ``partial_sums``).
PARTIAL_KEYS = ("sq_err", "n_sign_match", "dot", "dp_sq", "dq_sq", "count")


# ---------------------------------------------------------------------------
# Whole-tensor metrics (paper Eqs. 6, 8, 9)
# ---------------------------------------------------------------------------

def mse(dp: jnp.ndarray, dq: jnp.ndarray) -> jnp.ndarray:
    """Eq. 6/7: reconstruction MSE; identical whether computed on deltas or
    on (W_quant, W_post) — the base model cancels (paper Eq. 7)."""
    d = (dq - dp).astype(jnp.float32)
    return jnp.mean(d * d)


def sign_rate(dp: jnp.ndarray, dq: jnp.ndarray) -> jnp.ndarray:
    """Eq. 8: fraction of elements whose delta sign is preserved (sign(0)=0)."""
    sp = jnp.sign(dp.astype(jnp.float32))
    sq = jnp.sign(dq.astype(jnp.float32))
    return jnp.mean((sp == sq).astype(jnp.float32))


def cosine(dp: jnp.ndarray, dq: jnp.ndarray) -> jnp.ndarray:
    """Eq. 9: cosine similarity between the flattened delta vectors."""
    dp = dp.astype(jnp.float32)
    dq = dq.astype(jnp.float32)
    num = jnp.sum(dp * dq)
    den = jnp.sqrt(jnp.sum(dp * dp)) * jnp.sqrt(jnp.sum(dq * dq))
    return num / jnp.maximum(den, EPS)


def objective(name: str, dp: jnp.ndarray, dq: jnp.ndarray,
              hybrid_lambda: float = 0.5) -> jnp.ndarray:
    """Scalar maximization objective M (paper Eq. 3)."""
    if name == "mse":
        return -mse(dp, dq)
    if name == "sign":
        return sign_rate(dp, dq)
    if name == "cosine":
        return cosine(dp, dq)
    if name == "hybrid":
        # Beyond-paper: paper Sec 3.5 takeaway 3 suggests a hybrid metric.
        return hybrid_lambda * sign_rate(dp, dq) + (1 - hybrid_lambda) * cosine(dp, dq)
    raise ValueError(f"unknown metric {name!r}")


# ---------------------------------------------------------------------------
# Partial-sum forms: reduce over `axes`, keep the remaining (block) axes.
# Used by the per-block alpha search and mirrored by kernels/scale_search.
# ---------------------------------------------------------------------------

def partial_sums(dp: jnp.ndarray, dq: jnp.ndarray, axes) -> dict[str, jnp.ndarray]:
    import numpy as np
    dp = dp.astype(jnp.float32)
    dq = dq.astype(jnp.float32)
    diff = dq - dp
    sq_err = jnp.sum(diff * diff, axis=axes)
    count = float(np.prod([dp.shape[a] for a in (axes if isinstance(axes, tuple) else (axes,))]))
    return {
        "sq_err": sq_err,
        "n_sign_match": jnp.sum((jnp.sign(dp) == jnp.sign(dq)).astype(jnp.float32), axis=axes),
        "dot": jnp.sum(dp * dq, axis=axes),
        "dp_sq": jnp.sum(dp * dp, axis=axes),
        "dq_sq": jnp.sum(dq * dq, axis=axes),
        "count": jnp.full(sq_err.shape, count, jnp.float32),
    }


def objective_from_partials(name: str, p: dict[str, jnp.ndarray],
                            hybrid_lambda: float = 0.5) -> jnp.ndarray:
    """Per-block objective from partial sums (same semantics as `objective`
    restricted to a block)."""
    if name == "mse":
        return -p["sq_err"] / jnp.maximum(p["count"], 1.0)
    if name == "sign":
        return p["n_sign_match"] / jnp.maximum(p["count"], 1.0)
    cos = p["dot"] / jnp.maximum(jnp.sqrt(p["dp_sq"]) * jnp.sqrt(p["dq_sq"]), EPS)
    if name == "cosine":
        return cos
    if name == "hybrid":
        sr = p["n_sign_match"] / jnp.maximum(p["count"], 1.0)
        return hybrid_lambda * sr + (1 - hybrid_lambda) * cos
    raise ValueError(f"unknown metric {name!r}")


def combine_partials(parts: list[dict[str, jnp.ndarray]]) -> dict[str, jnp.ndarray]:
    """Sum partial sums across tensors (for model-level aggregate metrics)."""
    out: dict[str, jnp.ndarray] = {}
    for key in PARTIAL_KEYS:
        out[key] = sum(jnp.sum(p[key]) for p in parts)
    return out


def metrics_from_partials(p: dict[str, jnp.ndarray]) -> dict[str, jnp.ndarray]:
    return {
        "mse": p["sq_err"] / jnp.maximum(p["count"], 1.0),
        "sign_rate": p["n_sign_match"] / jnp.maximum(p["count"], 1.0),
        "cosine": p["dot"] / jnp.maximum(jnp.sqrt(p["dp_sq"]) * jnp.sqrt(p["dq_sq"]), EPS),
        "delta_l2": jnp.sqrt(p["sq_err"]),
    }
