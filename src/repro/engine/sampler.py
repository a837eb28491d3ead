"""Token samplers for the serving engine.

One jit-safe entry point :func:`sample` maps ``logits [..., V]`` to next-token
ids ``[...]`` under a static :class:`SamplingParams`:

* **greedy** — argmax (bit-identical to the pre-engine host loop);
* **temperature** — softmax sampling at ``temperature`` via
  ``jax.random.categorical``;
* **top-k** — logits outside the per-row top-k are masked to -inf before the
  categorical draw;
* **top-p (nucleus)** — after temperature, only the smallest set of tokens
  whose cumulative probability reaches ``top_p`` stays unmasked (the top
  token always survives; ties at the cut keep every equal-valued token).

:func:`warp_logits` exposes the shared distribution transform (top-k mask →
temperature → top-p mask) and :func:`probs` its normalized probabilities —
the speculative decoder's lossless rejection sampler needs the *warped*
draft and target distributions, not the raw logits, so both the accept test
and the residual draw see exactly what :func:`sample` would have sampled
from (engine/spec.py).

``SamplingParams`` is a frozen (hashable) dataclass so decode dispatches can
close over it and stay a single jit cache entry; the PRNG key is threaded by
the caller (the engine splits one key per decode step inside its scan).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

NEG_INF = -1e30


@dataclass(frozen=True)
class SamplingParams:
    greedy: bool = True
    temperature: float = 1.0
    top_k: int = 0          # 0 = no truncation
    top_p: float = 1.0      # 1.0 = no nucleus truncation

    def __post_init__(self):
        if not self.greedy and self.temperature <= 0:
            raise ValueError("temperature must be > 0 for sampling; "
                             "use greedy=True for argmax decoding")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")


def warp_logits(logits: jnp.ndarray, sp: SamplingParams) -> jnp.ndarray:
    """The sampling distribution's logits (fp32): top-k mask, then
    temperature, then top-p mask.  ``categorical(warp_logits(l))`` is what
    :func:`sample` draws for non-greedy params."""
    l32 = logits.astype(jnp.float32)
    V = l32.shape[-1]
    if 0 < sp.top_k < V:
        kth = jax.lax.top_k(l32, sp.top_k)[0][..., -1:]
        l32 = jnp.where(l32 < kth, NEG_INF, l32)
    l32 = l32 / sp.temperature
    if sp.top_p < 1.0:
        srt = jnp.sort(l32, axis=-1)[..., ::-1]           # descending
        ps = jax.nn.softmax(srt, axis=-1)
        cume = jnp.cumsum(ps, axis=-1) - ps               # mass BEFORE token
        keep = cume < sp.top_p                            # top token always
        thr = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True)
        l32 = jnp.where(l32 < thr, NEG_INF, l32)
    return l32


def probs(logits: jnp.ndarray, sp: SamplingParams) -> jnp.ndarray:
    """Normalized warped sampling distribution [..., V] (fp32)."""
    return jax.nn.softmax(warp_logits(logits, sp), axis=-1)


@jax.named_scope("sample")
def sample(logits: jnp.ndarray, key, sp: SamplingParams) -> jnp.ndarray:
    """logits [..., V] -> token ids [...] (int32).  jit- and scan-safe."""
    if sp.greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, warp_logits(logits, sp),
                                  axis=-1).astype(jnp.int32)
