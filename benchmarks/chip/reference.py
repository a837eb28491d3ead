"""Plain references, independent of the program: DAQ (paper Alg. 1) over the
benchmark's seeded pair, and a float32 forward of the dense decoder.

Nothing here imports the program.  The weights are drawn again from the
seed (``model.leaf_pair``), quantized by :func:`alg1` in plain ``jnp``, and
the forward runs on those codes and scales.  ``dt=jnp.bfloat16`` computes
Alg. 1 in bfloat16, and ``fp8_acts=True`` rounds every matmul input of the
forward to float8 e4m3: these are the controls, one precision step down,
that the comparisons in ``check.py`` must reject (with Alg. 1's search
skipped, alpha = 1, the third).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.model import leaf_pair

FP8 = jnp.float8_e4m3fn
QMAX = 448.0
HIGHEST = jax.lax.Precision.HIGHEST
# Rows of blocks scored at once: bounds the float32 temporaries of one
# [151552, 4096] embedding to a few tens of MB.
GROUP_BYTES = 64 << 20


# ---------------------------------------------------------------------------
# Alg. 1 on one [I, O] matrix, block granularity
# ---------------------------------------------------------------------------

def tile(qc: dict, shape) -> tuple[int, int]:
    """The tile [br, bc] the blocked view is made of: a block_size square
    for block granularity (one scale per tile); for channel granularity
    row groups of block_size (all rows when they do not divide) across
    every column (one scale per column, shared by the row groups)."""
    bs = qc["block_size"]
    if qc["granularity"] == "block":
        return bs, bs
    if qc["granularity"] == "channel":
        return (bs if shape[0] % bs == 0 else shape[0]), shape[1]
    raise ValueError(f"granularity {qc['granularity']!r}")


def blocked(w, t):
    """[I, O] -> zero-padded [nI, br, nO, bc] for the tile ``t``."""
    br, bc = t
    I, O = w.shape
    w = jnp.pad(w, ((0, -I % br), (0, -O % bc)))
    return w.reshape(w.shape[0] // br, br, w.shape[1] // bc, bc)


def unblocked(wb, shape):
    nI, br, nO, bc = wb.shape
    return wb.reshape(nI * br, nO * bc)[:shape[0], :shape[1]]


def _valid(gi, g, pb, shape):
    """int32 mask [g, br, nO, bc] of the real (unpadded) elements of
    group ``gi`` of ``g`` block rows."""
    _, br, nO, bc = pb.shape
    r = (gi * g + jnp.arange(g)[:, None]) * br + jnp.arange(br)[None, :]
    c = jnp.arange(nO)[:, None] * bc + jnp.arange(bc)[None, :]
    return ((r < shape[0])[:, :, None, None]
            & (c < shape[1])[None, None, :, :]).astype(jnp.int32)


def _groups(nI: int, row_bytes: int) -> int:
    fit = max(1, GROUP_BYTES // max(row_bytes, 1))
    return max(g for g in range(1, min(fit, nI) + 1) if nI % g == 0)


def scale_shape(pb, granularity: str):
    """Scales over the blocked view: [nI, 1, nO, 1] per tile, or
    [nI, 1, 1, O] per column (the same row for every row group)."""
    nI, _, nO, bc = pb.shape
    return (nI, 1, nO, 1) if granularity == "block" else (nI, 1, 1, bc)


def absmax_s0(pb, dt, granularity: str):
    axes = (1, 3) if granularity == "block" else (0, 1)
    amax = jnp.max(jnp.abs(pb.astype(dt)), axis=axes, keepdims=True)
    s0 = (jnp.maximum(amax, jnp.asarray(1e-12, dt)) / QMAX).astype(dt)
    return jnp.broadcast_to(s0, scale_shape(pb, granularity))


def fp8_codes(wb, scale, dt):
    """fp8 codes of W / scale.  Where the codes are widened again in the
    same program, an optimization barrier must sit between: the TPU
    compiler otherwise fuses convert(convert(x, fp8), f32) into x."""
    return jnp.clip(wb.astype(dt) / scale.astype(dt), -QMAX,
                    QMAX).astype(FP8)


def _map_groups(fn, arrays, g):
    """Sum ``fn(group index, *groups)`` over groups of ``g`` block rows of
    each array, so only one group's temporaries are live at a time."""
    nI = arrays[0].shape[0]
    split = [a.reshape(nI // g, g, *a.shape[1:]) for a in arrays]
    return jnp.sum(jax.lax.map(lambda xs: fn(*xs),
                               (jnp.arange(nI // g), *split)), axis=0)


@partial(jax.jit, static_argnames=("shape", "dt", "granularity"))
def sign_matches(pb, bb, alphas, shape, granularity, dt=jnp.float32):
    """For each alpha, the exact int32 count of elements where
    sign(W_post - W_base) equals sign(Q_{alpha s0}(W_post) - W_base), with
    the AbsMax scales s0 computed in the same program."""
    g = _groups(pb.shape[0], pb[0].size * 4)
    s0 = absmax_s0(pb, dt, granularity)

    def at(alpha):
        def one(gi, p, b, s):
            p, b = p.astype(dt), b.astype(dt)
            sc = alpha.astype(dt) * s
            q = jax.lax.optimization_barrier(fp8_codes(p, sc, dt))
            dq = q.astype(dt) * sc - b
            same = (jnp.sign(p - b) == jnp.sign(dq)).astype(jnp.int32)
            return jnp.sum(same * _valid(gi, g, pb, shape))
        return _map_groups(one, (pb, bb, s0), g)

    return jax.lax.map(at, jnp.asarray(alphas, jnp.float32))


def _scored(pb, bb, alphas, shape, gr, dt, n):
    """Sign-match counts at ``alphas``, padded to ``n`` so that every call
    for one matrix runs the same compiled program."""
    a = np.asarray(alphas, np.float32)
    a = np.concatenate([a, np.full(n - len(a), a[-1], np.float32)])
    return np.asarray(sign_matches(pb, bb, a, shape, gr, dt))[:len(alphas)]


def alg1(pb, bb, shape, qc: dict, dt=jnp.float32, at=1.0):
    """Paper Alg. 1 with the sign-rate objective: incumbent alpha = 1, a
    coarse grid, a fine grid one coarse step either side of the best, a
    candidate replacing the incumbent only when strictly better.  Returns
    (alpha, s0, best count, count at alpha ``at``).

    Every count of a matrix comes from one compiled program: two
    compilations of the same expression can count an element whose
    quantized delta is exactly zero differently."""
    lo_a, hi_a, nc, nf = (qc.get("alpha_min", 0.8), qc.get("alpha_max", 1.25),
                          qc.get("n_coarse", 5), qc.get("n_fine", 10))
    n = max(nc + 2, nf)
    cand = jnp.concatenate([jnp.ones((1,), jnp.float32),
                            jnp.linspace(lo_a, hi_a, nc)])
    gr = qc["granularity"]
    cm = _scored(pb, bb, [*np.asarray(cand), at], shape, gr, dt, n)
    cm, got = cm[:-1], int(cm[-1])
    i = 1 + int(np.argmax(cm[1:]))
    best_a, best = (cand[i], cm[i]) if cm[i] > cm[0] else (cand[0], cm[0])
    delta = (hi_a - lo_a) / max(nc - 1, 1)
    fine = jnp.linspace(jnp.maximum(lo_a, best_a - delta),
                        jnp.minimum(hi_a, best_a + delta), nf)
    fm = _scored(pb, bb, fine, shape, gr, dt, n)
    j = int(np.argmax(fm))
    if fm[j] > best:
        best_a, best = fine[j], fm[j]
    return jnp.float32(best_a), absmax_s0(pb, dt, gr), int(best), got


@partial(jax.jit, static_argnames=("shape",))
def code_mismatches(pb, scale, codes_b, shape):
    """Elements whose fp8 code differs from quantizing W_post with
    ``scale`` in float32."""
    g = _groups(pb.shape[0], pb[0].size * 4)

    def one(gi, p, s, c):
        mine = jax.lax.optimization_barrier(fp8_codes(p, s, jnp.float32))
        mine = mine.astype(jnp.float32)
        diff = (mine != c.astype(jnp.float32)).astype(jnp.int32)
        return jnp.sum(diff * _valid(gi, g, pb, shape))

    return _map_groups(one, (pb, scale, codes_b), g)


# ---------------------------------------------------------------------------
# The seeded pair, one matrix at a time
# ---------------------------------------------------------------------------

def matrices(specs, eligible):
    """(leaf index, path, layer or None, 2-D shape) of every matrix that
    ``eligible(path, shape, dtype)`` admits."""
    out = []
    for i, (path, shape, dtype, stacked) in enumerate(specs):
        if not eligible(path, shape, dtype):
            continue
        for l in (range(shape[0]) if stacked else [None]):
            out.append((i, path, l, tuple(shape[1:] if stacked else shape)))
    return out


@partial(jax.jit, static_argnames=("path", "shape", "rel", "dtype"))
def draw(key, path, shape, rel, dtype):
    """One matrix (or one layer of a stacked leaf) of the pair, drawn
    exactly as the benchmark made it, in the program's dtype."""
    p, b = leaf_pair(key, path, shape, rel)
    return p.astype(dtype), b.astype(dtype)


# ---------------------------------------------------------------------------
# Float32 forward of the dense decoder
# ---------------------------------------------------------------------------

def dequant(codes_b, scale, shape):
    return unblocked(codes_b.astype(jnp.float32) * scale, shape)


def _mm(x, w, fp8_acts):
    if fp8_acts:
        x = x.astype(FP8).astype(jnp.float32)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@partial(jax.jit, static_argnames=("arch", "fp8_acts", "q_chunk"))
def layer(x, w, arch, fp8_acts=False, q_chunk=256):
    """One pre-norm decoder layer over [S, T, D] float32, causal.

    ``w`` maps names to float32 arrays: ln1, ln2 [D]; wq, wk, wv, wo,
    w_gate, w_up, w_down [in, out]; optional bias_q/k/v."""
    D, H, Kv, hd, theta, eps = arch
    S, T, _ = x.shape
    G = H // Kv
    pos = jnp.arange(T)
    h = rmsnorm(x, w["ln1"], eps)
    q = _mm(h, w["wq"], fp8_acts) + w.get("bias_q", 0.0)
    k = _mm(h, w["wk"], fp8_acts) + w.get("bias_k", 0.0)
    v = _mm(h, w["wv"], fp8_acts) + w.get("bias_v", 0.0)
    q = rope(q.reshape(S, T, H, hd), pos, theta).reshape(S, T, Kv, G, hd)
    k = rope(k.reshape(S, T, Kv, hd), pos, theta)
    v = v.reshape(S, T, Kv, hd)

    def chunk(c):
        qc = jax.lax.dynamic_slice_in_dim(q, c * q_chunk, q_chunk, 1)
        s = jnp.einsum("bqkgh,bckh->bkgqc", qc, k,
                       precision=HIGHEST) * hd ** -0.5
        qpos = c * q_chunk + jnp.arange(q_chunk)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqc,bckh->bqkgh", p, v, precision=HIGHEST)

    o = jax.lax.map(chunk, jnp.arange(T // q_chunk))     # [n, S, qc, ...]
    o = jnp.moveaxis(o, 0, 1).reshape(S, T, H * hd)
    x = x + _mm(o, w["wo"], fp8_acts)
    h = rmsnorm(x, w["ln2"], eps)
    g = _mm(h, w["w_gate"], fp8_acts)
    u = _mm(h, w["w_up"], fp8_acts)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], fp8_acts)


@partial(jax.jit, static_argnames=("eps", "fp8_acts"))
def logits(h, final_scale, w_head, eps, fp8_acts=False):
    """Logits of rows of last-layer hidden states [R, D]."""
    return _mm(rmsnorm(h, final_scale, eps), w_head, fp8_acts)


@jax.jit
def gaps(ref_logits, tokens):
    """How far each token's reference logit lies below the row's best."""
    best = jnp.max(ref_logits, axis=-1)
    mine = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return best - mine
