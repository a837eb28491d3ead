"""Mamba-2 (SSD — state-space duality) block: chunked train/prefill scan and
constant-memory recurrent decode.

The SSD formulation computes the selective-state-space recurrence

    h_t = a_t * h_{t-1} + dt_t * x_t B_t^T          (h: [P, N] per head)
    y_t = C_t h_t + D * x_t

in matmul form: the sequence is split into chunks of length Q; within a
chunk the output is a masked (C_t . B_s) "attention" matmul, and a single
[P, N] state per chunk carries the recurrence across chunks via
``lax.scan``.  This keeps all heavy ops as MXU-shaped matmuls (the reason
SSD exists) and gives O(S * Q) memory instead of O(S^2).

TP note: the canonical Mamba-2 fuses [z | x | B | C | dt] into one
``in_proj``.  We keep them as **separate projections** so the head-structured
components (z, x, dt — all multiples of n_heads) shard cleanly over the
``model`` mesh axis while the head-shared B/C (ngroups=1) stay replicated;
the math is identical, and tensor parallelism needs no halo exchange on the
fused dim.  (Recorded in DESIGN.md §Hardware-adaptation.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ssm_decode
from repro.models.common import dense_init, split
from repro.quant_runtime import qlinear

# Bytes of one head group's [B, Q, Q, heads] intra-chunk decay in ssd_scan.
INTRA_BYTES = 64 << 20


def init_mamba(key, cfg: ModelConfig, dtype) -> dict:
    D = cfg.d_model
    di, N, nh = cfg.resolved_d_inner, cfg.ssm_state, cfg.n_ssm_heads
    K = cfg.conv_kernel
    ks = split(key, 7)
    return {
        "in_z": dense_init(ks[0], D, di, dtype),
        "in_x": dense_init(ks[1], D, di, dtype),
        "in_bc": dense_init(ks[2], D, 2 * N, dtype),
        "in_dt": dense_init(ks[3], D, nh, dtype),
        "conv_x_w": (0.1 * jax.random.normal(ks[4], (K, di), jnp.float32)
                     ).astype(dtype),
        "conv_x_b": jnp.zeros((di,), dtype),
        "conv_bc_w": (0.1 * jax.random.normal(ks[5], (K, 2 * N), jnp.float32)
                      ).astype(dtype),
        "conv_bc_b": jnp.zeros((2 * N,), dtype),
        "a_log": jnp.zeros((nh,), jnp.float32),        # A = -exp(a_log) = -1
        "dt_bias": jnp.full((nh,), -2.0, jnp.float32),  # softplus(-2) ~ 0.13
        "d_skip": jnp.ones((nh,), jnp.float32),
        "norm_scale": jnp.ones((di,), dtype),
        "out_proj": dense_init(ks[6], di, D, dtype),
    }


@jax.named_scope("mamba")
def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 state: jnp.ndarray | None = None, valid=None):
    """Depthwise causal conv over S.  x [B,S,C]; w [K,C].

    ``state`` [B,K-1,C] prepends history (decode/prefill continuation).
    ``valid`` [B] marks per-row true lengths of a right-padded chunk: the
    returned state is then the history as of row ``valid[b]`` (pad rows
    must not enter the recurrence — chunked prefill).
    Returns (silu(out) [B,S,C] fp32, new_state [B,K-1,C]).
    """
    K = w.shape[0]
    Bsz, S, C = x.shape
    if state is None:
        state = jnp.zeros((Bsz, K - 1, C), x.dtype)
    ext = jnp.concatenate([state.astype(x.dtype), x], axis=1)  # [B,S+K-1,C]
    out = jnp.zeros((Bsz, S, C), jnp.float32)
    for k in range(K):  # K is 4: unrolled taps, XLA fuses into one pass
        out = out + ext[:, k: k + S].astype(jnp.float32) * w[k].astype(jnp.float32)
    out = out + b.astype(jnp.float32)
    if valid is None:
        new_state = ext[:, S:]
    else:  # ext row (K-1) + t holds input position t
        idx = valid[:, None] + jnp.arange(K - 1)[None]
        new_state = jnp.take_along_axis(ext, idx[..., None], axis=1)
    return jax.nn.silu(out), new_state


@jax.named_scope("mamba")
def _gated_norm(y: jnp.ndarray, z: jnp.ndarray, scale: jnp.ndarray,
                eps: float = 1e-5) -> jnp.ndarray:
    """RMSNormGated: norm(y * silu(z)) * scale (fp32 internals)."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    ms = jnp.mean(g * g, axis=-1, keepdims=True)
    return g * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def _project(p: dict, h: jnp.ndarray, cfg: ModelConfig,
             conv_state: dict | None = None, valid=None):
    """Shared front half: projections + conv + dt.  Returns
    (z, xh [B,S,nh,P] fp32, Bc, Cc, dt, new_conv_state)."""
    Bsz, S, _ = h.shape
    di, N, nh, P = (cfg.resolved_d_inner, cfg.ssm_state, cfg.n_ssm_heads,
                    cfg.ssm_head_dim)
    z = qlinear.matmul(h, p["in_z"])
    xc = qlinear.matmul(h, p["in_x"])
    bc = qlinear.matmul(h, p["in_bc"])
    dt_raw = qlinear.matmul(h, p["in_dt"])
    cs_x = conv_state["conv_x"] if conv_state else None
    cs_bc = conv_state["conv_bc"] if conv_state else None
    xc, ns_x = _causal_conv(xc, p["conv_x_w"], p["conv_x_b"], cs_x, valid)
    bc, ns_bc = _causal_conv(bc, p["conv_bc_w"], p["conv_bc_b"], cs_bc, valid)
    xh = xc.reshape(Bsz, S, nh, P)
    Bc, Cc = bc[..., :N], bc[..., N:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    new_state = {"conv_x": ns_x.astype(jnp.bfloat16),
                 "conv_bc": ns_bc.astype(jnp.bfloat16)}
    return z, xh, Bc, Cc, dt, new_state


# ---------------------------------------------------------------------------
# Chunked SSD forward (train / prefill)
# ---------------------------------------------------------------------------

@jax.named_scope("mamba")
def ssd_scan(xh, Bc, Cc, dt, a_log, chunk: int, h0=None):
    """Chunked SSD.  xh [B,S,nh,P], Bc/Cc [B,S,N], dt [B,S,nh] (post-softplus).

    Returns (y [B,S,nh,P] fp32, h_final [B,nh,P,N] fp32).
    """
    Bsz, S, nh, P = xh.shape
    N = Bc.shape[-1]
    Q = min(chunk, S)
    nc = -(-S // Q)
    pad = nc * Q - S
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Bc = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
        Cc = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))

    A = -jnp.exp(a_log.astype(jnp.float32))            # [nh], negative
    la = A[None, None] * dt.astype(jnp.float32)        # log a_t  [B,S,nh]
    # chunk views, scan axis first
    xc = xh.reshape(Bsz, nc, Q, nh, P).transpose(1, 0, 2, 3, 4)
    bc = Bc.reshape(Bsz, nc, Q, N).transpose(1, 0, 2, 3)
    cc = Cc.reshape(Bsz, nc, Q, N).transpose(1, 0, 2, 3)
    dc = dt.reshape(Bsz, nc, Q, nh).transpose(1, 0, 2, 3)
    lc = la.reshape(Bsz, nc, Q, nh).transpose(1, 0, 2, 3)

    if h0 is None:
        h0 = jnp.zeros((Bsz, nh, P, N), jnp.float32)
    # heads per group of the intra-chunk term: its [B, Q, Q, heads] decay
    # tensor is the largest temporary, so it is built a group at a time
    g = nh
    while g > 1 and (Bsz * Q * Q * g * 4 > INTRA_BYTES or nh % g):
        g -= 1
    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def heads(a):  # [B, Q, nh, ...] -> [nh // g, B, Q, g, ...]
        a = a.reshape(a.shape[:2] + (nh // g, g) + a.shape[3:])
        return jnp.moveaxis(a, 2, 0)

    def body(h, xs):
        xq, bq, cq, dq, lq = xs                        # per-chunk tensors
        xq = xq.astype(jnp.float32)
        bq = bq.astype(jnp.float32)
        cq = cq.astype(jnp.float32)
        cum = jnp.cumsum(lq, axis=1)                   # [B,Q,nh] inclusive
        cb = jnp.einsum("btn,bsn->bts", cq, bq)        # [B,Q,Q]

        # intra-chunk: scores[t,s] = (C_t.B_s) * exp(cum_t - cum_s) * dt_s,
        # s <= t
        def intra(args):
            cg, dg, xg = args                          # [B,Q,g] x2, [B,Q,g,P]
            seg = cg[:, :, None, :] - cg[:, None, :, :]    # [B,t,s,g]
            decay = jnp.exp(jnp.where(tri[None, :, :, None], seg, -jnp.inf))
            w = cb[..., None] * decay * dg[:, None, :, :]
            return jnp.einsum("btsh,bshp->bthp", w, xg)

        if g == nh:
            y_intra = intra((cum, dq, xq))
        else:
            y_intra = jax.lax.map(intra, (heads(cum), heads(dq), heads(xq)))
            y_intra = jnp.moveaxis(y_intra, 0, 2).reshape(Bsz, Q, nh, P)
        # the state as [B, nh*P, N]: one layout for decode and prefill
        hf = h.reshape(Bsz, nh * P, N)
        # inter-chunk: contribution of h (state before this chunk)
        y_inter = jnp.einsum("btn,bkn->btk", cq, hf).reshape(Bsz, Q, nh, P)
        y_inter = y_inter * jnp.exp(cum)[..., None]    # exp(sum_{r<=t} la_r)
        # chunk state update: decay from s to the chunk's end
        rem = jnp.exp(cum[:, -1:, :] - cum)
        xw = (xq * (dq * rem)[..., None]).reshape(Bsz, Q, nh * P)
        contrib = jnp.einsum("bsk,bsn->bkn", xw, bq)
        h_new = (hf * jnp.repeat(jnp.exp(cum[:, -1]), P, axis=1)[..., None]
                 + contrib).reshape(Bsz, nh, P, N)
        return h_new, y_intra + y_inter

    h_fin, ys = jax.lax.scan(body, h0, (xc, bc, cc, dc, lc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bsz, nc * Q, nh, P)
    return y[:, :S], h_fin


def mamba_forward(p: dict, x_in: jnp.ndarray, h: jnp.ndarray,
                  cfg: ModelConfig, cache: dict | None = None):
    """Full Mamba-2 sublayer on normed input ``h``; ``x_in`` is the residual
    source dtype reference.  Returns (out [B,S,D], new_cache or None)."""
    Bsz, S, _ = h.shape
    di = cfg.resolved_d_inner
    z, xh, Bc, Cc, dt, conv_state = _project(p, h, cfg, cache)
    h0 = cache["h"] if cache is not None else None
    y, h_fin = ssd_scan(xh, Bc, Cc, dt, p["a_log"], cfg.ssm_chunk, h0)
    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] \
        * xh.astype(jnp.float32)
    y = _gated_norm(y.reshape(Bsz, S, di), z, p["norm_scale"])
    out = qlinear.matmul(y.astype(x_in.dtype), p["out_proj"])
    return out, {"h": h_fin, **conv_state}


def mamba_train(p: dict, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    out, _ = mamba_forward(p, h, h, cfg, cache=None)
    return out


def mamba_prefill_chunk(p: dict, x_in: jnp.ndarray, h: jnp.ndarray,
                        cfg: ModelConfig, cache: dict, valid: jnp.ndarray):
    """One prefill *chunk* through the Mamba-2 sublayer with state threading.

    ``h`` [B, C, D] holds rows at positions ``start..start+C-1`` of each
    slot's prompt; rows ``>= valid[b]`` are pads and are masked to **exact
    no-ops** of the SSD recurrence (``x = 0``, ``dt = 0`` post-softplus —
    the same zeros ``ssd_scan`` pads with internally), so the carried state
    and the real rows' outputs are bit-identical to the corresponding
    chunk of a one-shot :func:`mamba_forward` whenever chunk boundaries
    fall on multiples of ``cfg.ssm_chunk`` (the engine enforces
    ``chunk_size % ssm_chunk == 0`` for stacks with SSM layers).
    Slots with ``valid == 0`` pass their state through untouched.
    """
    Bsz, C, _ = h.shape
    di = cfg.resolved_d_inner
    z, xh, Bc, Cc, dt, conv_state = _project(p, h, cfg, cache, valid=valid)
    vm = jnp.arange(C)[None, :] < valid[:, None]            # [B, C]
    xh = jnp.where(vm[:, :, None, None], xh, 0.0)
    dt = jnp.where(vm[:, :, None], dt, 0.0)
    Bc = jnp.where(vm[:, :, None], Bc, 0.0)
    Cc = jnp.where(vm[:, :, None], Cc, 0.0)
    y, h_fin = ssd_scan(xh, Bc, Cc, dt, p["a_log"], cfg.ssm_chunk,
                        cache["h"])
    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] \
        * xh.astype(jnp.float32)
    y = _gated_norm(y.reshape(Bsz, C, di), z, p["norm_scale"])
    out = qlinear.matmul(y.astype(x_in.dtype), p["out_proj"])
    return out, {"h": h_fin, **conv_state}


def mamba_verify_chunk(p: dict, x_in: jnp.ndarray, h: jnp.ndarray,
                       cfg: ModelConfig, cache: dict, valid: jnp.ndarray):
    """Speculative-verify chunk: the **exact recurrence**, stepped row by
    row — a bitwise mirror of ``valid[b]`` successive :func:`mamba_decode`
    calls (same projections, same conv, the same per-step update
    ``ssm_decode.ref.step``; on a TPU decode runs it as the Pallas kernel,
    which multiplies in another order), unlike :func:`mamba_prefill_chunk`
    whose SSD chunk math reorders the float ops.  The speculative engine needs its
    verifier logits (and the rolled-back state on rejection) to equal what
    per-token decode would have produced, so the chunk here trades the SSD
    matmul form for per-row decode parity; chunks are ``n_spec + 1`` rows,
    so the sequential scan stays cheap.

    Rows ``>= valid[b]`` are state no-ops (``dt = 0``: decay ``exp(0) = 1``,
    update ``0``); slots with ``valid == 0`` pass state and conv history
    through untouched.  Returns (out [B,C,D], new_cache) — output rows at
    and beyond ``valid[b]`` are garbage, callers must mask.
    """
    Bsz, C, _ = h.shape
    di = cfg.resolved_d_inner
    z, xh, Bc, Cc, dt, conv_state = _project(p, h, cfg, cache, valid=valid)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    xh32 = xh.astype(jnp.float32)
    vm = jnp.arange(C)[None, :] < valid[:, None]            # [B, C]
    dt_m = jnp.where(vm[:, :, None], dt, 0.0)

    @jax.named_scope("mamba")
    def body(hst, xs):                 # xs: [B,nh,P] [B,N] [B,N] [B,nh]
        return ssm_decode.ref.step(hst, *xs, A)

    xs = (xh32.transpose(1, 0, 2, 3), Bc.transpose(1, 0, 2),
          Cc.transpose(1, 0, 2), dt_m.transpose(1, 0, 2))
    h_fin, ys = jax.lax.scan(body, cache["h"], xs)
    y = ys.transpose(1, 0, 2, 3)                            # [B,C,nh,P]
    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] * xh32
    y = _gated_norm(y.reshape(Bsz, C, di), z, p["norm_scale"])
    out = qlinear.matmul(y.astype(x_in.dtype), p["out_proj"])
    return out, {"h": h_fin, **conv_state}


def init_mamba_cache(cfg: ModelConfig, batch: int) -> dict:
    di, N, nh, P = (cfg.resolved_d_inner, cfg.ssm_state, cfg.n_ssm_heads,
                    cfg.ssm_head_dim)
    return {
        "h": jnp.zeros((batch, nh, P, N), jnp.float32),
        "conv_x": jnp.zeros((batch, cfg.conv_kernel - 1, di), jnp.bfloat16),
        "conv_bc": jnp.zeros((batch, cfg.conv_kernel - 1, 2 * N),
                             jnp.bfloat16),
    }


def mamba_decode(p: dict, x: jnp.ndarray, cache: dict, cfg: ModelConfig,
                 layer=None, keep=None):
    """One-token decode.  x [B,1,D].  Returns (y [B,1,D], new_cache).

    Uses the exact recurrence (no chunking) — one step of
    ``h = a h + dt x B^T; y = C h + D x``.  With ``layer``, ``cache["h"]``
    is the whole stacked state [n, B, nh, P, N] and the step updates it at
    ``layer`` in place (``kernels.ssm_decode``: a Pallas kernel on a TPU);
    the slots outside ``keep`` [B] (all when None) keep their state."""
    Bsz = x.shape[0]
    di = cfg.resolved_d_inner
    z, xh, Bc, Cc, dt, conv_state = _project(p, x, cfg, cache)
    with jax.named_scope("mamba"):
        A = -jnp.exp(p["a_log"].astype(jnp.float32))
        xh32 = xh.astype(jnp.float32)
        args = (xh32[:, 0], Bc[:, 0], Cc[:, 0], dt[:, 0], A)
        if layer is None:
            h, y = ssm_decode.ref.step(cache["h"], *args)
        else:
            h, y = ssm_decode.ops.ssm_decode(cache["h"], layer, keep, *args)
        y = y + p["d_skip"].astype(jnp.float32)[None, :, None] * xh32[:, 0]
    y = _gated_norm(y.reshape(Bsz, 1, di), z, p["norm_scale"])
    out = qlinear.matmul(y.astype(x.dtype), p["out_proj"])
    return out, {"h": h, **conv_state}
