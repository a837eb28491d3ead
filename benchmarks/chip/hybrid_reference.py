"""Plain float32 reference forward of the hybrid block (Granite 4.0-H),
at the published widths, on the reference's own Alg. 1 codes.

Nothing here imports the program.  Every matrix of the seeded pair is
drawn again (``hybrid_model.draw_layer``), quantized by ``reference.alg1``
in float32 (one alpha and per-column scales per matrix: each expert of a
stack is a matrix of its own), and the forward runs on those codes and
scales, in plain ``jnp`` under the highest matmul precision:

    x0 = m_emb * E[tok]
    x <- x + m_res * mixer(RMSNorm(x))     (attention at l % 10 == 5,
                                            else Mamba-2)
    x <- x + m_res * (MoE(h) + SwiGLU_shared(h)),  h = RMSNorm(x)
    logits = RMSNorm(x) E^T / logits_scaling

The Mamba-2 recurrence runs token by token (S_t = exp(dt_t A) S_{t-1} +
dt_t x_t B_t^T, y_t = S_t C_t + D x_t); the MoE computes every held expert
for every token and weights it by the softmax over the top-k router
logits (0 for the experts a token does not choose), with no sort.  It runs
one layer at a time over all sampled sequences, the attention in query
chunks, so that it fits on one chip.

The control, one precision step down: ``fp8_acts`` rounds every matmul
input to float8 e4m3.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference as R
from benchmarks.chip.hybrid_model import draw_layer, leaf_kind
from benchmarks.chip.model import SKIP, seed_key

HIGHEST = jax.lax.Precision.HIGHEST


def eligible(path: str, shape) -> bool:
    return (len(shape) >= 2 and min(shape[-2:]) >= 16
            and not any(s in path.lower() for s in SKIP))


def ref_weights(cfg: dict, seed: int, specs) -> dict:
    """path -> per-layer list of entries: (blocked codes, scales, shape) of
    a quantized matrix, a list of those for an expert stack, or a float32
    array (norms, biases, the router, the Mamba leaves)."""
    key = seed_key(seed)
    qc = cfg["quant"]
    out: dict = {}
    for i, (path, shape, dtype, stacked) in enumerate(specs):
        shp = tuple(shape[1:] if stacked else shape)
        out[path] = []
        for layer in (range(shape[0]) if stacked else [None]):
            p, b = draw_layer(key, i, 0 if layer is None else layer,
                              leaf_kind(path), shp, dtype, cfg["rel_delta"],
                              cfg["embed_std"])
            if not eligible(path, shp):
                out[path].append(p.astype(jnp.float32))
                continue
            mats = []
            for pm, bm in (zip(p, b) if len(shp) == 3 else [(p, b)]):
                t = R.tile(qc, pm.shape)
                Pb, Bb = R.blocked(pm, t), R.blocked(bm, t)
                a, s0, _, _ = R.alg1(Pb, Bb, pm.shape, qc)
                mats.append((R.fp8_codes(Pb, a * s0, jnp.float32), a * s0,
                             tuple(pm.shape)))
            out[path].append(mats if len(shp) == 3 else mats[0])
    return out


def _dense(entry):
    if isinstance(entry, list):
        return jnp.stack([_dense(e) for e in entry])
    if isinstance(entry, tuple):
        codes, scale, shape = entry
        return R.dequant(codes, scale, shape)
    return entry


def _mm(x, w, fp8_acts):
    if fp8_acts:
        x = x.astype(R.FP8).astype(jnp.float32)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _conv(x, w, b):
    """Depthwise causal conv over time of [S, T, C], then silu."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, k:k + T] * w[k] for k in range(K)) + b)


@partial(jax.jit, static_argnames=("arch", "fp8_acts"))
def mamba_layer(x, w, arch, fp8_acts=False):
    """x + m_res * Mamba-2(RMSNorm(x)) over [S, T, D] float32."""
    eps, m_res, N, nh, P = arch
    S, T, _ = x.shape
    h = _rmsnorm(x, w["ln1"], eps)
    z = _mm(h, w["in_z"], fp8_acts)
    xs = _conv(_mm(h, w["in_x"], fp8_acts), w["conv_x_w"], w["conv_x_b"])
    bc = _conv(_mm(h, w["in_bc"], fp8_acts), w["conv_bc_w"], w["conv_bc_b"])
    dt = jax.nn.softplus(_mm(h, w["in_dt"], fp8_acts) + w["dt_bias"])
    A = -jnp.exp(w["a_log"])
    xh = xs.reshape(S, T, nh, P)

    def step(St, inp):
        x_t, b_t, c_t, dt_t = inp              # [S,nh,P] [S,N] [S,N] [S,nh]
        St = (jnp.exp(dt_t * A)[:, :, None, None] * St
              + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
        return St, jnp.einsum("shpn,sn->shp", St, c_t, precision=HIGHEST)

    S0 = jnp.zeros((S, nh, P, N), jnp.float32)
    seq = (jnp.moveaxis(xh, 1, 0), jnp.moveaxis(bc[..., :N], 1, 0),
           jnp.moveaxis(bc[..., N:], 1, 0), jnp.moveaxis(dt, 1, 0))
    _, y = jax.lax.scan(step, S0, seq)
    y = jnp.moveaxis(y, 0, 1) + w["d_skip"][:, None] * xh
    g = _rmsnorm(y.reshape(S, T, nh * P) * jax.nn.silu(z), w["norm_scale"],
                 eps)
    return x + m_res * _mm(g, w["out_proj"], fp8_acts)


@partial(jax.jit, static_argnames=("arch", "fp8_acts", "q_chunk"))
def attention_layer(x, w, arch, fp8_acts=False, q_chunk=256):
    """x + m_res * attention(RMSNorm(x)): causal GQA, no position encoding,
    softmax scale ``scale``, over [S, T, D] float32 in query chunks."""
    eps, m_res, H, Kv, hd, scale = arch
    S, T, _ = x.shape
    G = H // Kv
    h = _rmsnorm(x, w["ln1"], eps)
    q = _mm(h, w["wq"], fp8_acts).reshape(S, T, Kv, G, hd)
    k = _mm(h, w["wk"], fp8_acts).reshape(S, T, Kv, hd)
    v = _mm(h, w["wv"], fp8_acts).reshape(S, T, Kv, hd)
    pos = jnp.arange(T)

    def chunk(c):
        qc = jax.lax.dynamic_slice_in_dim(q, c * q_chunk, q_chunk, 1)
        s = jnp.einsum("bqkgh,bckh->bkgqc", qc, k, precision=HIGHEST) * scale
        qpos = c * q_chunk + jnp.arange(q_chunk)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        return jnp.einsum("bkgqc,bckh->bqkgh", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    o = jax.lax.map(chunk, jnp.arange(T // q_chunk))
    o = jnp.moveaxis(o, 0, 1).reshape(S, T, H * hd)
    return x + m_res * _mm(o, w["wo"], fp8_acts)


@partial(jax.jit, static_argnames=("arch", "fp8_acts"))
def moe_layer(x, w, arch, fp8_acts=False):
    """x + m_res * (MoE(h) + SwiGLU_shared(h)), h = RMSNorm(x): the held
    experts ``first .. first + held - 1`` of the router's, top-k softmax
    gates, every held expert computed for every token."""
    eps, m_res, top_k, first = arch
    S, T, D = x.shape
    h = _rmsnorm(x, w["ln2"], eps).reshape(S * T, D)
    top, idx = jax.lax.top_k(_mm(h, w["router"], False), top_k)
    gates = jax.nn.softmax(top, axis=-1)
    held = first + jnp.arange(w["w_gate"].shape[0])
    weight = jnp.sum(gates[:, :, None] * (idx[:, :, None] == held), axis=1)
    hx = h.astype(R.FP8).astype(jnp.float32) if fp8_acts else h
    g = jnp.einsum("td,edf->tef", hx, w["w_gate"], precision=HIGHEST)
    u = jnp.einsum("td,edf->tef", hx, w["w_up"], precision=HIGHEST)
    a = weight[:, :, None] * jax.nn.silu(g) * u
    if fp8_acts:
        a = a.astype(R.FP8).astype(jnp.float32)
    y = jnp.einsum("tef,efd->td", a, w["w_down"], precision=HIGHEST)
    sh = jax.nn.silu(_mm(h, w["s_gate"], fp8_acts)) * _mm(h, w["s_up"],
                                                          fp8_acts)
    y = y + _mm(sh, w["s_down"], fp8_acts)
    return x + m_res * y.reshape(S, T, D)


_NAMES = {"mamba/norm_scale": "norm_scale", "ln1/norm_scale": "ln1",
          "ln2/norm_scale": "ln2", "moe/shared/w_gate": "s_gate",
          "moe/shared/w_up": "s_up", "moe/shared/w_down": "s_down"}


def layer_weights(weights: dict, pos: int, layer: int) -> dict:
    """Float32 weights of distinct layer ``layer`` at position ``pos`` of
    the period, by short name."""
    w = {}
    pre = f"stack/L{pos}/"
    for path, entries in weights.items():
        if path.startswith(pre):
            short = path[len(pre):]
            w[_NAMES.get(short, short.split("/")[-1])] = _dense(
                entries[layer])
    return w


def forward_hidden(cfg: dict, weights: dict, tokens: np.ndarray,
                   fp8_acts: bool = False):
    """Last-layer hidden states [S, T, D] of the float32 forward over
    ``tokens`` [S, T] (right-padded; every mixer is causal)."""
    eps, m_res = float(cfg["norm_eps"]), float(cfg["residual_multiplier"])
    table = _dense(weights["embed/embed"][0])
    x = cfg["embedding_multiplier"] * jnp.take(table, jnp.asarray(tokens),
                                               axis=0)
    del table
    P, dl = cfg["attn_every"], cfg["distinct_layers"]
    nh = cfg["d_inner"] // cfg["ssm_head_dim"]
    for l in range(cfg["n_layers"]):
        pos, distinct = l % P, (l % dl) // P
        w = layer_weights(weights, pos, distinct)
        if pos == P // 2:
            x = attention_layer(x, w, (eps, m_res, cfg["n_heads"],
                                       cfg["n_kv_heads"], cfg["head_dim"],
                                       float(cfg["attn_scale"])), fp8_acts)
        else:
            x = mamba_layer(x, w, (eps, m_res, cfg["ssm_state"], nh,
                                   cfg["ssm_head_dim"]), fp8_acts)
        x = moe_layer(x, w, (eps, m_res, cfg["top_k"], cfg["expert_first"]),
                      fp8_acts)
    return x


def serve_gaps(cfg: dict, weights: dict, samples, t_max: int,
               fp8_acts: bool = False):
    """Per served token: its gap below the reference's best logit.  With
    the control (``fp8_acts``), the gap of the token that the control's
    forward puts first at the same position, read in the reference's
    logits."""
    S = len(samples)
    toks = np.zeros((S, t_max), np.int32)
    rows = []
    for s, (prompt, served) in enumerate(samples):
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        toks[s, :len(seq)] = seq
        L = len(prompt)
        rows += [(s, L - 1 + j, served[j]) for j in range(len(served))]
    eps = float(cfg["norm_eps"])
    final = weights["final_norm/norm_scale"][0]
    head = _dense(weights["embed/embed"][0]).T / cfg["logits_scaling"]
    control = fp8_acts
    h = forward_hidden(cfg, weights, toks)
    hc = forward_hidden(cfg, weights, toks, True) if control else None
    out = []
    for c in range(0, len(rows), 256):
        part = rows[c:c + 256]
        si = jnp.asarray([r[0] for r in part])
        pi = jnp.asarray([r[1] for r in part])
        ref = R.logits(h[si, pi], final, head, eps)
        if control:
            tok = jnp.argmax(R.logits(hc[si, pi], final, head, eps,
                                      fp8_acts), -1)
        else:
            tok = jnp.asarray([r[2] for r in part], jnp.int32)
        out.append(np.asarray(R.gaps(ref, tok)))
    return np.concatenate(out)
