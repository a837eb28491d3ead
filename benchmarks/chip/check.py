"""The comparisons that decide ``correct``: what the timed path produced
against the plain references of ``reference.py``.

* DAQ: for every quantized matrix of the seeded pair, the program's scales
  against its chosen alpha times the AbsMax scales, its fp8 codes against
  W_post quantized with those scales, its reported global sign rate
  against exact integer counts at its alphas, and the search itself: the
  reference's Alg. 1 best count against its count at the program's alpha,
  summed over all matrices (``search_gap``), all in float32.  The alphas
  are not compared one by one: near-ties between candidates fall either
  way under rounding, so a sound program's gap is small but not 0.
* Serving: a sample of the requests the window finished, drawn from the
  seed with the longest prompt among them; the reference runs once over
  each prompt with its served tokens, and the number compared is the
  widest gap by which a served token's logit lies below the reference's
  best logit at that position (greedy decoding serves the argmax, so a
  sound program only loses rounding there).

Each number's limit lives in ``limits/<cell>.json``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference as R
from benchmarks.chip.model import SKIP, layer_key, seed_key


def eligible(path: str, shape, dtype=None) -> bool:
    return (len(shape) >= 2 and min(shape[-2:]) >= 16
            and not any(s in path.lower() for s in SKIP))


def _matrix(key, i, path, layer, shape, cfg, dtype):
    pb, bb = R.draw(layer_key(key, i, 0 if layer is None else layer), path,
                    shape, cfg["rel_delta"], dtype)
    t = R.tile(cfg["quant"], shape)
    return R.blocked(pb, t), R.blocked(bb, t)


def _scales(scale, Pb, granularity):
    """The program's scales ([nI, 1, nO, 1] for blocks, [1, O] for
    channels) over the reference's blocked view."""
    if scale.ndim == 2:
        scale = scale.reshape(1, 1, 1, -1)
    return jnp.broadcast_to(scale.astype(jnp.float32),
                            R.scale_shape(Pb, granularity))


# ---------------------------------------------------------------------------
# DAQ
# ---------------------------------------------------------------------------

def daq_numbers(cfg: dict, seed: int, specs, candidate) -> dict:
    """Compare a quantizer's output with Alg. 1 in float32.

    ``candidate(i, path, layer, shape, Pb, Bb)`` returns that quantizer's
    (alpha, scales, fp8 codes [I, O]) for the matrix,
    and ``candidate.sign_rate`` is its global sign rate once all matrices
    were asked for."""
    key = seed_key(seed)
    qc = cfg["quant"]
    scale_err = 0.0
    mism = matched = best = total = 0
    for i, path, layer, shape in R.matrices(specs, eligible):
        dtype = specs[i][2]
        Pb, Bb = _matrix(key, i, path, layer, shape, cfg, dtype)
        alpha, scale, codes = candidate(i, path, layer, shape, Pb, Bb)
        _, s0, top, got = R.alg1(Pb, Bb, shape, qc, at=alpha)
        want = jnp.float32(alpha) * s0
        scale = _scales(scale, Pb, qc["granularity"])
        scale_err = max(scale_err, float(jnp.max(jnp.abs(scale - want)
                                                 / want)))
        mism += int(R.code_mismatches(
            Pb, scale, R.blocked(codes, R.tile(qc, shape)), shape))
        matched += got
        best += top
        total += int(np.prod(shape))
    return {"scale_rel_err": scale_err,
            "code_mismatches": mism,
            "sign_rate_gap": abs(candidate.sign_rate - matched / total),
            "search_gap": (best - matched) / total}


def program_candidate(qparams, report):
    """The program's quantized tree and report as a DAQ candidate."""
    is_q = lambda x: hasattr(x, "data") and hasattr(x, "scale")
    leaves = jax.tree_util.tree_flatten_with_path(qparams, is_leaf=is_q)[0]
    by_index = [leaf for _, leaf in leaves]

    def cand(i, path, layer, shape, Pb, Bb):
        q = by_index[i]
        alpha = np.asarray(report.per_leaf[path]["alpha"])
        if layer is None:
            return float(alpha), q.scale, q.data
        return float(alpha[layer]), q.scale[layer], q.data[layer]

    cand.sign_rate = report.global_chosen["sign_rate"]
    return cand


def reference_candidate(qc: dict, dt=jnp.float32, search: bool = True):
    """Alg. 1 of the reference in the program's place.  The controls:
    ``dt=jnp.bfloat16`` computes it one precision step below the
    configuration's float32 search; ``search=False`` skips the search and
    keeps the AbsMax scales (alpha = 1)."""
    acc = {"matched": 0, "total": 0}

    def cand(i, path, layer, shape, Pb, Bb):
        a, s0, best, at_one = R.alg1(Pb, Bb, shape, qc, dt=dt)
        if not search:
            a, best = jnp.float32(1.0), at_one
        scale = jnp.asarray(a, dt) * s0
        codes = R.unblocked(R.fp8_codes(Pb, scale, dt), shape)
        acc["matched"] += best
        acc["total"] += int(np.prod(shape))
        cand.sign_rate = acc["matched"] / acc["total"]
        return float(a), scale, codes

    cand.sign_rate = 0.0
    return cand


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def ref_weights(cfg: dict, seed: int, specs) -> dict:
    """The reference's own quantized model: Alg. 1 in float32 over every
    eligible matrix of the seeded pair.  path -> per-layer list of
    (blocked codes, scales, shape) or of float32 arrays (norms, biases)."""
    key = seed_key(seed)
    qc = cfg["quant"]
    out: dict = {}
    for i, (path, shape, dtype, stacked) in enumerate(specs):
        layers = range(shape[0]) if stacked else [None]
        shp = tuple(shape[1:] if stacked else shape)
        out[path] = []
        for layer in layers:
            if not eligible(path, shp):
                p, _ = R.draw(layer_key(key, i, 0 if layer is None else layer),
                              path, shp, cfg["rel_delta"], dtype)
                out[path].append(p.astype(jnp.float32))
                continue
            Pb, Bb = _matrix(key, i, path, layer, shp, cfg, dtype)
            a, s0, _, _ = R.alg1(Pb, Bb, shp, qc)
            scale = a * s0
            out[path].append((R.fp8_codes(Pb, scale, jnp.float32), scale,
                              shp))
    return out


def _dense(entry):
    if isinstance(entry, tuple):
        codes, scale, shape = entry
        return R.dequant(codes, scale, shape)
    return entry


def forward_hidden(cfg: dict, weights: dict, tokens: np.ndarray,
                   fp8_acts: bool = False):
    """Last-layer hidden states [S, T, D] of the float32 forward over
    ``tokens`` [S, T] (right-padded; attention is causal)."""
    arch = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
            cfg["head_dim"], float(cfg["rope_theta"]), float(cfg["norm_eps"]))
    table = _dense(weights["embed/embed"][0])
    x = jnp.take(table, jnp.asarray(tokens), axis=0)
    del table
    dl = cfg["distinct_layers"]
    names = {p.split("/", 2)[2]: p for p in weights if p.startswith("stack/")}
    for l in range(cfg["n_layers"]):
        w = {}
        for short, p in names.items():
            leaf = short.split("/")[-1]
            key = {"norm_scale": short.split("/")[0]}.get(leaf, leaf)
            w[key] = _dense(weights[p][l % dl])
        x = R.layer(x, w, arch, fp8_acts)
    return x


def serve_gaps(cfg: dict, weights: dict, samples, t_max: int,
               fp8_acts: bool = False) -> np.ndarray:
    """Per served token: its gap below the reference's best logit.  With
    ``fp8_acts`` (the control), the gap of the token that the fp8 forward
    puts first at the same position, read in the reference's logits."""
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
    head = _dense(weights["embed/w_head"][0])
    h = forward_hidden(cfg, weights, toks)
    hc = forward_hidden(cfg, weights, toks, True) if fp8_acts else None
    out = []
    for c in range(0, len(rows), 256):
        part = rows[c:c + 256]
        si = jnp.asarray([r[0] for r in part])
        pi = jnp.asarray([r[1] for r in part])
        ref = R.logits(h[si, pi], final, head, eps)
        if fp8_acts:
            tok = jnp.argmax(R.logits(hc[si, pi], final, head, eps, True), -1)
        else:
            tok = jnp.asarray([r[2] for r in part], jnp.int32)
        out.append(np.asarray(R.gaps(ref, tok)))
    return np.concatenate(out)


def sample_requests(done, n: int, seed: int):
    """``n`` finished (prompt, served) pairs drawn from the seed, the one
    with the longest prompt always among them."""
    order = sorted(range(len(done)), key=lambda i: -len(done[i][0]))
    rest = np.random.default_rng([seed, 0x5EED]).permutation(order[1:])
    return [done[i] for i in [order[0], *rest[:n - 1]]]
