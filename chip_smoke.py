#!/usr/bin/env python3
"""Chip smoke test: DAQ quantize -> serve for glm4-9b on TPU v5e.

  python chip_smoke.py               # one chip: every phase below
  python chip_smoke.py --four-chips  # one host's four chips: sharded phase

One process drives the chip(s).  Without a TPU the script exits non-zero
before any phase runs.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed; a failed phase prints its traceback
and makes the exit code non-zero.

The model is glm4-9b at its published widths (d_model 4096, 32 query / 2 KV
heads of 128, d_ff 13696, vocabulary 151552, qkv bias) with random weights
from a seed; only the depth is cut, to ``DEPTH`` layers on one chip.

One chip:
  kernels   each Pallas kernel, compiled, against its ref at glm4 widths
            (the paged attention kernel at the decode cell's shapes)
  quantize  seeded base tree, post = base + seeded dW, then
            ``repro.quantize.quantize(method="daq", metric="sign",
            fmt="fp8_e4m3", granularity="channel", mode="storage")``
  serve     8 requests of a few hundred prompt tokens (four share a
            prefix) through ``Engine(prefix_cache=True)``,
            greedy, 32 generated tokens each
  logits    for two requests: prefill + decode through the cache against
            one prefill over prompt + generated tokens
Four chips:
  sharded   the DEPTH-layer model served over ``make_host_mesh(model=4)``
            against the same model on one device (logits), then glm4-9b at
            all 40 layers initialised straight into its shardings,
            DAQ-quantized and served over the mesh
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import QuantConfig, get_arch  # noqa: E402
from repro.engine import Engine  # noqa: E402
from repro.launch.sharding import params_shardings  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.quantize import quantize  # noqa: E402
from repro.runtime import enable_compile_cache  # noqa: E402

# Layers on one chip: the largest depth whose measured peak leaves headroom
# under the chip's 16.9 GB limit.  Quantizing holds the bf16 post and base
# trees and the fp8 output at once: 1.02 GB per layer on top of 6.2 GB for
# the embedding and head.  Measured on a v5e: peak 10.3 GB at 4 layers, so
# 8 layers peak near 14.4 GB and 9 would leave under 1.5 GB.
DEPTH = 8
# dW = REL_DELTA * (a second draw of the init): ||dW|| / ||W|| ~ 1%, the
# scale of a post-training delta, with signs that vary per element.
REL_DELTA = 0.01
GEN = 32            # generated tokens per request
SLOTS = 4
BLOCK = 16          # tokens per KV block
CACHE_LEN = 384     # >= longest prompt (352) + GEN
QCFG = QuantConfig(method="daq", metric="sign", fmt="fp8_e4m3",
                   granularity="channel")
# Logit checks compare two computations of the same bf16 model that round
# and reduce in different orders (flash tiles vs one softmax over the
# cache; tensor-parallel partial sums vs one device).  Each rounding is
# a bf16 ulp (2^-8 relative) and they add up over the layers; a wrong
# position, mask, cache row or shard instead moves logits by O(1) of
# their spread.  Limit: max |a - b| over a row, relative to that row's
# spread (max - min), must stay under LOGIT_TOL.
LOGIT_TOL = 0.03

_compile_s = [0.0]


def _on_event(event: str, duration: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    """A failed check fails its phase (also under ``python -O``)."""
    if not ok:
        raise RuntimeError(what)


def peak_bytes() -> int:
    return max(d.memory_stats()["peak_bytes_in_use"] for d in jax.devices())


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_kernels(K: int = 4096, N: int = 13696) -> None:
    """Each Pallas kernel, compiled for the chip, against its jnp ref at
    glm4-9b's MLP width (K = d_model, N = d_ff)."""
    from repro.kernels.fp8_matmul.kernel import matmul_fp8_pallas
    from repro.kernels.fp8_matmul.ref import matmul_fp8_ref
    from repro.kernels.fp8_quant.kernel import quantize_fp8_pallas
    from repro.kernels.fp8_quant.ref import quantize_fp8_ref
    from repro.kernels.scale_search.kernel import sweep_partials_pallas
    from repro.kernels.scale_search.ref import sweep_partials_ref

    bs = 128
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    wb = (jax.random.normal(k1, (K, N)) * 0.02).astype(jnp.bfloat16)
    wp = wb + (jax.random.normal(k2, (K, N)) * 2e-4).astype(jnp.bfloat16)
    wp32, wb32 = wp.astype(jnp.float32), wb.astype(jnp.float32)
    one = jnp.ones((1,), jnp.float32)

    # fp8_quant: scales exact; fp8 codes may differ where the kernel's and
    # XLA's f32 divisions round a value across a bucket edge (~1e-6 of them)
    q, s = jax.jit(quantize_fp8_pallas)(wp32, one)
    qr, sr = jax.jit(quantize_fp8_ref)(wp32, one)
    flips = float(jnp.mean((q.astype(jnp.float32)
                            != qr.astype(jnp.float32)).astype(jnp.float32)))
    s_err = float(jnp.max(jnp.abs(s - sr) / sr))
    log(f"[kernels] fp8_quant [{K},{N}]: scale max rel err {s_err:.2e}, "
        f"fp8 code mismatches {flips:.2e}")
    check(s_err <= 1e-6 and flips <= 1e-4, "fp8_quant disagrees with ref")

    # fp8_matmul: exact products (bf16 x times fp8 widened to bf16) with
    # f32 accumulation, against an f32 ref at HIGHEST precision; only the
    # summation order differs (~1e-7 of the output range), while a bf16
    # rounding of the products would show at ~1e-3
    x = jax.random.normal(k3, (16, K)).astype(jnp.bfloat16)
    y = jax.jit(matmul_fp8_pallas)(x, qr, sr)
    with jax.default_matmul_precision("highest"):
        yr = jax.jit(matmul_fp8_ref)(x, qr, sr)
    m_err = float(jnp.max(jnp.abs(y - yr)) / jnp.max(jnp.abs(yr)))
    log(f"[kernels] fp8_matmul [16,{K}]x[{K},{N}]: max err / max|y| "
        f"{m_err:.2e}")
    check(m_err <= 1e-4, "fp8_matmul disagrees with ref")

    # scale_search: per candidate, tensor-level partial sums; continuous
    # sums differ by summation order, sign counts by the same rare bucket
    # flips as fp8_quant
    amax = jnp.max(jnp.abs(wp32.reshape(K // bs, bs, N // bs, bs)),
                   axis=(1, 3))
    s0 = jnp.maximum(amax, 1e-12) / 448.0
    alphas = jnp.linspace(0.8, 1.25, 16)
    pk = jnp.sum(jax.jit(sweep_partials_pallas)(wp32, wb32, s0, alphas),
                 axis=(1, 2))
    pr = jnp.sum(jax.jit(sweep_partials_ref)(wp32, wb32, s0, alphas),
                 axis=(1, 2))
    cont = np.asarray([0, 2, 3, 4])
    c_err = float(jnp.max(jnp.abs(pk[:, cont] - pr[:, cont])
                          / jnp.abs(pr[:, cont])))
    n_err = float(jnp.max(jnp.abs(pk[:, 1] - pr[:, 1]))) / (K * N)
    log(f"[kernels] scale_search [{K},{N}] x 16 candidates: continuous "
        f"stats max rel err {c_err:.2e}, sign-match diff {n_err:.2e} of "
        f"elements")
    check(c_err <= 1e-4 and n_err <= 1e-4, "scale_search disagrees with ref")

    # paged_attention: 64 slots of 2 KV heads x 16 query heads, tables of
    # 48 blocks of 16 (the decode cell's), lengths 0..767 and a verify
    # chunk of 4; the kernel's online softmax against the gather's plain
    # one, both with bf16 dot inputs and f32 accumulation (bf16 output)
    from repro.kernels.paged_attention.ops import paged_attention_kernel
    from repro.kernels.paged_attention.ref import paged_attention_ref
    B, MB, NB, hd = 64, 48, 3072, 128
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    pool = [jax.random.normal(k, (2, NB + 1, 16, 2, hd), jnp.bfloat16)
            for k in ks[:2]]
    start = jnp.asarray(np.random.RandomState(0).randint(0, MB * 16 - 4, B),
                        jnp.int32)
    tbl = jax.random.permutation(ks[2], NB)[:B * MB].reshape(B, MB)
    tbl = jnp.where(jnp.arange(MB)[None] * 16 < start[:, None] + 4, tbl, -1)
    for C in (1, 4):
        q = jax.random.normal(ks[3], (B, C, 32, hd), jnp.bfloat16)
        kv = jax.random.normal(ks[4], (2, B, C, 2, hd), jnp.bfloat16)
        args = (q, kv[0], kv[1], *pool, jnp.int32(1), tbl, start)
        a = jax.jit(paged_attention_kernel)(*args).astype(jnp.float32)
        r = jax.jit(paged_attention_ref)(*args).astype(jnp.float32)
        a_err = float(jnp.max(jnp.abs(a - r)))
        log(f"[kernels] paged_attention [{B},{C},32,{hd}] over {MB} blocks "
            f"of 16: max abs err {a_err:.2e}")
        check(a_err <= 2e-2, "paged_attention disagrees with ref")


def make_pair(model, seed: int, mesh=None):
    """Seeded base tree and post = base + REL_DELTA * (an independent draw
    of the model's init), so each leaf's dW has that leaf's own init scale.
    One program builds both, straight into the mesh's shardings when one
    is given (no device ever holds a whole tree)."""
    def pair(key):
        k_base, k_delta = jax.random.split(key)
        base = model.init(k_base)
        post = jax.tree.map(
            lambda w, d: (w.astype(jnp.float32) + REL_DELTA
                          * d.astype(jnp.float32)).astype(w.dtype),
            base, model.init(k_delta))
        return post, base

    key = jax.random.PRNGKey(seed)
    if mesh is None:
        return jax.jit(pair)(key)
    shard = params_shardings(jax.eval_shape(model.init, key), model.cfg,
                             mesh)
    return jax.jit(pair, out_shardings=(shard, shard))(key)


def phase_quantize(model, seed: int = 0, mesh=None):
    """DAQ over the whole tree; post and base are freed on return."""
    t0 = time.perf_counter()
    post, base = make_pair(model, seed, mesh)
    jax.block_until_ready(post)
    n = sum(x.size for x in jax.tree.leaves(post))
    log(f"[quantize] post/base trees: {n / 1e9:.3f} B params each "
        f"({time.perf_counter() - t0:.1f} s to build)")
    t0 = time.perf_counter()
    qparams, report = quantize(post, base, QCFG, mode="storage",
                               out_dtype="bfloat16")
    jax.block_until_ready(qparams)
    del post, base
    log(f"[quantize] DAQ {time.perf_counter() - t0:.1f} s "
        f"(compilation included)")
    log("\n".join("[quantize] " + ln for ln in report.summary().split("\n")))
    sd = report.global_default["sign_rate"]
    sc = report.global_chosen["sign_rate"]
    log(f"[quantize] sign_rate default {sd!r} -> chosen {sc!r}")
    check(sc >= sd, "DAQ chose a scale worse than AbsMax on its metric")
    check(report.n_quantized > 0, "nothing was quantized")
    return qparams


def make_prompts(vocab: int, seed: int = 3) -> list[np.ndarray]:
    """8 prompts of 200-352 tokens; four share a 256-token prefix (a
    system prompt).  One of them is in the first wave of SLOTS admissions
    and three in the second, which can reuse its cached prefix blocks."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, 256)
    s0, s1, s2, s3 = [np.concatenate([prefix, rng.integers(0, vocab, n)])
                      for n in (40, 64, 80, 96)]
    a0, a1, a2, a3 = [rng.integers(0, vocab, n) for n in (200, 240, 288, 320)]
    return [p.astype(np.int32) for p in (s0, a0, a1, a2, s1, s2, s3, a3)]


def phase_serve(model, qparams, prompts, mesh=None):
    """Serve the prompts twice (cold, then compiled with a warm prefix
    cache); returns the first run's outputs."""
    V = model.cfg.vocab_size
    eng = Engine(model, qparams, slots=SLOTS, cache_len=CACHE_LEN,
                 k_steps=8, block_size=BLOCK, prefix_cache=True,
                 mesh=mesh)
    runs = []
    for label in ("cold", "warm"):
        c0, t0 = _compile_s[0], time.perf_counter()
        outs, stats = eng.serve(prompts, gen_tokens=GEN, return_stats=True)
        dt = time.perf_counter() - t0
        n_tok = sum(len(o) for o in outs)
        log(f"[serve] {label}: {len(outs)} requests, {n_tok} tokens in "
            f"{dt:.2f} s (compile {_compile_s[0] - c0:.1f} s), "
            f"{stats['prefill_tokens']} prompt tokens prefilled, "
            f"{stats['prefix_hits']} prefix-hit; "
            f"{n_tok / dt:.1f} tok/s (information, not a metric)")
        check(len(outs) == len(prompts), "a request was not served")
        for i, o in enumerate(outs):
            check(len(o) == GEN, f"request {i}: {len(o)} of {GEN} tokens")
            check(all(0 <= t < V for t in o), f"request {i}: id outside vocab")
        runs.append(outs)
    same = sum(a == b for a, b in zip(*runs))
    log(f"[serve] cold and warm outputs identical for {same}/{len(prompts)} "
        f"requests (information)")
    log(f"[serve] compile counts: {eng.compile_counts()}")
    return runs[0], eng


def decode_logits(model, params, prompt, gen):
    """Logits for each generated position: prefill the prompt, then decode
    the generated tokens one by one through the cache."""
    prefill = jax.jit(model.prefill, static_argnames="cache_len")
    step = jax.jit(model.decode_step)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                            cache_len=len(prompt) + len(gen))
    rows = [logits[0]]
    for t in gen[:-1]:
        logits, cache = step(params, jnp.asarray([[t]], jnp.int32), cache)
        rows.append(logits[0])
    return jnp.stack(rows).astype(jnp.float32)


def prefill_logits(model, params, prompt, gen):
    """The same logits from one prefill over prompt + generated tokens:
    one row per position, each row's true length cut where it predicts."""
    full = np.concatenate([prompt, np.asarray(gen, np.int32)])
    n = len(gen)
    toks = jnp.asarray(np.broadcast_to(full, (n, len(full))))
    lens = jnp.arange(len(prompt), len(prompt) + n, dtype=jnp.int32)
    logits, _ = jax.jit(model.prefill)(params, {"tokens": toks},
                                       lengths=lens)
    return logits.astype(jnp.float32)


def logit_gap(a, b) -> float:
    """max |a - b| per row over that row's spread, worst row."""
    spread = jnp.max(b, axis=-1) - jnp.min(b, axis=-1)
    return float(jnp.max(jnp.max(jnp.abs(a - b), axis=-1) / spread))


def phase_logits(model, qparams, prompts, outs) -> None:
    for i in (0, len(prompts) - 1):
        a = decode_logits(model, qparams, prompts[i], outs[i])
        b = prefill_logits(model, qparams, prompts[i], outs[i])
        gap = logit_gap(a, b)
        argmax_same = float(jnp.mean(jnp.argmax(a, -1) == jnp.argmax(b, -1)))
        log(f"[logits] request {i} ({len(prompts[i])} + {len(outs[i])} "
            f"tokens): prefill+decode vs one prefill, max gap / spread "
            f"{gap:.2e} (limit {LOGIT_TOL}); argmax agrees at "
            f"{argmax_same:.0%} of positions")
        check(gap <= LOGIT_TOL, f"request {i}: decode logits disagree")


def phase_sharded(cfg, depth: int) -> None:
    """The depth-L model on the 4-chip mesh against one device, then the
    whole model (``cfg``'s own depth) sharded end to end."""
    from repro.launch.mesh import make_host_mesh, mesh_info
    mesh = make_host_mesh(model=4)
    log(f"[sharded] mesh {mesh_info(mesh)}")
    prompts = make_prompts(cfg.vocab_size)

    model = build_model(dataclasses.replace(cfg, n_layers=depth))
    qparams = phase_quantize(model)
    outs, eng1 = phase_serve(model, qparams, prompts[:SLOTS])
    _, eng4 = phase_serve(model, qparams, prompts[:SLOTS], mesh=mesh)
    for i in (0, SLOTS - 1):
        a = prefill_logits(model, eng4.params, prompts[i], outs[i])
        b = prefill_logits(model, eng1.params, prompts[i], outs[i])
        gap = logit_gap(a, b)
        log(f"[sharded] {depth} layers, request {i}: 4-chip vs 1-chip "
            f"logits, max gap / spread {gap:.2e} (limit {LOGIT_TOL})")
        check(gap <= LOGIT_TOL, f"request {i}: sharded logits disagree")
    del qparams, eng1, eng4

    model = build_model(cfg)
    log(f"[sharded] {cfg.n_layers} layers, params initialised into their "
        f"shardings")
    qparams = phase_quantize(model, mesh=mesh)
    log(f"[sharded] peak bytes in use after quantize (max over chips): "
        f"{peak_bytes()}")
    phase_serve(model, qparams, prompts, mesh=mesh)


# ---------------------------------------------------------------------------
# Main
# ---------------------------------------------------------------------------

def run_phase(name: str, fn, failed: list[str]):
    c0, t0 = _compile_s[0], time.perf_counter()
    try:
        out = fn()
    except Exception:
        traceback.print_exc()
        failed.append(name)
        log(f"[{name}] FAILED")
        return None
    log(f"[{name}] ok in {time.perf_counter() - t0:.1f} s, compile "
        f"{_compile_s[0] - c0:.1f} s, peak bytes in use {peak_bytes()}")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase on a host's 4 chips")
    args = ap.parse_args()

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devs)}",
              file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": want}
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    log(f"[setup] device {device['kind']} x{len(devs)} (using {want}); "
        f"compile cache {enable_compile_cache()}; bytes limit "
        f"{devs[0].memory_stats()['bytes_limit']}")

    failed: list[str] = []
    if args.four_chips:
        log(f"[setup] glm4-9b at {DEPTH} layers and all 40 over 4 chips")
        run_phase("sharded",
                  lambda: phase_sharded(get_arch("glm4-9b"), DEPTH), failed)
    else:
        cfg = dataclasses.replace(get_arch("glm4-9b"), n_layers=DEPTH)
        log(f"[setup] {DEPTH} layers stand for one stage of a "
            f"{get_arch('glm4-9b').n_layers}-layer pipeline")
        log(f"[setup] glm4-9b d_model {cfg.d_model}, heads {cfg.n_heads}/"
            f"{cfg.n_kv_heads} x {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, "
            f"vocab {cfg.vocab_size}, qkv_bias {cfg.qkv_bias}, layers L="
            f"{cfg.n_layers}")
        model = build_model(cfg)
        prompts = make_prompts(cfg.vocab_size)
        run_phase("kernels", phase_kernels, failed)
        qparams = run_phase("quantize", lambda: phase_quantize(model),
                            failed)
        served = None
        if qparams is not None:
            served = run_phase(
                "serve", lambda: phase_serve(model, qparams, prompts),
                failed)
        if served is not None:
            run_phase("logits",
                      lambda: phase_logits(model, qparams, prompts,
                                           served[0]), failed)
        if qparams is None or served is None:
            failed.append("skipped: serve/logits")
    log(f"[done] backend compile {_compile_s[0]:.1f} s in all; peak bytes "
        f"in use {peak_bytes()}")
    if failed:
        log(f"[done] FAILED: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
