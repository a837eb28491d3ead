"""Self-speculative serving bench: per-quantization-method draft acceptance
rate + tok/s vs the non-speculative paged engine, plus the composed
shared-system-prompt workload — speculation × prefix cache × chunked
prefill — reporting acceptance rate, prefix-hit rate and tok/s per method
(BENCH_spec.json).

This measures the paper's claim where it matters — in the serving hot path:
the quantized tree drafts, the full-precision tree verifies, and the
**draft acceptance rate** is a data-free token-level behavioral-fidelity
metric for the quantization method.  A delta-aware method (``daq``) should
draft closer to the full-precision model than the reconstruction-only
baseline (``absmax``) on the same weights — acceptance is the end-to-end
readout of that.  Greedy parity vs the non-speculative engine is asserted
in-bench (the lossless guarantee), so the tok/s column is a pure scheduling
comparison: identical tokens, fewer serial verifier steps.  On CPU the
verify forward costs ~C single steps, so tok/s gains need a memory-bound
accelerator; the acceptance columns are hardware-independent.

  PYTHONPATH=src python -m benchmarks.bench_spec [--gen 24 --n-spec 4 ...]
  PYTHONPATH=src python -m benchmarks.run spec       # same, CSV + JSON

Writes ``BENCH_spec.json`` and prints ``benchmarks.common.emit`` CSV rows.
Each engine is warmed once; the second run is timed (best of N).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, run_meta
from repro.configs import QuantConfig, get_arch, reduced
from repro.data import LanguageSpec, sample_batch
from repro.engine import Engine
from repro.models import build_model
from repro.quantize import quantize


def _race(fns: dict, repeats: int = 3) -> dict:
    outs = {name: fn() for name, fn in fns.items()}      # warm
    best = {name: float("inf") for name in fns}
    for _ in range(repeats):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            outs[name] = fn()
            best[name] = min(best[name], time.perf_counter() - t0)
    return {name: (outs[name], best[name]) for name in fns}


def run(arch: str = "glm4-9b", requests: int = 8, batch: int = 4,
        prompt_len: int = 16, gen: int = 24, k_steps: int = 8,
        n_spec: int = 4, block_size: int = 8,
        methods: tuple = ("daq", "absmax"),
        out_path: str = "BENCH_spec.json") -> dict:
    from repro.telemetry import MetricsRegistry
    reg = MetricsRegistry()   # shared: all engines' lifecycle metrics
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    spec = LanguageSpec(vocab=cfg.vocab_size)
    # a perturbed base stands in for a real base checkpoint: the delta
    # ΔW = W_post - W_base is then non-trivial, so delta-aware methods
    # have something to preserve (see launch/serve.py --base-ckpt for
    # serving against a real base tree)
    base = jax.tree.map(
        lambda p: p - 0.01 * jnp.ones_like(p) * (p.ndim >= 2), params)
    prompts = [sample_batch(jax.random.PRNGKey(i), spec, 1, prompt_len)[0]
               for i in range(requests)]
    cache_len = prompt_len + gen + n_spec + 8

    peng = Engine(model, params, slots=batch, cache_len=cache_len,
                  k_steps=k_steps, block_size=block_size)
    engines = {"paged": lambda: peng.serve(prompts, gen_tokens=gen,
                                           return_stats=True)}
    drafts = {}
    drafts_trees = {}
    for method in methods:
        qcfg = QuantConfig(method=method, granularity="channel")
        dtree, rep = quantize(params, base, qcfg, mode="storage",
                              out_dtype="bfloat16")
        drafts[method] = rep
        drafts_trees[method] = dtree
        eng = Engine(model, params, slots=batch, cache_len=cache_len,
                     k_steps=k_steps, block_size=block_size,
                     n_spec=n_spec, draft_params=dtree, metrics=reg)
        engines[f"spec-{method}"] = (
            lambda e=eng: e.serve(prompts, gen_tokens=gen,
                                  return_stats=True))

    raced = _race(engines)
    (base_outs, base_stats), base_dt = raced["paged"]
    result = {
        "workload": {"arch": arch, "requests": requests, "batch": batch,
                     "prompt_len": prompt_len, "gen": gen,
                     "k_steps": k_steps, "n_spec": n_spec,
                     "block_size": block_size,
                     "methods": list(methods)},
        "paged": {"tok_per_s": base_stats["tokens"] / base_dt,
                  "wall_s": base_dt, "tokens": base_stats["tokens"],
                  "host_syncs": base_stats["host_syncs"]},
        "methods": {},
    }
    for method in methods:
        (outs, stats), dt = raced[f"spec-{method}"]
        parity = outs == base_outs
        assert parity, (f"speculative greedy parity violated for draft "
                        f"method {method!r}")
        acc = (stats["draft_accepted"] / stats["draft_tokens"]
               if stats["draft_tokens"] else 0.0)
        row = {
            "tok_per_s": stats["tokens"] / dt,
            "wall_s": dt,
            "tokens": stats["tokens"],
            "host_syncs": stats["host_syncs"],
            "greedy_parity": parity,
            "acceptance_rate": acc,
            "draft_tokens": stats["draft_tokens"],
            "draft_accepted": stats["draft_accepted"],
            "spec_rounds": stats["spec_rounds"],
            "speedup_vs_paged": (stats["tokens"] / dt)
            / (base_stats["tokens"] / base_dt),
            "draft_sign_rate": drafts[method].global_chosen.get(
                "sign_rate", 0.0),
        }
        result["methods"][method] = row
        emit(f"spec.{method}", dt * 1e6,
             f"tok_per_s={row['tok_per_s']:.1f};"
             f"acceptance={acc:.3f};"
             f"speedup={row['speedup_vs_paged']:.2f}")
    emit("spec.paged_baseline", base_dt * 1e6,
         f"tok_per_s={result['paged']['tok_per_s']:.1f}")
    result["shared_prefix"] = _run_shared(
        model, params, drafts_trees, spec, batch=batch, requests=requests,
        gen=gen, k_steps=k_steps, n_spec=n_spec, block_size=block_size,
        metrics=reg)
    result["meta"] = run_meta(result["workload"], metrics=reg)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    return result


def _run_shared(model, params, drafts_trees: dict, spec, *, batch: int,
                requests: int, gen: int, k_steps: int, n_spec: int,
                block_size: int, system_len: int = 32,
                tail_len: int = 16, chunk: int = 16, metrics=None) -> dict:
    """The composed serving workload: every request opens with the same
    system prompt, engines run speculation × prefix cache × chunked
    prefill.  ``_race`` warms each engine once, so the timed passes hit a
    warm prefix index — the system prompt's blocks are shared, not
    recomputed — while the quantized tree drafts.  Reports the prefix-hit
    rate (prompt tokens served from cache) next to the acceptance rate:
    the two multiplicative sources of saved verifier forwards."""
    system = sample_batch(jax.random.PRNGKey(99), spec, 1, system_len)[0]
    prompts = [jnp.concatenate(
        [system,
         sample_batch(jax.random.PRNGKey(100 + i), spec, 1, tail_len)[0]])
        for i in range(requests)]
    L = system_len + tail_len
    cache_len = L + gen + n_spec + 8

    def mk(dtree=None):
        kw = dict(n_spec=n_spec, draft_params=dtree) if dtree is not None \
            else {}
        return Engine(model, params, slots=batch, cache_len=cache_len,
                      k_steps=k_steps, block_size=block_size,
                      chunk_size=chunk, prefix_cache=True, metrics=metrics,
                      **kw)

    beng = mk()
    engines = {"prefix": lambda: beng.serve(prompts, gen_tokens=gen,
                                            return_stats=True)}
    for method, dtree in drafts_trees.items():
        eng = mk(dtree)
        engines[f"spec-{method}"] = (
            lambda e=eng: e.serve(prompts, gen_tokens=gen,
                                  return_stats=True))
    raced = _race(engines)
    (base_outs, base_stats), base_dt = raced["prefix"]

    def hit_rate(stats):
        seen = stats["prefix_hits"] + stats["prefill_tokens"]
        return stats["prefix_hits"] / seen if seen else 0.0

    out = {
        "workload": {"system_len": system_len, "tail_len": tail_len,
                     "chunk_size": chunk, "requests": requests,
                     "batch": batch, "gen": gen},
        "prefix_baseline": {"tok_per_s": base_stats["tokens"] / base_dt,
                            "wall_s": base_dt,
                            "prefix_hit_rate": hit_rate(base_stats)},
        "methods": {},
    }
    for method in drafts_trees:
        (outs, stats), dt = raced[f"spec-{method}"]
        assert outs == base_outs, (
            f"composed speculative greedy parity violated for {method!r}")
        acc = (stats["draft_accepted"] / stats["draft_tokens"]
               if stats["draft_tokens"] else 0.0)
        row = {
            "tok_per_s": stats["tokens"] / dt,
            "wall_s": dt,
            "greedy_parity": True,
            "acceptance_rate": acc,
            "prefix_hit_rate": hit_rate(stats),
            "final_spec_depth": stats["spec_depth"],
            "speedup_vs_prefix": (stats["tokens"] / dt)
            / (base_stats["tokens"] / base_dt),
        }
        out["methods"][method] = row
        emit(f"spec.shared.{method}", dt * 1e6,
             f"tok_per_s={row['tok_per_s']:.1f};"
             f"acceptance={acc:.3f};"
             f"prefix_hit={row['prefix_hit_rate']:.3f}")
    emit("spec.shared.prefix_baseline", base_dt * 1e6,
         f"tok_per_s={out['prefix_baseline']['tok_per_s']:.1f};"
         f"prefix_hit={out['prefix_baseline']['prefix_hit_rate']:.3f}")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--k-steps", type=int, default=8)
    ap.add_argument("--n-spec", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--methods", nargs="+", default=["daq", "absmax"])
    ap.add_argument("--out", default="BENCH_spec.json")
    args = ap.parse_args(argv)
    run(args.arch, args.requests, args.batch, args.prompt_len, args.gen,
        args.k_steps, args.n_spec, args.block_size, tuple(args.methods),
        args.out)


if __name__ == "__main__":
    main()
