"""Serving CLI: a thin driver over the device-resident engine.

All serving mechanics live in :mod:`repro.engine` — slot scheduling on
device, K-step decode dispatch (one host sync per K tokens) over a paged KV
pool with prompts prefilled in chunks inside it, greedy / temperature /
top-k sampling, and opt-in sharded serving over a host mesh.  This module
only parses flags, builds (and optionally quantizes) the model, and calls
``Engine.serve``.

* **Quantized weights** — pass ``--daq`` to serve fp8 weights quantized
  through ``repro.quantize`` (method selectable via ``--method``): the
  parameter tree's matmul leaves become QuantizedTensor nodes and the same
  model code serves them (quant_runtime/qlinear.py) with an XLA dequantize
  + matmul.  The base tree is dropped as soon as quantizing is done.
  Delta-aware methods want a real base model — point ``--base-ckpt`` at a
  checkpoint directory (e.g. ``experiments/study/base``); without it a
  jittered copy stands in (with a loud warning — demo only).
* **Sharded serving** — ``--mesh N`` builds a host mesh with model-parallel
  size N (``launch/mesh.make_host_mesh``), initialises params straight into
  their ``launch/sharding`` specs (no device ever holds the whole tree) and
  places the cache the same way; quantized ``wq/data`` / ``wq/scale``
  leaves inherit the dense weight's layout.
* **Speculative decoding** — ``--spec-draft METHOD --n-spec N``
  quantizes the weights with METHOD and serves them as the *draft* model:
  up to N drafted tokens per round, verified by one forward of the
  full-precision weights (engine/spec.py).  Composes freely with
  ``--prefix-cache`` / ``--chunk-size`` — speculative rounds, CoW prefix
  writes and chunk-prefill pieces are phases of one dispatch — so
  shared-prefix workloads measure draft fidelity too.  The round depth is
  dynamic by default (AIMD on the acceptance rate, 1..N, zero recompiles;
  ``--spec-static`` pins it at N).  Greedy output is token-exact vs
  non-speculative serving; the summary line reports the draft acceptance
  rate — a data-free behavioral-fidelity readout of the quantization
  method.

Usage (CPU-scale):
  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --reduced \
      --requests 6 --batch 2 --prompt-len 16 --gen 8 --k-steps 8 \
      [--daq [--method daq] [--base-ckpt experiments/study/base]] \
      [--block-size 8 --prefix-cache --spec-draft daq --n-spec 4] \
      [--temperature 0.8 --top-k 40 --top-p 0.95] [--mesh 1] \
      [--metrics-out metrics.json --trace-out trace.json]

``--metrics-out`` writes the request-lifecycle metrics snapshot
(``repro.telemetry.metrics/v1`` JSON — TTFT/TPOT/queue-wait percentiles,
acceptance rate, prefix-hit fraction, allocator gauges) and ``--trace-out``
a Chrome/Perfetto trace of the run; the CLI summary is printed from the
same snapshot either way.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import QuantConfig, get_arch, reduced as reduce_cfg
from repro.data import LanguageSpec, sample_batch
from repro.engine import Engine, SamplingParams
from repro.launch.sharding import init_params
from repro.models import build_model
from repro.runtime import enable_compile_cache


def serve(model, params, requests: list[jnp.ndarray], *, batch: int,
          gen_tokens: int, cache_len: int, greedy: bool = True,
          sampling: SamplingParams | None = None, k_steps: int = 8,
          mesh=None, seed: int = 0) -> list[list[int]]:
    """Compat wrapper: serve ``requests`` through a fresh :class:`Engine`.

    Kept so existing callers (tests, examples) of the old host-loop API keep
    working; new code should construct an ``Engine`` directly and reuse it
    across calls.
    """
    sp = sampling or SamplingParams(greedy=greedy)
    eng = Engine(model, params, slots=batch, cache_len=cache_len,
                 k_steps=k_steps, sampling=sp, mesh=mesh)
    return eng.serve(requests, gen_tokens=gen_tokens, seed=seed)


def _load_base_params(base_ckpt: str, params):
    """Base tree for delta-aware quantization.

    With ``--base-ckpt``: restore the real base model via repro.checkpoint
    (accepts either a bare params tree or a train-state checkpoint with a
    ``params`` sub-tree).  Without: fall back to a jittered copy of the
    serving weights — delta metrics are then meaningless, so warn loudly.
    """
    if base_ckpt:
        from repro import checkpoint as ckpt
        step = ckpt.latest(base_ckpt)
        if step is None:
            raise SystemExit(f"--base-ckpt {base_ckpt}: no checkpoint found")
        params_shape = jax.eval_shape(lambda: params)
        # the manifest tells the layout apart: train-state checkpoints nest
        # leaves under "params.", bare params trees don't — so a genuine
        # restore failure (e.g. arch/shape mismatch) propagates as itself
        leaves = ckpt.meta(base_ckpt, step)["leaves"]
        if any(name.startswith("params.") for name in leaves):
            return ckpt.restore(base_ckpt, step,
                                {"params": params_shape})["params"]
        return ckpt.restore(base_ckpt, step, params_shape)
    print("[serve] WARNING: no --base-ckpt given; using a jittered copy of "
          "the serving weights as the base model. Delta-aware metrics are "
          "meaningless against a fake base — pass --base-ckpt for real use.",
          flush=True)
    return jax.tree.map(
        lambda p: p - 0.01 * jnp.ones_like(p) * (p.ndim >= 2), params)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--k-steps", type=int, default=8,
                    help="decode steps per device dispatch (1 host sync "
                         "per k-steps tokens)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 = greedy (default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation for sampling (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus (top-p) truncation for sampling "
                         "(1.0 = off)")
    ap.add_argument("--mesh", type=int, default=0, metavar="MP",
                    help="serve sharded over a host mesh with "
                         "model-parallel size MP (0 = unsharded)")
    ap.add_argument("--block-size", type=int, default=8,
                    help="tokens per block of the paged KV pool")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="KV pool size in blocks; 0 = every slot's whole "
                         "capacity (slots * ceil(cache_len / block_size))")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="prompt tokens prefilled per dispatch step (prompts "
                         "stream through the decode dispatch instead of "
                         "stalling decode); 0 = the engine's default")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="refcounted prompt-prefix block sharing: matched "
                         "leading blocks are mapped instead of re-prefilled "
                         "and stay cached (LRU) after requests finish")
    ap.add_argument("--spec-draft", default="", metavar="METHOD",
                    help="self-speculative decoding: quantize the weights "
                         "with METHOD (repro.quantize registry key, e.g. "
                         "daq | absmax) and use them as the draft model, "
                         "verified by the full-precision weights")
    ap.add_argument("--n-spec", type=int, default=4,
                    help="maximum drafted tokens per speculative round "
                         "(with --spec-draft; must be < --k-steps)")
    ap.add_argument("--spec-static", action="store_true",
                    help="pin the speculation depth at --n-spec instead of "
                         "moving it 1..n-spec from acceptance telemetry "
                         "(engine/spec.DepthController)")
    ap.add_argument("--daq", action="store_true",
                    help="serve fp8-quantized weights (repro.quantize)")
    ap.add_argument("--metric", default="sign")
    ap.add_argument("--method", default=None,
                    help="quantization method registry key for --daq "
                         "serving (daq | absmax | daq-per-block | ...); "
                         "default daq.  The speculative draft's method is "
                         "--spec-draft's value, not this flag")
    ap.add_argument("--base-ckpt", default="",
                    help="checkpoint dir of the BASE model for delta-aware "
                         "quantization (loaded via repro.checkpoint)")
    ap.add_argument("--metrics-out", default="", metavar="PATH",
                    help="write the request-lifecycle metrics snapshot "
                         "(repro.telemetry.metrics/v1 JSON: TTFT/TPOT "
                         "percentiles, acceptance rate, prefix-hit "
                         "fraction, allocator gauges) to PATH")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="write a Chrome/Perfetto trace-event JSON of the "
                         "serve run (admission / dispatch / spec / "
                         "prefill-chunk / eviction tracks) to PATH — open "
                         "in https://ui.perfetto.dev or chrome://tracing")
    args = ap.parse_args()
    if not args.daq and not args.spec_draft \
            and (args.base_ckpt or args.method is not None
                 or args.metric != "sign"):
        raise SystemExit("--method/--metric/--base-ckpt configure quantized "
                         "serving and require --daq (or --spec-draft)")
    if args.spec_draft and args.method is not None:
        raise SystemExit("--method configures --daq serving and is not "
                         "read by the speculative path: the draft's "
                         "quantization method IS --spec-draft's value "
                         f"({args.spec_draft!r}) — drop --method")
    if args.spec_draft and args.daq:
        raise SystemExit("--spec-draft verifies quantized drafts against "
                         "the FULL-precision weights; it cannot combine "
                         "with --daq (which quantizes the served weights)")

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    if cfg.family in ("vlm", "encdec"):
        raise SystemExit("serve CLI drives LM-style archs; "
                         "vlm/encdec need modality inputs (see examples/)")
    print(f"[serve] compile cache: {enable_compile_cache()}")
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh, mesh_info
        mesh = make_host_mesh(model=args.mesh)
        print(f"[serve] host mesh: {mesh_info(mesh)}")
    model = build_model(cfg)
    params = init_params(model, jax.random.PRNGKey(0), mesh)

    spec = LanguageSpec(vocab=cfg.vocab_size)
    # the base tree is an argument expression, never bound to a name: it is
    # freed as soon as quantize returns, before serving starts
    if args.daq:
        from repro.quantize import quantize
        qcfg = QuantConfig(method=args.method or "daq", metric=args.metric,
                           granularity="channel")
        # model=/spec= feed the calibrate hook of calibration-based
        # methods (smoothquant/awq); data-free methods ignore them
        params, report = quantize(params,
                                  _load_base_params(args.base_ckpt, params),
                                  qcfg, mode="storage", out_dtype="bfloat16",
                                  model=model, spec=spec)
        print(report.summary())
    draft_params = None
    if args.spec_draft:
        from repro.quantize import quantize
        qcfg = QuantConfig(method=args.spec_draft, metric=args.metric,
                           granularity="channel")
        draft_params, report = quantize(
            params, _load_base_params(args.base_ckpt, params), qcfg,
            mode="storage", out_dtype="bfloat16", model=model, spec=spec)
        print(f"[serve] speculative draft ({args.spec_draft}):")
        print(report.summary())
    prompts = [sample_batch(jax.random.PRNGKey(i), spec, 1,
                            args.prompt_len)[0] for i in range(args.requests)]
    cache_len = args.prompt_len + args.gen + 8
    if args.temperature <= 0 and args.top_k == 0 and args.top_p >= 1.0:
        sp = SamplingParams()                        # greedy
    else:  # any flag alone enables sampling (temperature defaults to 1)
        sp = SamplingParams(greedy=False,
                            temperature=args.temperature
                            if args.temperature > 0 else 1.0,
                            top_k=args.top_k, top_p=args.top_p)
    from repro.telemetry import MetricsRegistry, Tracer
    reg = MetricsRegistry()
    tracer = Tracer() if args.trace_out else None
    eng = Engine(model, params, slots=args.batch, cache_len=cache_len,
                 k_steps=args.k_steps, sampling=sp, mesh=mesh,
                 block_size=args.block_size,
                 num_blocks=args.num_blocks, chunk_size=args.chunk_size,
                 prefix_cache=args.prefix_cache,
                 n_spec=args.n_spec if args.spec_draft else 0,
                 spec_dynamic=not args.spec_static,
                 draft_params=draft_params, metrics=reg, tracer=tracer)

    t0 = time.time()
    outs, stats = eng.serve(prompts, gen_tokens=args.gen, return_stats=True)
    dt = time.time() - t0
    n_tok = sum(len(o) for o in outs)
    kind = "paged"
    if args.prefix_cache:
        kind += "+prefix"
    if args.spec_draft:
        kind += f"+spec({args.spec_draft})"
    extra = (f", {stats['prefill_tokens']} prompt tokens prefilled"
             + (f" ({stats['prefix_hits']} prefix-hit)"
                if args.prefix_cache else ""))
    snap = reg.snapshot()
    # acceptance / depth come from the metrics snapshot (the device
    # counter tree feeds the spec.* gauges); non-spec runs report n/a
    acc = snap["gauges"].get("spec.acceptance_rate")
    depth = snap["gauges"].get("spec.depth")
    extra += (", acceptance: n/a" if acc is None else
              f", draft acceptance {acc:.1%} over "
              f"{stats.get('spec_rounds', 0)} rounds of <={args.n_spec}, "
              f"final depth {depth:.0f}")
    print(f"served {args.requests} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s, "
          f"{stats['host_syncs']/max(n_tok, 1):.3f} host syncs/token; "
          f"{stats['dispatches']} dispatches of {args.k_steps} steps, "
          f"{stats['prefill_calls']} prefill calls; {kind} cache, "
          f"{stats['cache_bytes']} cache bytes{extra})")
    print("metrics:")
    print(reg.summary())
    if args.metrics_out:
        reg.save(args.metrics_out)
        print(f"[serve] metrics snapshot ({snap['schema']}) -> "
              f"{args.metrics_out}")
    if tracer is not None:
        tracer.save(args.trace_out)
        print(f"[serve] perfetto trace ({len(tracer.events)} events) -> "
              f"{args.trace_out}")
    # jit cache size per entry point: each holds at 1 in steady state.
    # Anything above that is an avoidable recompile — the signature
    # contracts live in `python -m repro.staticcheck`.
    counts = eng.compile_counts()
    total = sum(counts.values())
    print(f"compiles: {total} total ("
          + ", ".join(f"{k.lstrip('_')}={v}"
                      for k, v in sorted(counts.items())) + ")")
    for i, o in enumerate(outs[:4]):
        print(f"  req{i}: {o}")


if __name__ == "__main__":
    main()
