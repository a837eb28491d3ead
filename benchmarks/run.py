"""Benchmark orchestrator — one entry per paper table plus kernel/system
micro-benches.  Output format: ``name,us_per_call,derived`` CSV rows (tables
additionally print their rows as they compute).

  PYTHONPATH=src python -m benchmarks.run                # everything
  PYTHONPATH=src python -m benchmarks.run table2 table4  # subset
  PYTHONPATH=src python -m benchmarks.run kernels

Paper-table benches reuse the cached study checkpoints under
``experiments/study`` (first invocation trains them: ~10 min CPU).
"""
from __future__ import annotations

import sys
import time


def _timed(name, fn):
    t0 = time.perf_counter()
    fn()
    print(f"{name},{(time.perf_counter()-t0)*1e6:.0f},total_wall", flush=True)


def table(n: str):
    from repro.pipeline.daq_study import run_tables
    _timed(f"table{n}", lambda: run_tables(tables=(n,)))


def kernels():
    from benchmarks import bench_kernels
    bench_kernels.main()


def train_throughput():
    """tokens/s of the reduced-config training step (system bench)."""
    import jax
    from benchmarks.common import emit, time_call
    from repro.configs import TrainConfig, get_arch, reduced
    from repro.data import LanguageSpec, train_batch
    from repro.launch.steps import init_train_state, make_train_step
    from repro.models import build_model

    cfg = reduced(get_arch("glm4-9b"))
    tc = TrainConfig()
    model = build_model(cfg)
    state = init_train_state(model, tc, jax.random.PRNGKey(0))
    spec = LanguageSpec(vocab=cfg.vocab_size)
    batch = train_batch(spec, 0, 0, 8, 128)
    step = jax.jit(make_train_step(model, tc))
    us = time_call(lambda: step(state, batch)[1]["loss"])
    emit("train.step_glm4smoke_b8s128", us,
         f"tok_per_s={8*128/(us/1e6):.0f}")


def decode_throughput():
    import jax
    import jax.numpy as jnp
    from benchmarks.common import emit, time_call
    from repro.configs import get_arch, reduced
    from repro.engine import SamplingParams, make_decode_dispatch
    from repro.engine.scheduler import init_slot_state
    from repro.models import build_model

    cfg = reduced(get_arch("glm4-9b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # 8 slots, each holding its whole 256-row table of 16-row blocks
    nb = 8 * 16
    cache = model.init_paged_cache(8, 256, block_size=16, num_blocks=nb)
    cache.update(tbl=jnp.arange(nb, dtype=jnp.int32).reshape(8, 16),
                 ref=jnp.ones((nb,), jnp.int32), n_free=jnp.int32(0),
                 slot_active=jnp.ones((8,), bool),
                 lengths=jnp.full((8,), 128, jnp.int32))
    state = init_slot_state(8, prompt_cap=256)
    state["active"] = jnp.ones((8,), bool)
    state["remaining"] = jnp.full((8,), 10**6, jnp.int32)
    K = 8
    dispatch = jax.jit(make_decode_dispatch(model, SamplingParams(), K))
    key = jax.random.PRNGKey(0)
    us = time_call(lambda: dispatch(params, state, cache, key)[2])
    emit(f"serve.decode_glm4smoke_b8_cache256_k{K}", us,
         f"tok_per_s={8*K/(us/1e6):.0f}")


def spec_bench():
    """Speculative decoding: acceptance rate + tok/s per draft
    quantization method (BENCH_spec.json)."""
    from benchmarks import bench_spec
    bench_spec.main([])


def roofline():
    from benchmarks import roofline_report
    t = roofline_report.table("pod16x16")
    n = t.count("\n") - 1
    print(f"roofline.report,0,rows={n}", flush=True)


BENCHES = {
    "table2": lambda: table("2"),
    "table3": lambda: table("3"),
    "table4": lambda: table("4"),
    "table5": lambda: table("5"),
    "kernels": kernels,
    "train": train_throughput,
    "decode": decode_throughput,
    "spec": spec_bench,
    "roofline": roofline,
}


def main() -> None:
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    names = sys.argv[1:] or list(BENCHES)
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()


if __name__ == "__main__":
    main()
