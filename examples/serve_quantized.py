"""Serve a DAQ-quantized model through the device-resident engine.

  PYTHONPATH=src python examples/serve_quantized.py

Compares dense-bf16 serving vs fp8 DAQ-quantized serving on the same
requests: same model code, QuantizedTensor leaves (quant_runtime/qlinear),
which XLA dequantizes and multiplies on every backend.  Both
runs go through ``repro.engine.Engine`` — slot scheduling lives on device
and the host syncs once per ``k_steps`` decode steps.
"""
import time

import jax
import jax.numpy as jnp

from repro.configs import QuantConfig, get_arch, reduced
from repro.data import LanguageSpec, sample_batch
from repro.engine import Engine
from repro.models import build_model
from repro.quantize import quantize


def main():
    cfg = reduced(get_arch("glm4-9b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    base = jax.tree.map(
        lambda p: (p - 0.002 * jax.random.normal(
            jax.random.PRNGKey(1), p.shape).astype(p.dtype))
        if p.ndim >= 2 else p, params)

    qcfg = QuantConfig(method="daq", metric="sign", granularity="channel")
    qparams, report = quantize(params, base, qcfg, mode="storage",
                               out_dtype="bfloat16")
    print(report.summary())

    spec = LanguageSpec(vocab=cfg.vocab_size)
    prompts = [sample_batch(jax.random.PRNGKey(i), spec, 1, 16)[0]
               for i in range(6)]

    for name, p in (("bf16", params), ("fp8-DAQ", qparams)):
        eng = Engine(model, p, slots=2, cache_len=40, k_steps=8)
        t0 = time.time()
        outs, stats = eng.serve(prompts, gen_tokens=8, return_stats=True)
        dt = time.time() - t0
        n = sum(len(o) for o in outs)
        print(f"{name:8s}: {n} tokens in {dt:.2f}s ({n/dt:.1f} tok/s, "
              f"{stats['host_syncs']} host syncs); "
              f"first request -> {outs[0]}")


if __name__ == "__main__":
    main()
