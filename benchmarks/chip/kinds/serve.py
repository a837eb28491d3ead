"""Timed path ``serve``: offline batch serving of the DAQ-quantized model."""
from __future__ import annotations

import gc
import math
import time
from pathlib import Path

import jax
import numpy as np

from benchmarks.chip import check, counts, timed
from benchmarks.chip import model as M
from benchmarks.chip.traffic import Traffic


class Timed:
    """Offline batch serving: the window loops ``Engine.serve`` over the
    next call's requests from the seeded stream until the window is over.
    Rates count all calls, from the first call's start to the last's end."""

    def __init__(self, cfg: dict, mix: dict, seed: int, here: Path):
        self.cfg, self.mix, self.seed, self.here = cfg, mix, seed, here

    def setup(self):
        from repro.engine import Engine
        from repro.models import build_model
        from repro.quantize import quantize
        cfg, mix = self.cfg, self.mix
        t = time.perf_counter()
        post, base = M.make_pair(cfg, self.seed)
        jax.block_until_ready(post)
        timed.log(f"pair made in {time.perf_counter() - t:.1f} s")
        t = time.perf_counter()
        qparams, _ = quantize(post, base, M.quant_config(cfg),
                              mode="storage", out_dtype="bfloat16")
        jax.block_until_ready(qparams)
        timed.log(f"quantized in {time.perf_counter() - t:.1f} s")
        del post, base
        qparams = M.repeat_stack(qparams,
                                 cfg["n_layers"] // cfg["distinct_layers"])
        self.engine = Engine(build_model(M.model_config(cfg)), qparams,
                             slots=mix["slots"], cache_len=mix["cache_len"],
                             num_blocks=mix["num_blocks"], **cfg["engine"])
        del qparams
        self.traffic = Traffic(mix, cfg["vocab_size"], self.seed, self.here)
        t = time.perf_counter()
        # enough tokens to run a prefill dispatch and then a decode one
        with timed.annotate("warmup"):
            self.engine.serve(self.traffic.warmup(),
                              gen_tokens=cfg["engine"]["k_steps"] + 1)
        timed.log(f"engine warm in {time.perf_counter() - t:.1f} s")
        self.specs, _ = M.layout(M.model_config(cfg, cfg["distinct_layers"]))

    def window(self, seconds: float):
        from repro.telemetry import MetricsRegistry
        eng, gen = self.engine, self.mix["gen_tokens"]
        eng.metrics = MetricsRegistry()      # the window's requests only
        before = eng.compile_counts()
        self.done, self.stats, self.prefix_lens = [], [], []
        t0 = time.perf_counter()
        while True:
            reqs = self.traffic.next_call()
            t = time.perf_counter()
            with timed.annotate("serve_call"):
                outs, stats = eng.serve(reqs, gen_tokens=gen,
                                        return_stats=True)
            timed.log(f"serve call {len(self.stats)}: "
                      f"{time.perf_counter() - t:.2f} s, "
                      f"{stats['dispatches']} dispatches, "
                      f"{stats['prefill_tokens']} prefilled")
            self.done += list(zip(reqs, outs))
            self.prefix_lens += self.traffic.last_prefix_lens
            self.stats.append(stats)
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0
        after = eng.compile_counts()
        self.compiles = sum(after.values()) - sum(before.values())
        self.tpot = sorted(eng.metrics.histogram("request.tpot_s")._samples)
        V = self.cfg["vocab_size"]
        self.attempted = len(self.done)
        self.failed = sum(1 for _, o in self.done
                          if len(o) != gen or not all(0 <= t < V for t in o))

    def end_to_end(self, peak_bytes: int) -> dict:
        gen = sum(len(o) for _, o in self.done)
        out = {"gen_tok_s": gen / self.window_s}
        if self.tpot:
            rank = max(1, math.ceil(0.9 * len(self.tpot)))
            out["tpot_p90_ms"] = 1e3 * self.tpot[rank - 1]
        return out

    def work(self) -> dict:
        cfg, bs = self.cfg, self.cfg["engine"]["block_size"]
        flops = 0
        # the warm-up cached every shared prefix: its whole blocks hit
        for (prompt, out), pl in zip(self.done, self.prefix_lens):
            flops += counts.request_flops(cfg, len(prompt), pl // bs * bs,
                                          len(out))
        return {
            "model_flops": flops,
            "generated": sum(len(o) for _, o in self.done),
            "prompt_tokens": sum(len(p) for p, _ in self.done),
            "prefix_hit_tokens": sum(s["counters"]["prefix_hit_tokens"]
                                     for s in self.stats),
            "decode_steps": sum(s["decode_steps"] for s in self.stats),
            "slots": self.mix["slots"],
        }

    def free(self):
        self.engine = None
        gc.collect()

    def _gap(self, fp8_acts: bool = False) -> float:
        samples = check.sample_requests(self.done,
                                        self.mix["check_requests"], self.seed)
        weights = check.ref_weights(self.cfg, self.seed, self.specs)
        t_max = -(-self.mix["cache_len"] // 256) * 256
        return float(np.max(check.serve_gaps(self.cfg, weights, samples,
                                             t_max, fp8_acts)))

    def check(self) -> dict:
        return {"logit_gap": self._gap(), "window_compiles": self.compiles}

    def controls(self) -> dict:
        """The logit gap of the forward with float8 e4m3 matmul inputs in
        the program's place, read at the same prompts and tokens."""
        return {"fp8": {"logit_gap": self._gap(fp8_acts=True)}}
