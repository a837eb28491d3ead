"""Timed path ``serve_hybrid``: offline batch serving of a DAQ-quantized
hybrid model (Mamba-2 + attention + an MoE of which this chip holds a
share), as ``serve`` does for the dense decoder.

What differs from ``serve``: the model and its seeded pair come from
``hybrid_model``, the check runs ``hybrid_reference``, and the work adds
the expert layer's device counters (``moe_assigned``, ``moe_rows``) and
counts the routed experts' FLOPs from them (``hybrid_counts``).

The check reads the mean of the served tokens' gaps below the reference's
best logit (``logit_gap_mean``), which a forward one precision step down
moves 40-fold.  The tied head makes the logits small (std ~0.02 at these
widths), so near-ties flip under any rounding: the widest gap of a sound
run and of that control overlap, and is no limit here.
"""
from __future__ import annotations

import time
from pathlib import Path

import jax
import numpy as np

from benchmarks.chip import check, hybrid_counts, timed
from benchmarks.chip import hybrid_model as HM
from benchmarks.chip import hybrid_reference as HR
from benchmarks.chip import model as M
from benchmarks.chip.traffic import Traffic

Serve = timed.load(Path(__file__).resolve().parents[1], "serve")


class Timed(Serve):

    def setup(self):
        from repro.engine import Engine
        from repro.models import build_model
        from repro.quantize import quantize
        cfg, mix = self.cfg, self.mix
        t = time.perf_counter()
        post, base = HM.make_pair(cfg, self.seed)
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
        self.engine = Engine(build_model(HM.model_config(cfg)), qparams,
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
        self.specs, _ = M.layout(HM.model_config(cfg, cfg["distinct_layers"]))

    def work(self) -> dict:
        ctr = [s["counters"] for s in self.stats]
        assigned = sum(c.get("moe_assigned", 0) for c in ctr)
        flops = sum(hybrid_counts.request_flops(self.cfg, len(p), len(o))
                    for p, o in self.done)
        return {
            "model_flops": flops + hybrid_counts.expert_flops(self.cfg,
                                                              assigned),
            "generated": sum(len(o) for _, o in self.done),
            "prompt_tokens": sum(len(p) for p, _ in self.done),
            "prefix_hit_tokens": sum(c["prefix_hit_tokens"] for c in ctr),
            "decode_steps": sum(s["decode_steps"] for s in self.stats),
            "slots": self.mix["slots"],
            "moe_assigned": assigned,
            "moe_rows": sum(c.get("moe_rows", 0) for c in ctr),
        }

    def _gaps(self, weights=None, **control) -> dict:
        samples = check.sample_requests(self.done,
                                        self.mix["check_requests"], self.seed)
        if weights is None:
            weights = HR.ref_weights(self.cfg, self.seed, self.specs)
        t_max = -(-self.mix["cache_len"] // 256) * 256
        g = HR.serve_gaps(self.cfg, weights, samples, t_max, **control)
        return {"logit_gap_mean": float(np.mean(g))}

    def check(self) -> dict:
        return {**self._gaps(), "window_compiles": self.compiles}

    def controls(self) -> dict:
        """The gaps of the forward with float8 e4m3 matmul inputs in the
        program's place."""
        return {"fp8": self._gaps(fp8_acts=True)}
