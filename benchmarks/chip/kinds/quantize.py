"""Timed path ``quantize``: the DAQ quantizer alone."""
from __future__ import annotations

import gc
import math
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import check, counts, timed
from benchmarks.chip import model as M


class Timed:
    """The window repeats ``repro.quantize.quantize`` over the seeded
    distinct-layer pair; each call ends in ``block_until_ready`` and the
    previous output is freed before the next call."""

    def __init__(self, cfg: dict, mix: dict, seed: int, here: Path):
        self.cfg, self.mix, self.seed = cfg, mix, seed

    def _quantize(self):
        from repro.quantize import quantize
        out, report = quantize(self.post, self.base, M.quant_config(self.cfg),
                               mode="storage", out_dtype="bfloat16")
        jax.block_until_ready(out)
        return out, report

    def setup(self):
        t = time.perf_counter()
        self.post, self.base = M.make_pair(self.cfg, self.seed)
        jax.block_until_ready(self.post)
        timed.log(f"pair made in {time.perf_counter() - t:.1f} s")
        t = time.perf_counter()
        with timed.annotate("warmup"):
            self._quantize()
        timed.log(f"warm quantize in {time.perf_counter() - t:.1f} s")
        self.specs, _ = M.layout(M.model_config(self.cfg,
                                                self.cfg["distinct_layers"]))
        bs = self.cfg["quant"]["block_size"]
        mats = [s for p, s, _, _ in self.specs if check.eligible(p, s)]
        self.n_params = sum(int(np.prod(s)) for s in mats)
        block = self.cfg["quant"]["granularity"] == "block"
        self.n_scales = sum(int(np.prod(s[:-2])) * (
            math.ceil(s[-2] / bs) * math.ceil(s[-1] / bs) if block
            else s[-1]) for s in mats)

    def window(self, seconds: float):
        self.out = self.report = None
        self.calls = 0
        t0 = time.perf_counter()
        while True:
            self.out = self.report = None
            t = time.perf_counter()
            with timed.annotate("quantize_call"):
                self.out, self.report = self._quantize()
            timed.log(f"quantize call {self.calls}: "
                      f"{time.perf_counter() - t:.2f} s")
            self.calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0
        self.attempted, self.failed = self.calls, 0

    def end_to_end(self, peak_bytes: int) -> dict:
        return {"quant_gparams_s":
                self.calls * self.n_params / self.window_s / 1e9,
                "peak_hbm_gb": peak_bytes / 1e9}

    def work(self) -> dict:
        return {"min_bytes": self.calls * counts.daq_bytes(self.n_params,
                                                           self.n_scales)}

    def free(self):
        self.post = self.base = None
        gc.collect()

    def check(self) -> dict:
        cand = check.program_candidate(self.out, self.report)
        return check.daq_numbers(self.cfg, self.seed, self.specs, cand)

    def controls(self) -> dict:
        """The numbers of ``check`` for the two controls in the program's
        place: Alg. 1 in bfloat16, and the search skipped."""
        self.out = self.report = None
        qc = self.cfg["quant"]
        return {name: check.daq_numbers(self.cfg, self.seed, self.specs,
                                        check.reference_candidate(qc, **kw))
                for name, kw in (("bf16", {"dt": jnp.bfloat16}),
                                 ("no_search", {"search": False}))}
