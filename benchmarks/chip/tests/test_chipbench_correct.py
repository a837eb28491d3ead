"""The comparisons that decide ``correct``, driven end to end on the CPU at
a tiny size with the chip's check skipped: sound runs pass, and each fault
the cells can have, and each control one precision step down, fails.

The DAQ numbers are held to the DAQ cell's own limits.  The serving cells'
logit-gap limit is set between readings at the published widths; at this
size the program reads 0.0 (the CPU's bf16 matmuls accumulate in float32
and round once) and the fp8 control reads 0.115 and 0.058 on seeds 11 and
12, so the tiny cell's limit is 0.02."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as T
from benchmarks.chip import check, timed
from benchmarks.chip import model as M


TINY_LIMITS = {"decode": {"logit_gap": 0.02}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return T.make_root(tmp_path_factory.mktemp("bench"), TINY_LIMITS)


def test_serve_sound_and_altered_token(root, monkeypatch):
    res = T.run(root, "decode")
    assert res["correct"], res["checks"]
    from repro.engine import Engine
    serve = Engine.serve

    def altered(self, *a, **kw):
        res = serve(self, *a, **kw)
        if kw.get("return_stats"):      # the window's calls, not warm-up
            for o in res[0]:
                o[len(o) // 2] = (o[len(o) // 2] + 1) % T.TINY["vocab_size"]
        return res

    monkeypatch.setattr(Engine, "serve", altered)
    bad = T.run(root, "decode")
    assert not bad["correct"]
    assert bad["checks"]["logit_gap"]["value"] > \
        bad["checks"]["logit_gap"]["limit"]


def test_serve_control_fails(root):
    import json
    limit = json.loads((root / "benchmarks/chip/limits/tiny.decode.json")
                       .read_text())["logit_gap"]
    here = root / "benchmarks/chip"
    d = timed.load(here, "serve")(T.TINY, T.MIXES["decode"], 11, here)
    d.setup()
    d.window(0.1)
    d.free()
    assert d.check()["logit_gap"] <= limit < \
        d.controls()["fp8"]["logit_gap"]


def test_daq_sound_and_altered_code(root, monkeypatch):
    res = T.run(root, "daq")
    assert res["correct"], res["checks"]

    def altered(self):
        from repro.quantize import quantize
        out, rep = quantize(self.post, self.base, M.quant_config(self.cfg),
                            mode="storage", out_dtype="bfloat16")
        wq = out["stack"]["L0"]["mlp"]["w_up"]
        flipped = wq.data.at[0, 5, 7].set(
            (wq.data[0, 5, 7].astype(jnp.float32) + 8).astype(wq.data.dtype))
        out["stack"]["L0"]["mlp"]["w_up"] = type(wq)(
            flipped, wq.scale, wq.fmt, wq.granularity, wq.block_size,
            wq.out_dtype)
        return out, rep

    quantize = timed.load(root / "benchmarks/chip", "quantize")
    monkeypatch.setattr(quantize, "_quantize", altered)
    bad = T.run(root, "daq")
    assert not bad["correct"]
    assert bad["checks"]["code_mismatches"]["value"] > \
        bad["checks"]["code_mismatches"]["limit"]


def test_daq_search_skipped(root, monkeypatch):
    """The program with Alg. 1's search skipped (alpha = 1 everywhere)
    keeps its scales, codes and reported rate consistent; only the search
    gap catches it."""
    def skipped(self):
        from repro.quantize import quantize
        cfg = dict(self.cfg, quant=dict(self.cfg["quant"], alpha_min=1.0,
                                        alpha_max=1.0))
        out, rep = quantize(self.post, self.base, M.quant_config(cfg),
                            mode="storage", out_dtype="bfloat16")
        assert all(np.allclose(v["alpha"], 1.0, rtol=0, atol=1e-6)
                   for v in rep.per_leaf.values())
        return out, rep

    quantize = timed.load(root / "benchmarks/chip", "quantize")
    monkeypatch.setattr(quantize, "_quantize", skipped)
    bad = T.run(root, "daq")
    assert not bad["correct"]
    c = bad["checks"]
    assert c["search_gap"]["value"] > c["search_gap"]["limit"]
    assert all(c[k]["value"] <= c[k]["limit"] for k in c if k != "search_gap")


@pytest.mark.parametrize("control", ["bf16", "no_search"])
def test_daq_control_fails(root, control):
    import json
    limits = json.loads((root / "benchmarks/chip/limits/tiny.daq.json")
                        .read_text())
    specs, _ = M.layout(M.model_config(T.TINY, T.TINY["distinct_layers"]))
    kw = {"bf16": {"dt": jnp.bfloat16}, "no_search": {"search": False}}
    nums = check.daq_numbers(T.TINY, 4, specs, check.reference_candidate(
        T.TINY["quant"], **kw[control]))
    assert any(v > limits[k] for k, v in nums.items()), nums
