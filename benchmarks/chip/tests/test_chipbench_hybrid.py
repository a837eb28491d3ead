"""The hybrid cell's timed path (``serve_hybrid``), its reference, counts
and metric, driven on the CPU at a tiny size: a sound run is correct and
reports the expert layer's fill, an altered token fails, the control with
float8 matmul inputs fails, and the configuration file keeps every number
of the published ``config.json``.

At this size the logits are small (std ~0.02: the tied head is the
embedding, of std 1/192); the mean logit gap reads 1.6e-5 for the program
and 4.0e-4 for the fp8 control on seed 13.  The tiny cell's limit is
0.0002.  A bfloat16 SSM state is the CPU tests' control
(``tests/test_granite.py``): at these prompt lengths it moves no greedy
token."""
import json

import pytest

import chipbench_tiny as T
from benchmarks.chip import hybrid_counts, hybrid_model, peaks, timed
from benchmarks.chip.model import load_config

TINY = {
    "name": "tiny-hybrid", "family": "hybrid", "n_layers": 8,
    "d_model": 128, "d_ff": 64, "d_ff_shared": 96, "head_dim": 32,
    "n_heads": 4, "n_kv_heads": 2, "vocab_size": 512,
    "n_experts_routed": 8, "n_experts": 4, "expert_first": 2, "top_k": 2,
    "ssm_state": 16, "d_inner": 128, "ssm_head_dim": 16, "ssm_chunk": 32,
    "conv_kernel": 4, "attn_every": 4, "moe_every": 1, "moe_offset": 0,
    "rope_theta": 10000, "position_embedding_type": "nope",
    "attn_scale": 0.03125, "embedding_multiplier": 12.0,
    "residual_multiplier": 0.22, "logits_scaling": 16.0, "norm_eps": 1e-5,
    "tie_embeddings": True, "qkv_bias": False, "distinct_layers": 4,
    "embed_std": 1 / 192,
    "rel_delta": 0.01,
    "quant": {"method": "daq", "metric": "sign", "fmt": "fp8_e4m3",
              "granularity": "channel", "block_size": 128},
    "engine": {"paged": True, "prefix_cache": False, "chunk_size": 32,
               "block_size": 16, "k_steps": 4},
}
MIX = {"timed": "serve_hybrid", "slots": 4, "cache_len": 256,
       "num_blocks": 64, "gen_tokens": 16, "requests_per_call": 4,
       "warmup_requests": 2, "check_requests": 2,
       "prompt": {"dist": "lognormal", "median": 60, "sigma": 0.6,
                  "min": 32, "max": 128}}
CELL = "tiny-hybrid.decode"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = T.make_root(tmp_path_factory.mktemp("bench"),
                       {"decode": {"logit_gap_mean": 0.0002}})
    chip = root / "benchmarks" / "chip"
    (chip / "configs" / "tiny-hybrid.json").write_text(json.dumps(TINY))
    (chip / "traffic" / "tiny_hybrid.json").write_text(json.dumps(MIX))
    (chip / "limits" / f"{CELL}.json").write_text(
        (chip / "limits" / "tiny.decode.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-hybrid", "source": "test",
                             "file": "benchmarks/chip/configs/"
                                     "tiny-hybrid.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-hybrid",
                               "traffic": "tiny_hybrid", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "granite-4.0-h-small.decode" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, seed=3, trace=False):
    import jax

    from benchmarks.chip.run import run_cell
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return run_cell(root, bench, CELL, seed, 0.1, trace, jax.devices()[:1])


def test_hybrid_cell_sound_and_altered_token(root, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    res = _run(root, trace=True)
    assert res["correct"], res["checks"]
    fill = res["metrics"]["moe_fill.serve"]["value"]
    assert 0 < fill <= 100
    assert 0 < res["metrics"]["mfu.serve"]["value"]
    from repro.engine import Engine
    serve = Engine.serve

    def altered(self, *a, **kw):
        res = serve(self, *a, **kw)
        if kw.get("return_stats"):      # the window's calls, not warm-up
            for o in res[0]:
                o[len(o) // 2] = (o[len(o) // 2] + 1) % TINY["vocab_size"]
        return res

    monkeypatch.setattr(Engine, "serve", altered)
    bad = _run(root)
    assert not bad["correct"]


def test_hybrid_controls_fail(root):
    limit = json.loads((root / "benchmarks/chip/limits" / f"{CELL}.json")
                       .read_text())["logit_gap_mean"]
    here = root / "benchmarks/chip"
    d = timed.load(here, "serve_hybrid")(TINY, MIX, 13, here)
    d.setup()
    d.window(0.1)
    d.free()
    sound, control = d.check(), d.controls()["fp8"]
    assert control["logit_gap_mean"] > limit >= sound["logit_gap_mean"]


def test_hybrid_counts():
    """hybrid_counts against the shapes worked out by hand."""
    cfg = dict(TINY, n_layers=8)
    # Mamba: 2 x (128 x (2 x 128 + 2 x 16 + 8) + 128 x 128) projections,
    # 2 x 4 x (128 + 32) conv, 4 x 128 x 16 recurrence
    mamba = 2 * (128 * 296 + 128 * 128) + 2 * 4 * 160 + 4 * 128 * 16
    # attention: 2 x (128 x 128 + 2 x 128 x 64 + 128 x 128)
    attn = 2 * (128 * 128 + 2 * 128 * 64 + 128 * 128)
    # router 2 x 128 x 8, shared SwiGLU 6 x 128 x 96, on all 8 layers
    ffn = 2 * 128 * 8 + 6 * 128 * 96
    assert hybrid_counts.token_flops(cfg) == 6 * mamba + 2 * attn + 8 * ffn
    # 3 prompt tokens, 2 generated: 4 tokens through the layers, keys
    # 1 + 2 + 3 + 4 in the 2 attention layers, the head twice
    assert hybrid_counts.request_flops(cfg, 3, 2) == (
        4 * hybrid_counts.token_flops(cfg) + 2 * 4 * 4 * 32 * 10
        + 2 * 2 * 128 * 512)
    assert hybrid_counts.expert_flops(cfg, 5) == 5 * 6 * 128 * 64


def test_granite_file_keeps_the_published_config():
    """Every number of the published config.json is in the file under its
    own key, and the program's model has the published widths with 9 of
    the 72 experts held."""
    cfg = load_config(T.CHIP / "configs" / "granite-4.0-h-small.json")
    for key, value in cfg["published"].items():
        assert cfg[key] == value, key
    m = hybrid_model.model_config(cfg)
    assert (m.d_model, m.n_heads, m.n_kv_heads, m.resolved_head_dim,
            m.resolved_d_inner, m.n_ssm_heads, m.ssm_state, m.d_ff,
            m.resolved_d_ff_shared, m.n_experts, m.top_k, m.vocab_size,
            m.n_layers) == (4096, 32, 8, 128, 8192, 128, 128, 768, 1536,
                            72, 10, 100352, 40)
    assert (m.resolved_experts_held, m.rope_theta, m.attn_scale) == \
        (9, 0.0, 0.0078125)
