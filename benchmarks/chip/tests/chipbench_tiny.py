"""A copy of the benchmark with a tiny configuration and tiny mixes, for
tests that drive whole runs on the CPU."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY = {
    "name": "tiny", "family": "dense", "n_layers": 4, "d_model": 128,
    "d_ff": 256, "head_dim": 32, "n_heads": 4, "n_kv_heads": 2,
    "vocab_size": 512, "qkv_bias": True, "norm_eps": 1e-5,
    "rope_theta": 10000.0, "tie_embeddings": False, "distinct_layers": 2,
    "rel_delta": 0.01,
    "quant": {"method": "daq", "metric": "sign", "fmt": "fp8_e4m3",
              "granularity": "channel", "block_size": 128},
    "engine": {"paged": True, "prefix_cache": True, "chunk_size": 32,
               "block_size": 16, "k_steps": 4},
}
MIXES = {
    "decode": {"timed": "serve", "slots": 4, "cache_len": 256,
               "num_blocks": 64, "gen_tokens": 32, "requests_per_call": 4,
               "warmup_requests": 2, "check_requests": 2,
               "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.6,
                          "min": 16, "max": 128}},
    "daq": {"timed": "quantize"},
}


def make_root(tmp: Path, limits: dict | None = None) -> Path:
    """``tmp`` holding BENCHMARK.json and the benchmark's files, plus the
    tiny configuration, its mixes, cells and limits."""
    dst = tmp / "benchmarks" / "chip"
    shutil.copytree(CHIP, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "testdata"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (dst / "configs" / "tiny.json").write_text(json.dumps(TINY))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "benchmarks/chip/configs/tiny.json",
                             "reduced": [], "why": "test"})
    for mix, body in MIXES.items():
        (dst / "traffic" / f"tiny_{mix}.json").write_text(json.dumps(body))
        name = f"tiny.{mix}"
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": f"tiny_{mix}", "chips": 1,
                                   "why": "test"})
        real = f"glm4-9b.{mix}"
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real in m.get("workloads", []):
                m["workloads"].append(name)
        lim = json.loads((dst / "limits" / f"{real}.json").read_text())
        lim.update((limits or {}).get(mix, {}))
        (dst / "limits" / f"{name}.json").write_text(json.dumps(lim))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run(root: Path, mix: str, seed: int = 3, seconds: float = 0.1,
        trace: bool = False) -> dict:
    import jax

    from benchmarks.chip.run import run_cell
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return run_cell(root, bench, f"tiny.{mix}", seed, seconds, trace,
                    jax.devices()[:1])
