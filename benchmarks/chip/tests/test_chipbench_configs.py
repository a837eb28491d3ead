"""Each configuration file holds its published widths; only the keys that
BENCHMARK.json lists under ``reduced`` differ from the source, and none of
them is a width."""
import json

import pytest

import chipbench_tiny
from benchmarks.chip.model import load_config

BENCH = json.loads((chipbench_tiny.ROOT / "BENCHMARK.json").read_text())
WIDTHS = {"d_model", "d_ff", "head_dim", "n_heads", "n_kv_heads",
          "vocab_size"}
FILES = sorted((chipbench_tiny.CHIP / "configs").glob("*.json"))


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_widths_are_published(path):
    cfg = load_config(path)
    entry = next((c for c in BENCH["configs"] if c["name"] == cfg["name"]),
                 {"reduced": list(cfg["reduced_why"])})
    assert not WIDTHS & set(entry["reduced"])
    assert set(entry["reduced"]) == set(cfg["reduced_why"])
    assert entry.get("source", cfg["source"]) == cfg["source"]
    for key, hf in cfg["published_as"].items():
        if key not in entry["reduced"]:
            assert cfg[key] == cfg["published"][hf], key
    for key in WIDTHS - set(cfg["published_as"]):
        assert key in cfg["assumed"], key
    assert cfg["head_dim"] * cfg["n_heads"] >= cfg["d_model"] // 2


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_model_config_carries_the_file(path):
    from benchmarks.chip.model import model_config
    cfg = load_config(path)
    m = model_config(cfg)
    assert (m.d_model, m.d_ff, m.resolved_head_dim, m.n_heads, m.n_kv_heads,
            m.vocab_size, m.n_layers) == (
        cfg["d_model"], cfg["d_ff"], cfg["head_dim"], cfg["n_heads"],
        cfg["n_kv_heads"], cfg["vocab_size"], cfg["n_layers"])
