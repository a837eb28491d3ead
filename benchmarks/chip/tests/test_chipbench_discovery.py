"""A new configuration, traffic mix, timed path, length distribution and
per-layer metric need only new files plus new entries in BENCHMARK.json:
the harness finds them by name and no file that is already there
changes."""
import json

import chipbench_tiny as T
from benchmarks.chip import peaks
from benchmarks.chip.run import cell_files
from benchmarks.chip.traffic import Traffic

PROBE = '''def read(ctx):
    return float(ctx["work"].get("generated", 0)) or None
'''
# a timed path of a new kind: serving whose every call sends the same
# requests, built on the serve path's file
KIND = '''from pathlib import Path

from benchmarks.chip import timed


class Timed(timed.load(Path(__file__).parents[1], "serve")):
    def setup(self):
        super().setup()
        first = self.traffic.next_call()
        self.traffic.next_call = lambda: first
'''
CONSTANT = '''def quantile(dist, u):
    return dist["len"]
'''


def test_new_files_and_entries_are_found(tmp_path, monkeypatch):
    root = T.make_root(tmp_path)
    chip = root / "benchmarks" / "chip"
    (chip / "metrics" / "tiny_tokens.py").write_text(PROBE)
    (chip / "kinds" / "tiny_repeat.py").write_text(KIND)
    (chip / "dists" / "tiny_constant.py").write_text(CONSTANT)
    mix = dict(T.MIXES["decode"], timed="tiny_repeat",
               prompt={"dist": "tiny_constant", "len": 24, "min": 16,
                       "max": 128})
    (chip / "traffic" / "tiny_repeat.json").write_text(json.dumps(mix))
    (chip / "limits" / "tiny.repeat.json").write_text(
        (chip / "limits" / "tiny.decode.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.repeat", "config": "tiny",
                               "traffic": "tiny_repeat", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.decode" in m.get("workloads", []):
            m["workloads"].append("tiny.repeat")
    bench["per_layer"].append({
        "name": "tiny_tokens", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "gen_tok_s", "workloads": ["tiny.decode", "tiny.repeat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    for f in T.CHIP.rglob("*"):
        rel = f.relative_to(T.CHIP)
        if f.is_file() and rel.parts[0] not in ("tests", "testdata") \
                and "__pycache__" not in rel.parts:
            assert (chip / rel).read_bytes() == f.read_bytes(), rel

    files = cell_files(root, bench, "tiny.decode")
    assert files["config"] == chip / "configs" / "tiny.json"
    assert files["traffic"] == chip / "traffic" / "tiny_decode.json"
    assert "tiny_tokens" in [m["name"] for m in files["per_layer"]]

    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    res = T.run(root, "decode", trace=True)
    mix = T.MIXES["decode"]
    assert res["correct"]
    assert res["metrics"]["tiny_tokens"]["value"] == \
        res["attempted"] * mix["gen_tokens"]
    assert 0 < res["metrics"]["occupancy.serve"]["value"] <= 100
    assert "gen_tok_s" not in res["metrics"]
    assert list(res)[-1] == "checks"

    reqs = Traffic(json.loads((chip / "traffic" / "tiny_repeat.json")
                              .read_text()), 512, 3, chip).next_call()
    assert [len(r) for r in reqs] == [24] * len(reqs)
    res = T.run(root, "repeat", seconds=0.5)
    assert res["correct"] and res["attempted"] >= 2 * mix["requests_per_call"]
    assert res["metrics"]["gen_tok_s"]["value"] > 0
