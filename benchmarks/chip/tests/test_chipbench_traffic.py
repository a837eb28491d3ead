"""Every traffic mix is deterministic in the seed, gives every seed the same
work, and follows the distributions it declares."""
import json
import statistics

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the repo on sys.path)
from benchmarks.chip import traffic as T

MIXES = sorted(p.stem for p in (chipbench_tiny.CHIP / "traffic").glob("*.json")
               if json.loads(p.read_text())["timed"] == "serve")
VOCAB = 1000


def mix(name):
    return json.loads((chipbench_tiny.CHIP / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = T.Traffic(mix(name), VOCAB, 2**31 + 7).next_call()
    b = T.Traffic(mix(name), VOCAB, 2**31 + 7).next_call()
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work_not_the_tokens(name):
    a = T.Traffic(mix(name), VOCAB, 1).next_call()
    b = T.Traffic(mix(name), VOCAB, 2).next_call()
    assert sorted(map(len, a)) == sorted(map(len, b))
    assert any(not np.array_equal(x, y) for x, y in zip(a, b))
    assert all(0 <= t.min() and t.max() < VOCAB for t in a)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_declared_distribution(name):
    m = mix(name)
    tr = T.Traffic(m, VOCAB, 5)
    reqs = tr.next_call()
    pre = m.get("prefixes")
    own = [len(r) - p for r, p in zip(reqs, tr.last_prefix_lens)]
    d = m["prompt"]
    assert len(reqs) == m["requests_per_call"]
    assert min(own) >= d["min"] and max(own) <= d["max"]
    med = statistics.median(own)
    if d["dist"] == "lognormal":
        assert abs(med - d["median"]) <= 0.05 * d["median"]
    else:
        assert abs(med - (d["min"] + d["max"]) / 2) <= 0.05 * d["max"]
    if pre:
        lens = sorted({len(p) for p in tr.prefixes})
        assert len(lens) == pre["count"]
        assert lens[0] >= pre["len"]["min"] and lens[-1] <= pre["len"]["max"]
        counts = [tr.last_prefix_lens.count(len(p)) for p in tr.prefixes]
        w = np.array([1 / (k + 1) ** pre["zipf_s"] for k in range(len(counts))])
        want = w / w.sum() * len(reqs)
        assert np.all(np.abs(np.array(counts) - want) <= 1)
        for r, p in zip(reqs, tr.last_prefix_lens):
            assert any(np.array_equal(r[:p], q) for q in tr.prefixes)


def test_stratified_quantiles():
    d = {"dist": "uniform", "min": 0, "max": 100}
    assert T.stratified(d, 4) == [12, 38, 62, 88]
    ln = {"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 1,
          "max": 10**6}
    assert T.quantile(ln, 0.5) == 100
    assert T.zipf_counts(2, 1.0, 3) == [2, 1]
