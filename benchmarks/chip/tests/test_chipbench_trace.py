"""The trace reduction on a small trace recorded on a TPU v5e by
``record_trace.py``: 40 bf16 [2048, 2048] matmuls inside the host span
``window``, with a 50 ms host sleep between two bursts of 20."""
import pytest

import chipbench_tiny
from benchmarks.chip import trace_reduce as T

TRACE = chipbench_tiny.CHIP / "testdata" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return T.reduce(*T.load(TRACE))


def test_busy_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(0.060700175, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.003588467, rel=1e-9)
    # 40 x 2 x 2048^3 FLOPs take at least this long at 197 TFLOP/s, less
    # a tenth for the clock's rounding
    assert reduced["busy_s"] >= 0.9 * 40 * 2 * 2048**3 / 197e12


def test_top_op_is_the_matmul(reduced):
    name, secs = reduced["device_ops"][0]
    assert name == "jit__lambda:fusion"
    assert secs == pytest.approx(0.003184616, rel=1e-9)
    assert sum(s for _, s in reduced["device_ops"]) >= \
        reduced["busy_s"] * (1 - 1e-9)


def test_longest_gap_is_the_sleep(reduced):
    label, secs = reduced["idle_gaps"][0]
    assert label == "$time sleep"
    assert 0.05 <= secs <= 0.06
    assert all(s <= secs for _, s in reduced["idle_gaps"])


def test_union_merges_overlaps():
    assert T._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]


def test_nothing_to_read_gives_nothing():
    assert T.reduce({}, {"main": [("window", 0, 10)]}) == {}
