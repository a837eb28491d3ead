"""counts.py against FLOPs and bytes worked out by hand."""
import chipbench_tiny  # noqa: F401  (puts the repo on sys.path)
from benchmarks.chip import counts
from benchmarks.chip.model import load_config

CONFIGS = chipbench_tiny.CHIP / "configs"


def test_glm4_9b():
    cfg = load_config(CONFIGS / "glm4-9b.json")
    # 4096*4096 (q) + 2*4096*256 (k, v) + 4096*4096 (o) + 3*4096*13696
    assert counts.matmul_params(cfg) == 203_948_032
    # 40 layers x (2 x 203948032 + 4 x 32 heads x 128 x 1 key)
    assert counts.token_flops(cfg, 0, False) == 16_316_497_920
    # plus the head: 2 x 4096 x 151552
    assert counts.token_flops(cfg, 0, True) == 17_558_011_904
    # prefill of positions 1, 2 (keys 2 + 3), head once, one decode at 3
    mm = 40 * 2 * 203_948_032
    att = 40 * 4 * 32 * 128
    head = 2 * 4096 * 151552
    assert counts.request_flops(cfg, 3, 1, 2) == (
        2 * mm + att * 5 + head + mm + att * 4 + head)


def test_phi3_medium_14b():
    cfg = load_config(CONFIGS / "phi3-medium-14b.json")
    # 5120*5120 + 2*5120*1280 + 5120*5120 + 3*5120*17920
    assert counts.matmul_params(cfg) == 340_787_200
    # 20 layers here x (2 x 340787200 + 4 x 40 heads x 128 x 1 key)
    assert counts.token_flops(cfg, 0, False) == 13_631_897_600
    assert counts.token_flops(cfg, 0, True) == (13_631_897_600
                                                + 2 * 5120 * 32064)


def test_daq_bytes():
    # 3 passes x (post + base) x 2 B, 1 B of code, 4 B per scale
    assert counts.daq_bytes(1000, 10) == 12_000 + 1_000 + 40
