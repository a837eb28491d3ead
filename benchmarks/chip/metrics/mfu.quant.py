"""The whole quantize's share of the chip's peak, in percent.  DAQ runs no
matrix multiplication, so its peak is HBM bandwidth: the bytes Alg. 1 must
move (counts.daq_bytes) over the peak bandwidth, over the measured time."""


def read(ctx):
    w = ctx["work"]
    if not w.get("min_bytes"):
        return None
    return 100.0 * w["min_bytes"] / ctx["peaks"]["hbm_bytes_s"] / ctx["window_s"]
