"""The rank kernel's share of its roofline, in %: the HBM bytes of every
launch in the traced window (read off each launch's shapes in the trace,
``benchlib.kernelbytes``) at the chip's peak bandwidth, over the summed
device time of those launches.  The kernel compares and merges and does
no matmul, so bandwidth is its bound."""
from benchlib import kernelbytes, peaks

PROGRAM = r"topk_rank_batch"
KERNEL = r"tpu_custom_call"


def read(ctx):
    s = ctx.get("trace")
    if s is None:
        return None
    evs = s.matching(KERNEL, module=PROGRAM)
    if not evs:
        return None
    moved = sum(kernelbytes.rank_bytes_from_hlo(e.name) for e in evs)
    seconds = sum(e.dur_ns for e in evs) / 1e9
    bw = peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return kernelbytes.roofline_share(moved, seconds, bw)
