"""maxmin_batch against its bandwidth roofline over the traced window.

The least time is the bytes the OPT=MIN requests must move (each nonzero
weight in and each yield out, as float64, counted from the requests and
not from the padded batch) over the chip's HBM bandwidth; no float64
compute peak is published for the chip, so the compute bound is left out.
"""
from chipbench.stats import roofline_pct, solve_bytes


def read(ctx):
    hit = ctx.device.program("maxmin_batch") if ctx.device else None
    if hit is None or hit[0] <= 0:
        return None
    moved = sum(solve_bytes(s.nnz_min, s.cols_min) for s in ctx.spans)
    return roofline_pct(moved, ctx.peaks["hbm_bytes_per_s"], hit[0])
