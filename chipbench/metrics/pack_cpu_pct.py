"""The lanes' CPU inside MCB8 packs (the program's ``dfrs.pack``) as a
share of all their CPU between requests, over the traced window."""
from chipbench.rounds import window_rounds


def read(ctx):
    rows = window_rounds(ctx)
    if not rows or not hasattr(rows[0], "pack_cpu_s"):
        return None
    lane = sum(r.lane_cpu_s for r in rows)
    if lane <= 0:
        return None
    return 100.0 * sum(r.pack_cpu_s for r in rows) / lane
