"""Waiting jobs the lanes' resume scans tried per allocation request over
the traced window: the backlog each completion walks."""
from chipbench.rounds import window_rounds


def read(ctx):
    rows = window_rounds(ctx)
    if not rows or not hasattr(rows[0], "scanned"):
        return None
    requests = sum(r.requests for r in rows)
    if not requests:
        return None
    return sum(r.scanned for r in rows) / requests
