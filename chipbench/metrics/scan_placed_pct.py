"""The waiting jobs that the lanes' resume scans put through
``greedy_place`` on a pool copy, as a share of those they walked, over the
traced window."""
from chipbench.rounds import window_rounds


def read(ctx):
    rows = window_rounds(ctx)
    if not rows or not hasattr(rows[0], "placed"):
        return None
    scanned = sum(r.scanned for r in rows)
    if not scanned:
        return None
    return 100.0 * sum(r.placed for r in rows) / scanned
