"""Mean lane CPU milliseconds per allocation request in the traced window:
the lane thread's CPU time from its last answer to its next request."""
from chipbench.rounds import window_rounds


def read(ctx):
    rows = window_rounds(ctx)
    requests = sum(r.requests for r in rows or ())
    if not requests:
        return None
    return 1e3 * sum(r.lane_cpu_s for r in rows) / requests
