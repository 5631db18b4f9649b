"""The lanes' CPU seconds over the seconds the serving thread waited at the
barrier in the traced window: near 1 when the lanes run one at a time and
fill the wait, under 1 when handing control between them loses time."""
from chipbench.rounds import barrier_wait_s, window_rounds


def read(ctx):
    rows = window_rounds(ctx)
    if rows is None:
        return None
    wait = barrier_wait_s(ctx, rows)
    if wait <= 0:
        return None
    return sum(r.lane_cpu_s for r in rows) / wait
