"""Share of the traced window the serving thread waited at the lockstep
barrier for the lanes' requests (the program's ``dfrs.barrier_wait``)."""
from chipbench.rounds import barrier_wait_s, window_rounds


def read(ctx):
    rows = window_rounds(ctx)
    if rows is None or ctx.window_s <= 0:
        return None
    return 100.0 * barrier_wait_s(ctx, rows) / ctx.window_s
