"""Mean milliseconds per OPT=MIN round of waiting for the solve and copying
its answer back (the program's ``dfrs.fetch``)."""
from chipbench.rounds import mean_ms, window_rounds


def read(ctx):
    return mean_ms(window_rounds(ctx), "fetch_s", "min_requests")
