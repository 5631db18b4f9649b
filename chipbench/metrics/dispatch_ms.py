"""Mean milliseconds per OPT=MIN round of moving the padded batch to the
device and launching the solve (the program's ``dfrs.dispatch``)."""
from chipbench.rounds import mean_ms, window_rounds


def read(ctx):
    return mean_ms(window_rounds(ctx), "dispatch_s", "min_requests")
