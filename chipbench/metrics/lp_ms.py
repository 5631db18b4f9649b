"""Mean milliseconds per OPT=AVG round of the host LPs (the program's
``dfrs.lp``, summed over the round's requests)."""
from chipbench.rounds import mean_ms, window_rounds


def read(ctx):
    return mean_ms(window_rounds(ctx), "lp_s", "lps")
