"""Mean milliseconds of one allocate_many round in the traced window."""
from chipbench.stats import alloc_round_ms


def read(ctx):
    return alloc_round_ms(ctx.spans)
