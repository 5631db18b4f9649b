"""Requests per lockstep round over the traced window."""
from chipbench.stats import lanes_per_round


def read(ctx):
    return lanes_per_round(ctx.spans)
