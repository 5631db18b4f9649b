"""Share of the traced window spent inside allocate_many."""
from chipbench.stats import alloc_share_pct


def read(ctx):
    return alloc_share_pct(ctx.spans, ctx.window_s)
