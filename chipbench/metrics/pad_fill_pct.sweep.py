"""OPT=MIN nonzeros over the padded B*N*W of the traced window."""
from chipbench.stats import pad_fill_pct


def read(ctx):
    return pad_fill_pct(ctx.spans)
