"""Device microseconds of one maxmin_batch run in the traced window."""


def read(ctx):
    hit = ctx.device.program("maxmin_batch") if ctx.device else None
    if hit is None or hit[1] == 0:
        return None
    return 1e6 * hit[0] / hit[1]
