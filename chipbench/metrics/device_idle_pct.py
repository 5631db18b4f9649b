"""Share of the traced window with no operation on the device."""


def read(ctx):
    if ctx.device is None or ctx.window_s <= 0 or ctx.device.n_devices == 0:
        return None
    return 100.0 * (1.0 - ctx.device.busy_s / ctx.window_s)
