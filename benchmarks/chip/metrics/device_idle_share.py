"""device_idle_share: the share of the traced window in which no operation
ran on the device, averaged over the chips used."""


def read(ctx):
    if ctx.reduced.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.reduced.busy_s / ctx.reduced.window_s)
