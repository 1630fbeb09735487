"""idle_share (device): the share of the traced window in which no
operation ran on the device (1 - the union of busy intervals)."""


def read(ctx):
    if ctx.red is None or ctx.red.window_s <= 0:
        return None
    return 100.0 * ctx.red.idle_share
