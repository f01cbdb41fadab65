"""Share of the traced window in which no operation ran on the device:
one minus the union of the device's op intervals over the window."""


def read(ctx):
    if ctx.summary is None or ctx.summary.window_s <= 0:
        return None
    return 100.0 * ctx.summary.idle_share
