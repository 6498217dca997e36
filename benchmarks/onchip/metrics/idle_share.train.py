"""Share of the traced window in which no operation ran on the device, in %
(1 - busy / window, busy being the union of the device's op intervals)."""


def read(ctx):
    return None if ctx.trace is None else 100.0 * ctx.trace.idle_share
