"""Share of the serving window in which no operation ran on the device."""


def read(ctx):
    lo, hi = ctx.window
    return 100.0 * ctx.devtrace.idle_share(lo, hi)
