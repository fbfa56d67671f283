"""Share of the window the fit waited on its chunk source: the program's
``io_wait`` spans (featurizing each chunk on the host) over the window."""


def read(ctx):
    lo, hi = ctx.window_epoch
    waits = [s for s in ctx.spans if s.get("ev") == "span" and s["name"] == "io_wait"]
    if not waits:
        return None
    busy = sum(max(0.0, min(s["t"] + s["dur"], hi) - max(s["t"], lo)) for s in waits)
    return 100.0 * busy / (hi - lo)
