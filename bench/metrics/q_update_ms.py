"""Device time of the programs launched inside the program's
``q_update`` spans (``power_update_Q``: the centering and ``orth`` of
both views), per Q update (``bench/launches.py``)."""

import launches


def read(ctx):
    lo, hi = ctx.window
    ln = launches.for_run(ctx.run)
    n = ln.count("q_update", lo, hi) if ln else 0
    if not n:
        return None
    return 1e3 * ln.charged(lo, hi).get("q_update", 0.0) / n
