"""Device time per fit between the passes: q Q updates
(``power_update_Q``) and one finish (``finalize_result``), each with the
merge-tree result before it, averaged over the whole boundaries of the
window."""

import fitphases


def read(ctx):
    lo, hi = ctx.window
    q = ctx.records.get("q")
    n_chunks = ctx.config["n"] // ctx.config["chunk"]
    if q is None:
        return None
    b = fitphases.pass_boundaries(ctx.devtrace, lo, hi, n_chunks, q)
    if not b["finish"] or (q and not b["q_update"]):
        return None
    mean = lambda xs: sum(xs) / len(xs)
    return 1e3 * ((q * mean(b["q_update"]) if q else 0.0) + mean(b["finish"]))
