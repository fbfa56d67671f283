"""Device time of the programs launched inside the program's ``finish``
spans (``finalize_result``: the k x k problem, its SVD and X = Q W), per
fit (``bench/launches.py``)."""

import launches


def read(ctx):
    lo, hi = ctx.window
    ln = launches.for_run(ctx.run)
    n = ln.count("finish", lo, hi) if ln else 0
    if not n:
        return None
    return 1e3 * ln.charged(lo, hi).get("finish", 0.0) / n
