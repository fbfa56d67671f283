"""Device programs launched inside the program's pass-boundary spans
(``merge``, ``q_update``, ``finish``) and accumulator zeroing
(``acc_init``), per ``finish`` span of the window: how many programs a
fit dispatches outside its chunk folds (``bench/launches.py``)."""

import launches

SPANS = launches.BOUNDARY + ("acc_init",)


def read(ctx):
    lo, hi = ctx.window
    ln = launches.for_run(ctx.run)
    fits = ln.count("finish", lo, hi) if ln else 0
    if not fits:
        return None
    n = sum(1 for p in ln.programs
            if p.launch is not None and lo <= p.launch < hi and ln.timeline.at(p.launch) in SPANS)
    return n / fits
