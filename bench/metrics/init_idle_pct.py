"""Share of the window in which the device was idle while the host was
inside the program's ``acc_init`` spans, making a pass's zero
accumulators (``bench/launches.py``)."""

import launches


def read(ctx):
    lo, hi = ctx.window
    ln = launches.for_run(ctx.run)
    if not ln or not ln.count("acc_init", lo, hi):
        return None
    return 100.0 * ln.idle(lo, hi).get("acc_init", 0.0) / (hi - lo)
