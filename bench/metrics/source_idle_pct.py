"""Share of the window in which the device was idle while the host was
inside the program's ``io_wait`` spans, pulling the next chunk from its
source (``bench/launches.py``)."""

import launches


def read(ctx):
    lo, hi = ctx.window
    ln = launches.for_run(ctx.run)
    if not ln or not ln.count("io_wait", lo, hi):
        return None
    return 100.0 * ln.idle(lo, hi).get("io_wait", 0.0) / (hi - lo)
