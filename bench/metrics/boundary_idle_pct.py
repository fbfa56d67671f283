"""Share of the window in which the device was idle while the host was
inside the program's pass-boundary spans (``merge``, ``q_update``,
``finish``), dispatching the work between passes (``bench/launches.py``)."""

import launches


def read(ctx):
    lo, hi = ctx.window
    ln = launches.for_run(ctx.run)
    if not ln or not any(ln.count(name, lo, hi) for name in launches.BOUNDARY):
        return None
    idle = ln.idle(lo, hi)
    return 100.0 * sum(idle.get(name, 0.0) for name in launches.BOUNDARY) / (hi - lo)
