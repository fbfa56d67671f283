"""Least time of the chunk updates' needed work (``bench/work.py``,
counted from each chunk's own data) over their measured device time."""

import fitphases
import work


def read(ctx):
    lo, hi = ctx.window
    ev = fitphases.chunk_updates(ctx.devtrace, lo, hi)
    needed = ctx.records.get("chunk_work", [])
    if not ev or not needed:
        return None
    least = sum(work.least_time(ops, nbytes, ctx.peaks)[0] for ops, nbytes in needed)
    return 100.0 * least / sum(min(e.end, hi) - e.start for e in ev)
