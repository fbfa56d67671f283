"""Time a chunk's host rows spend on their way to the device, per chunk
folded: the part of the window in which a host-to-device copy was in
flight, from the runtime's relayout of the rows to the end of their DMA
(``bench/transfers.py``), in ms.  The program's ``h2d`` span covers only
the call that starts the copies, so the time is read from the trace."""

import transfers


def read(ctx):
    lo, hi = ctx.window
    events = transfers.for_run(ctx.run)
    chunks = ctx.records.get("chunks_folded", 0)
    seconds = transfers.seconds_in_flight(events, lo, hi) if events else None
    if seconds is None or not chunks:
        return None
    return 1e3 * seconds / chunks
