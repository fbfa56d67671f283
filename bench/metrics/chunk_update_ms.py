"""Device time of the chunk-update programs per chunk folded."""

import fitphases


def read(ctx):
    lo, hi = ctx.window
    ev = fitphases.chunk_updates(ctx.devtrace, lo, hi)
    chunks = ctx.records.get("chunks_folded", 0)
    if not ev or not chunks:
        return None
    return 1e3 * sum(min(e.end, hi) - e.start for e in ev) / chunks
