"""Host time spent hashing token bags into dense rows, per chunk folded:
the program's ``featurize`` spans (``HashingFeaturizer.featurize_batch``,
one per view per chunk) inside the window, in ms."""


def read(ctx):
    lo, hi = ctx.window_epoch
    spans = [s for s in ctx.spans if s.get("ev") == "span" and s["name"] == "featurize"
             and s["t"] < hi and s["t"] + s["dur"] > lo]
    chunks = ctx.records.get("chunks_folded", 0)
    if not spans or not chunks:
        return None
    return 1e3 * sum(min(s["t"] + s["dur"], hi) - max(s["t"], lo) for s in spans) / chunks
