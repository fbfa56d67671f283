"""Share of the chip's bf16 peak FLOP/s that the window's needed work
would fill: the operations of every chunk update folded and of every
pass boundary completed (``bench/work.py``, counted from the data and
the sizes, not from the program's kernel plans) over the traced window.
It bounds every kernel's roofline share from above in the same cells."""


def read(ctx):
    needed = ctx.records.get("chunk_work")
    lo, hi = ctx.window
    if not needed or hi <= lo:
        return None
    ops = sum(o for o, _ in needed) + ctx.records.get("boundary_ops", 0)
    return 100.0 * ops / ((hi - lo) * ctx.peaks["bf16_flops_per_s"])
