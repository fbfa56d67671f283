"""Which device programs of a fit are chunk updates, and what runs
between the passes.

The chunk updates are the program's jitted per-chunk functions, named
``jit_update_power_stats*`` and ``jit_update_final_stats*`` in the
trace.  Between the last chunk update of a pass and the first of the
next run the merge-tree result, ``power_update_Q`` (after a power pass)
or ``finalize_result`` (after the final pass), and the next fit's draw
of Omega, which is left out by name.
"""

from __future__ import annotations

import re

CHUNK_UPDATE = re.compile(r"^jit_update_(power|final)_stats")
OMEGA_DRAW = re.compile(r"threefry|_normal|random_bits|_uniform")


def chunk_updates(trace, lo: float, hi: float) -> list:
    return [e for e in trace.modules_in(lo, hi) if CHUNK_UPDATE.search(e.name)]


def pass_boundaries(trace, lo: float, hi: float, n_chunks: int, q: int) -> dict:
    """Device seconds of each whole pass boundary in [lo, hi]:
    ``{"q_update": [...], "finish": [...]}``.  The window starts with a
    fit, so every ``n_chunks``-th chunk update closes a pass."""
    out = {"q_update": [], "finish": []}
    seen, gap, passes = 0, None, 0
    for e in trace.modules_in(lo, hi):
        if CHUNK_UPDATE.search(e.name):
            if gap is not None:
                out["q_update" if passes % (q + 1) < q else "finish"].append(gap)
                passes += 1
                gap = None
            seen += 1
            if seen % n_chunks == 0:
                gap = 0.0
        elif gap is not None and not OMEGA_DRAW.search(e.name):
            gap += min(e.end, hi) - e.start
    return out
