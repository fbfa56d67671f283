"""Host-to-device copies, read from the TPU runtime's events in a
``jax.profiler`` trace.

``jax.device_put`` of a numpy array returns before the copy is done, so
a host span around the call does not time it.  The runtime first lays
the rows out in the device's tiled layout on its own threads
(``XlaLinearize``), then issues the DMA (``tpu::System::TransferToDevice``),
whose completion is a later event on another thread
(``tpu::System::TransferToDevice=>IssueEvent=>Done``).  Completions come
in the order the copies were issued.  A copy is in flight from the start
of its linearization to its completion.

Times are seconds on the trace's clock, as in ``devtrace``.
"""

from __future__ import annotations

import functools
import glob
import os
from typing import Iterable, List, Optional, Tuple

import devtrace
from devtrace import Event

LINEARIZE = "XlaLinearize"
ISSUE = "tpu::System::TransferToDevice"
DONE = "tpu::System::TransferToDevice=>IssueEvent=>Done"


def in_flight(events: Iterable[Tuple[str, float, float]]) -> List[Event]:
    """Intervals in which a host-to-device copy was in flight, from the
    runtime's ``(name, start, end)`` events: each linearization, and
    each DMA from its issue to the first completion at or after it that
    no earlier issue took."""
    events = list(events)
    issues = sorted(s for n, s, _ in events if n == ISSUE)
    dones = sorted(s for n, s, _ in events if n == DONE)
    out = [Event(n, s, e) for n, s, e in events if n == LINEARIZE]
    j = 0
    for s in issues:
        while j < len(dones) and dones[j] < s:
            j += 1
        if j == len(dones):
            break
        out.append(Event("dma", s, dones[j]))
        j += 1
    return out


def seconds_in_flight(events, lo: float, hi: float) -> Optional[float]:
    """Seconds of [lo, hi] in which some copy was in flight, or None
    when no copy is in the trace there."""
    spans = [e for e in in_flight(events) if e.start < hi and e.end > lo]
    return devtrace.union_length(spans, lo, hi) if spans else None


def from_profile(pd) -> List[Tuple[str, float, float]]:
    """The runtime's copy events of a ``jax.profiler.ProfileData``."""
    names = (LINEARIZE, ISSUE, DONE)
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events if e.name in names]


@functools.lru_cache(maxsize=1)
def _load_file(path: str):
    import jax

    return from_profile(jax.profiler.ProfileData.from_file(path))


def for_run(run) -> Optional[List[Tuple[str, float, float]]]:
    """The copy events of the run's traced window (``bench/harness.py``
    profiles into ``<out_dir>/profile``), or None without a trace."""
    files = sorted(glob.glob(os.path.join(run.out_dir, "profile", "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return _load_file(files[-1]) if files else None
