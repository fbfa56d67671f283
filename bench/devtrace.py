"""Reduction of a ``jax.profiler`` device trace to the benchmark's numbers.

The profiler writes an XSpace (``*.xplane.pb``).  Each TPU is a plane
``/device:TPU:<i>`` whose line ``XLA Modules`` holds one event per
program run (``jit_<function>(<hash>)``) and whose line ``XLA Ops``
holds one event per operation.  Host threads are lines of the plane
``/host:CPU``; the benchmark's own ``TraceAnnotation`` spans (names
starting with ``bench.``) are among them.  All events are on one clock,
in nanoseconds from the start of the profile; the plane ``Task
Environment`` gives that start on the epoch clock, which places the
program's ``RCCA_TRACE`` spans on the same timeline.

Times below are seconds on that clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    modules: Dict[str, List[Event]]   # device plane -> program runs
    ops: Dict[str, List[Event]]       # device plane -> operations
    host: List[Event]                 # the benchmark's host annotations
    start_epoch: Optional[float]      # profile start, epoch seconds

    def window(self, name: str) -> Interval:
        spans = [e for e in self.host if e.name == name]
        if not spans:
            raise ValueError(f"no host span {name!r} in the trace")
        return spans[0].start, spans[0].end

    def busy_seconds(self, lo: float, hi: float) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(union_length(ev, lo, hi) for ev in self.ops.values()) / len(self.ops)

    def idle_share(self, lo: float, hi: float) -> float:
        return 1.0 - self.busy_seconds(lo, hi) / (hi - lo)

    def modules_in(self, lo: float, hi: float) -> List[Event]:
        """Program runs of all devices that start inside [lo, hi], by start."""
        out = [e for ev in self.modules.values() for e in ev if lo <= e.start < hi]
        return sorted(out, key=lambda e: e.start)

    def spans_from_epoch(self, records: Iterable[dict]) -> List[Event]:
        """The program's RCCA_TRACE span records on this trace's clock."""
        if self.start_epoch is None:
            return []
        return [Event(r["name"], r["t"] - self.start_epoch,
                      r["t"] - self.start_epoch + r["dur"])
                for r in records if r.get("ev") == "span"]

    def breakdown(self, lo: float, hi: float, spans_epoch=(), top: int = 10) -> dict:
        """The device programs that took most time, and the longest idle
        gaps labelled with the innermost host span open at their middle."""
        per: Dict[str, float] = {}
        for e in self.modules_in(lo, hi):
            per[e.name] = per.get(e.name, 0.0) + clip(e, lo, hi)
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        spans = self.host + self.spans_from_epoch(spans_epoch)
        busy = [e for ev in self.ops.values() for e in ev]
        gaps = sorted(idle_gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[label_at((a + b) / 2, spans, exclude=("bench.window",)), b - a]
                              for a, b in gaps]}


def clip(e: Event, lo: float, hi: float) -> float:
    return max(0.0, min(e.end, hi) - max(e.start, lo))


def merged(events: Iterable[Event], lo: float, hi: float) -> List[Interval]:
    """The union of the events' intervals inside [lo, hi], as disjoint
    intervals in order."""
    iv = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                if e.end > lo and e.start < hi)
    out: List[list] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_length(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(events, lo, hi))


def idle_gaps(events: Iterable[Event], lo: float, hi: float) -> List[Interval]:
    """The intervals of [lo, hi] in which no event runs."""
    out, t = [], lo
    for a, b in merged(events, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(t: float, spans: Iterable[Event], exclude=()) -> str:
    """The shortest span that contains ``t``, or ``"none"``."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and s.name not in exclude:
            if best is None or s.dur < best.dur:
                best = s
    return best.name if best is not None else "none"


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def from_profile(pd) -> Trace:
    """A ``jax.profiler.ProfileData`` reduced to what the metrics need."""
    modules, ops, host, start = {}, {}, [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = _events(line)
                elif line.name == "XLA Ops":
                    ops[plane.name] = _events(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(e for e in _events(line) if e.name.startswith("bench."))
        elif plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                start = int(stats["profile_start_time"]) * 1e-9
    return Trace(modules=modules, ops=ops, host=host, start_epoch=start)


def load(log_dir: str) -> Trace:
    """The newest ``*.xplane.pb`` under ``log_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(files[-1]))
