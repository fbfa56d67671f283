"""Device programs charged to the host span that launched them, and the
device's idle time split by the host span open meanwhile.

Under ``RCCA_TRACE`` each of the program's spans is also a profiler
annotation ``rcca.<span>`` (``repro.obs.trace``); the benchmark's own
are ``bench.<name>``.  Both lie on the dispatching thread's host line of
the trace, on the profiler's clock.

A device program is joined to its launch through the profiler's flows.
Its ``XLA Modules`` event carries a flow id ``_c`` and a ``run_id`` that
match one host ``DoEnqueueProgram`` (``_p``, ``run_id``).  That enqueue
runs on the dispatching thread or on a runtime thread, and either way
inside a ``tpu::System::Execute=>IssueSequencedEvent`` whose flow
(``_c``) leads to the dispatching thread's ``tpu::System::Execute``
(``_p``).  That event's start is the launch.

The device's events run ahead of the host's on the trace's clock: a
program may start on the device 0.5-1.4 ms before its launch, by an
offset that differs from trace to trace.  ``skew`` is the least shift
that puts every program after its launch, and the idle split reads the
host span open at (device instant + skew).

Times are seconds on the trace's clock, as in ``devtrace``.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

import devtrace
from devtrace import Event

PREFIXES = ("rcca.", "bench.")
WINDOW = "bench.window"
NONE = "none"           # launched, or idle, outside every span
UNJOINED = "unjoined"   # no launch found for the program
BOUNDARY = ("merge", "q_update", "finish")


@dataclasses.dataclass
class Program:
    event: Event                 # the run on the device
    launch: Optional[float]      # its launch on the host, None if not joined


class Timeline:
    """The innermost of properly nested host spans, at any instant."""

    def __init__(self, spans: Iterable[Event]):
        self.times: List[float] = []
        self.labels: List[str] = []
        stack: List[Event] = []
        for s in sorted(spans, key=lambda s: (s.start, -s.end)):
            self._close(stack, s.start)
            if stack and s.end > stack[-1].end:  # keep the nesting proper
                s = Event(s.name, s.start, stack[-1].end)
            stack.append(s)
            self._mark(s.start, s.name)
        self._close(stack, float("inf"))

    def _mark(self, t: float, label: str) -> None:
        self.times.append(t)
        self.labels.append(label)

    def _close(self, stack: List[Event], t: float) -> None:
        while stack and stack[-1].end <= t:
            top = stack.pop()
            self._mark(top.end, stack[-1].name if stack else NONE)

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.times, t) - 1
        return self.labels[i] if i >= 0 else NONE

    def split(self, a: float, b: float, out: Dict[str, float]) -> None:
        """Add to ``out`` the seconds of [a, b) under each innermost span."""
        i = bisect.bisect_right(self.times, a) - 1
        t = a
        while t < b:
            nxt = self.times[i + 1] if i + 1 < len(self.times) else b
            end = min(nxt, b)
            if end > t:
                label = self.labels[i] if i >= 0 else NONE
                out[label] = out.get(label, 0.0) + (end - t)
                t = end
            i += 1


def short(name: str) -> str:
    """``rcca.q_update`` -> ``q_update``; ``bench.*`` names stay whole."""
    return name[len("rcca."):] if name.startswith("rcca.") else name


@dataclasses.dataclass
class Launches:
    trace: devtrace.Trace
    programs: List[Program]
    spans: List[Event]           # the dispatching thread's annotations, short names
    skew: float = 0.0            # device clock ahead of the host's, seconds

    def __post_init__(self):
        self.timeline = Timeline(s for s in self.spans if s.name != WINDOW)

    def count(self, name: str, lo: float, hi: float) -> int:
        """Spans named ``name`` that start inside [lo, hi)."""
        return sum(1 for s in self.spans if s.name == name and lo <= s.start < hi)

    def charged(self, lo: float, hi: float) -> Dict[str, float]:
        """Device seconds inside [lo, hi], by the innermost span open at
        each program's launch."""
        out: Dict[str, float] = {}
        for p in self.programs:
            sec = devtrace.clip(p.event, lo, hi)
            if sec > 0:
                label = UNJOINED if p.launch is None else self.timeline.at(p.launch)
                out[label] = out.get(label, 0.0) + sec
        return out

    def idle(self, lo: float, hi: float) -> Dict[str, float]:
        """Idle seconds of [lo, hi] (``devtrace.idle_gaps``, averaged over
        the devices as ``Trace.idle_share`` is), by the innermost span
        open on the host meanwhile.  They sum to the idle seconds."""
        out: Dict[str, float] = {}
        devices = self.trace.ops or {"": []}
        for ops in devices.values():
            for a, b in devtrace.idle_gaps(ops, lo, hi):
                self.timeline.split(a + self.skew, b + self.skew, out)
        return {k: v / len(devices) for k, v in out.items()}


def _seconds(e) -> Tuple[float, float]:
    return e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def from_profile(pd) -> Launches:
    """A ``jax.profiler.ProfileData`` with each device program joined to
    its launch."""
    modules = []      # (Event, flow, run_id)
    enqueues = {}     # flow -> (host line, start, run_id)
    issues = {}       # host line -> [(start, end, flow)]
    executes = {}     # flow -> start
    notes: Dict[int, List[Event]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        st = dict(e.stats)
                        modules.append((Event(e.name, *_seconds(e)), st.get("_c"),
                                        st.get("run_id")))
        elif plane.name == "/host:CPU":
            for li, line in enumerate(plane.lines):
                for e in line.events:
                    name = e.name
                    if name == "DoEnqueueProgram":
                        st = dict(e.stats)
                        enqueues[st.get("_p")] = (li, _seconds(e)[0], st.get("run_id"))
                    elif name == "tpu::System::Execute=>IssueSequencedEvent":
                        issues.setdefault(li, []).append((*_seconds(e), dict(e.stats).get("_c")))
                    elif name == "tpu::System::Execute":
                        executes[dict(e.stats).get("_p")] = _seconds(e)[0]
                    elif name.startswith(PREFIXES):
                        notes.setdefault(li, []).append(Event(short(name), *_seconds(e)))
    for iv in issues.values():
        iv.sort()
    starts = {li: [a for a, _, _ in iv] for li, iv in issues.items()}

    def launch_of(flow, run_id) -> Optional[float]:
        if flow not in enqueues:
            return None
        li, t, rid = enqueues[flow]
        if rid != run_id or li not in issues:
            return None
        i = bisect.bisect_right(starts[li], t) - 1
        if i < 0 or issues[li][i][1] < t:
            return None
        return executes.get(issues[li][i][2])

    programs = [Program(ev, launch_of(flow, rid)) for ev, flow, rid in modules]
    lags = [p.launch - p.event.start for p in programs if p.launch is not None]
    # the dispatching thread is the host line with the most annotations
    spans = max(notes.values(), key=len) if notes else []
    return Launches(trace=devtrace.from_profile(pd), programs=programs, spans=spans,
                    skew=max([0.0] + lags))


@functools.lru_cache(maxsize=1)
def _load_file(path: str) -> Launches:
    import jax

    return from_profile(jax.profiler.ProfileData.from_file(path))


def load(log_dir: str) -> Optional[Launches]:
    """The newest ``*.xplane.pb`` under ``log_dir``, or None; parsed once
    per process, so that every reader of a run shares one load."""
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    return _load_file(files[-1]) if files else None


def for_run(run) -> Optional[Launches]:
    """The run's traced window (``bench/harness.py`` profiles into
    ``<out_dir>/profile``)."""
    return load(os.path.join(run.out_dir, "profile"))
