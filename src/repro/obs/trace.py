"""Structured spans and counters — the one trace stream for the pass stack.

Every process in a fit (driver, coordinator, cluster workers) appends
JSONL records to its own file under the directory named by the
``RCCA_TRACE`` environment variable (the value ``1`` selects the default
directory ``rcca_trace/``).  Records are written with a single
``os.write`` on an ``O_APPEND`` descriptor, so concurrent threads and
processes interleave whole lines and a killed worker leaves at worst one
torn final line — which the reader skips.

Record shapes (all carry ``ev``, ``t`` = epoch seconds, ``pid``, ``tid``
= the recording OS thread id, and the process ``ctx`` dict set via
:func:`set_context`; ``tid`` is what lets the Perfetto exporter give
each thread — e.g. the engine's I/O prefetchers next to the fold loop —
its own track):

* ``{"ev": "span", "name": ..., "t": t0, "dur": seconds, "sid": n,
  "parent": m | None, "attrs": {...}}`` — one record per completed
  ``with span(...)`` block, emitted at exit.  ``sid`` is unique per
  process; ``parent`` is the enclosing span's sid on the same thread.
* ``{"ev": "ctr", "name": ..., "parent": m | None, "fields": {...}}`` —
  a named bundle of numeric (or short string, for grouping) fields.
* ``{"ev": "proto", "op": ..., "path": ..., "meta": {...}}`` — a cluster
  protocol event mirrored from :mod:`repro.analysis.protocol`; the
  top-level ``op``/``path``/``meta`` keys keep ``check_trace`` working
  directly on an obs trace file.

When ``RCCA_TRACE`` is set every span is also a
``jax.profiler.TraceAnnotation`` named ``rcca.<name>`` (attributes stay
in the JSONL record), entered and exited on the same thread, so a
``jax.profiler`` trace taken meanwhile shows the fit's phases on the
profiler's own clock, beside the device programs they launch.

When ``RCCA_TRACE`` is unset every entry point is a no-op: ``span``
returns a shared null context manager and ``counter`` returns before
building the record, so the traced code path costs one environment
lookup.  Instrumented call sites that loop per chunk should additionally
branch on :func:`enabled` and keep their original loop byte-for-byte.

This module is also the sanctioned clock home for pass-path code
(analysis rule RCCA007): take timings via :func:`monotonic` /
:func:`wall` so spans, counters, and diagnostics share one clock domain.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

TRACE_ENV = "RCCA_TRACE"
DEFAULT_DIR = "rcca_trace"

# RCCA007 exemption: this module *implements* the obs clocks.
monotonic = time.perf_counter
wall = time.time  # rcca: noqa[RCCA004]


def trace_dir() -> Optional[str]:
    """Resolved trace directory, or None when tracing is disabled."""
    val = os.environ.get(TRACE_ENV)
    if not val:
        return None
    return DEFAULT_DIR if val == "1" else val


def enabled() -> bool:
    return bool(os.environ.get(TRACE_ENV))


_CTX: Dict[str, Any] = {}
_FDS: Dict[str, int] = {}
_SIDS = itertools.count(1)
_TLS = threading.local()


def set_context(**attrs: Any) -> None:
    """Stamp process-wide attributes (fit_id, role, shard) on every record."""
    for k, v in attrs.items():
        if v is None:
            _CTX.pop(k, None)
        else:
            _CTX[k] = v


def _fd(dir_: str) -> int:
    path = os.path.join(dir_, f"trace-{os.getpid()}.jsonl")
    fd = _FDS.get(path)
    if fd is None:
        os.makedirs(dir_, exist_ok=True)
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        _FDS[path] = fd
    return fd


def _emit(rec: Dict[str, Any]) -> None:
    dir_ = trace_dir()
    if dir_ is None:
        return
    rec["pid"] = os.getpid()
    rec["tid"] = threading.get_native_id()
    if _CTX:
        rec["ctx"] = dict(_CTX)
    line = json.dumps(rec, sort_keys=True, default=str) + "\n"
    os.write(_fd(dir_), line.encode())


def _stack() -> List[int]:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


class _Span:
    """Context manager recording one span on exit (even when unwinding)."""

    __slots__ = ("name", "attrs", "sid", "parent", "_t0", "_w0", "_note")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        st = _stack()
        self.parent = st[-1] if st else None
        self.sid = next(_SIDS)
        st.append(self.sid)
        # jax is imported here, not at module load: the report CLI and
        # processes that never trace the device import this module too
        import jax.profiler

        self._note = jax.profiler.TraceAnnotation(f"rcca.{self.name}")
        self._note.__enter__()
        self._w0 = wall()
        self._t0 = monotonic()
        return self

    def note(self, **attrs: Any) -> None:
        """Add attributes known only once the span's work is done."""
        self.attrs.update(attrs)

    def __exit__(self, *exc: Any) -> None:
        dur = monotonic() - self._t0
        self._note.__exit__(*exc)
        st = _stack()
        if st and st[-1] == self.sid:
            st.pop()
        rec: Dict[str, Any] = {
            "ev": "span",
            "name": self.name,
            "t": self._w0,
            "dur": dur,
            "sid": self.sid,
            "parent": self.parent,
        }
        if self.attrs:
            rec["attrs"] = self.attrs
        _emit(rec)


class _NullSpan:
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def note(self, **attrs: Any) -> None:
        return None

    def __exit__(self, *exc: Any) -> None:
        return None


_NULL = _NullSpan()


def span(name: str, **attrs: Any) -> Any:
    """``with span("pass", pass_idx=0):`` — no-op when tracing is off."""
    if not os.environ.get(TRACE_ENV):
        return _NULL
    return _Span(name, attrs)


def counter(name: str, **fields: Any) -> None:
    """Record a named bundle of numeric fields (strings allowed as keys
    for grouping, e.g. ``kernel="powerpass"`` or ``site="prefetch"``)."""
    if not os.environ.get(TRACE_ENV):
        return
    st = _stack()
    _emit({
        "ev": "ctr",
        "name": name,
        "t": wall(),
        "parent": st[-1] if st else None,
        "fields": fields,
    })


def proto_event(rec: Dict[str, Any]) -> None:
    """Mirror a cluster-protocol event into the obs stream (op/path/meta
    stay top-level so the protocol race detector reads obs files)."""
    if not os.environ.get(TRACE_ENV):
        return
    out = dict(rec)
    out["ev"] = "proto"
    out["t"] = wall()
    _emit(out)


def iter_events(path: str) -> Iterator[Dict[str, Any]]:
    """Yield records from a trace file or directory of ``*.jsonl`` files.

    Tolerates a torn final line (a killed writer) by skipping anything
    that does not parse as JSON.
    """
    if os.path.isdir(path):
        files = sorted(
            os.path.join(path, f) for f in os.listdir(path)
            if f.endswith(".jsonl")
        )
    else:
        files = [path]
    for fp in files:
        with open(fp, "r", encoding="utf-8", errors="replace") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict):
                    yield rec


def load_events(path: str) -> List[Dict[str, Any]]:
    return list(iter_events(path))
