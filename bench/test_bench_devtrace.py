"""The trace reduction, on hand-made events and on a small device trace
recorded on a TPU v5e (``bench/testdata/fit_small``: two whole fits of 4
chunks each, q = 1, made by ``bench/record_testdata.py``)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import fitphases  # noqa: E402
from devtrace import Event  # noqa: E402

DATA = os.path.join(BENCH, "testdata", "fit_small")


def test_union_gaps_and_labels_by_hand():
    ev = [Event("x", 1.0, 3.0), Event("y", 2.0, 4.0), Event("z", 6.0, 7.0), Event("w", 9.5, 12.0)]
    assert devtrace.merged(ev, 0.0, 10.0) == [(1.0, 4.0), (6.0, 7.0), (9.5, 10.0)]
    assert devtrace.union_length(ev, 0.0, 10.0) == pytest.approx(4.5)
    assert devtrace.idle_gaps(ev, 0.0, 10.0) == [(0.0, 1.0), (4.0, 6.0), (7.0, 9.5)]
    spans = [Event("outer", 0.0, 10.0), Event("inner", 4.5, 5.5)]
    assert devtrace.label_at(5.0, spans) == "inner"
    assert devtrace.label_at(8.0, spans) == "outer"
    assert devtrace.label_at(8.0, spans, exclude=("outer",)) == "none"


def test_pass_boundaries_by_hand():
    class T:
        def modules_in(self, lo, hi):
            return mods

    p = lambda t: Event("jit_update_power_stats_kernel(1)", t, t + 1)
    f = lambda t: Event("jit_update_final_stats_kernel(2)", t, t + 1)
    mods = [p(0), Event("jit_add(3)", 1, 1.5), p(2),                 # pass 0, a merge inside
            Event("jit_eigh(4)", 3, 5),                                # Q update: 2 s
            f(6), f(7), Event("jit_svd(5)", 8, 11),                    # finish: 3 s
            Event("jit__normal(6)", 11, 12),                           # next fit's Omega: left out
            p(12), p(13), Event("jit_eigh(4)", 14, 15), f(16)]         # Q update: 1 s
    b = fitphases.pass_boundaries(T(), 0, 20, n_chunks=2, q=1)
    assert b == {"q_update": [2.0, 1.0], "finish": [3.0]}


@pytest.fixture(scope="module")
def trace():
    return devtrace.load(DATA)


def test_recorded_trace_window_and_busy_share(trace):
    lo, hi = trace.window("bench.window")
    assert 0 < lo < hi
    (dev,) = trace.ops
    busy = trace.busy_seconds(lo, hi)
    gaps = devtrace.idle_gaps(trace.ops[dev], lo, hi)
    assert busy + sum(b - a for a, b in gaps) == pytest.approx(hi - lo)
    assert max(e.dur for e in trace.ops[dev] if lo <= e.start < hi) <= busy
    assert 0.0 < trace.idle_share(lo, hi) < 1.0


def test_recorded_trace_chunk_updates_and_boundaries(trace):
    lo, hi = trace.window("bench.window")
    ups = fitphases.chunk_updates(trace, lo, hi)
    kinds = [fitphases.CHUNK_UPDATE.search(e.name).group(1) for e in ups]
    assert kinds == (["power"] * 4 + ["final"] * 4) * 2
    b = fitphases.pass_boundaries(trace, lo, hi, n_chunks=4, q=1)
    assert len(b["q_update"]) == 2 and len(b["finish"]) == 1
    assert all(s > 0 for s in b["q_update"] + b["finish"])


def test_recorded_trace_program_spans_share_the_clock(trace):
    with open(os.path.join(DATA, "rcca.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    lo, hi = trace.window("bench.window")
    spans = [s for s in trace.spans_from_epoch(recs) if s.name == "chunk" and s.start >= lo]
    ups = fitphases.chunk_updates(trace, lo, hi)
    assert len(spans) == len(ups) == 16
    # each chunk's fold is dispatched (span starts) before the device runs it
    assert all(s.start < u.end for s, u in zip(spans, ups))
    fits = [e for e in trace.host if e.name == "bench.fit" and e.start >= lo]
    assert len(fits) == 2 and all(lo <= e.start and e.end <= hi for e in fits)


def test_recorded_trace_breakdown(trace):
    with open(os.path.join(DATA, "rcca.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    lo, hi = trace.window("bench.window")
    b = trace.breakdown(lo, hi, spans_epoch=recs)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    names = {n for n, _ in b["idle_gaps"]}
    assert names <= {"bench.fit", "bench.featurize", "fit", "pass", "chunk", "io_wait", "none"}
