"""Device programs charged to the host span that launched them
(``bench/launches.py``), on hand-made events and on two small traces
recorded on a TPU v5e: ``testdata/fit_small`` (``record_testdata.py``,
the program's spans only in its JSONL) and ``testdata/fit_spans``
(``record_spans_testdata.py``, the same two fits with the spans as
``rcca.*`` profiler annotations)."""

import os
import shutil
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import fitphases  # noqa: E402
import harness  # noqa: E402
import launches  # noqa: E402
from devtrace import Event  # noqa: E402
from launches import Launches, Program  # noqa: E402

DATA = os.path.join(BENCH, "testdata")
READERS = ("q_update_ms", "finalize_ms", "source_idle_pct", "boundary_idle_pct")


def test_timeline_innermost_span_by_hand():
    tl = launches.Timeline([Event("fit", 0.0, 10.0), Event("pass", 1.0, 6.0),
                            Event("chunk", 2.0, 3.0), Event("q_update", 4.0, 6.0),
                            Event("merge", 4.0, 4.5)])
    assert [tl.at(t) for t in (-1.0, 0.5, 1.5, 2.5, 3.5, 4.2, 5.0, 7.0, 11.0)] == [
        "none", "fit", "pass", "chunk", "pass", "merge", "q_update", "fit", "none"]
    out = {}
    tl.split(-1.0, 4.75, out)
    assert out == pytest.approx({"none": 1.0, "fit": 1.0, "pass": 2.0, "chunk": 1.0,
                                 "merge": 0.5, "q_update": 0.25})


def _synthetic():
    """One device, a window [0, 10] and two fits' worth of host spans.
    The device's clock runs 0.5 s ahead of the host's."""
    host = [Event("bench.window", 0.0, 10.0), Event("bench.fit", 0.0, 9.0),
            Event("fit", 0.0, 8.0), Event("io_wait", 0.0, 1.0), Event("chunk", 1.0, 2.0),
            Event("q_update", 2.0, 4.0), Event("merge", 2.0, 2.5),
            Event("finish", 5.0, 7.0)]
    # (name, device start, device end, launch on the host)
    runs = [("jit_dynamic_slice", -0.4, 0.3, 0.1), ("jit_update_power_stats", 1.0, 2.0, 1.2),
            ("jit_add", 1.6, 1.8, 2.1), ("jit_eigh", 2.0, 3.0, 2.6),
            ("jit_svd", 5.0, 6.0, 5.5), ("jit_transpose", 8.6, 9.5, 9.4),
            ("jit_matmul", 9.5, 10.5, None)]
    programs = [Program(Event(n, a, b), t) for n, a, b, t in runs]
    trace = devtrace.Trace(modules={"/device:TPU:0": [p.event for p in programs]},
                           ops={"/device:TPU:0": [p.event for p in programs]},
                           host=[e for e in host if e.name.startswith("bench.")],
                           start_epoch=None)
    return Launches(trace=trace, programs=programs, spans=host, skew=0.5)


def test_charging_by_launch_on_synthetic_trace():
    ln = _synthetic()
    assert ln.charged(0.0, 10.0) == pytest.approx({
        "io_wait": 0.3,     # clipped to the window
        "chunk": 1.0, "merge": 0.2, "q_update": 1.0, "finish": 1.0,
        "none": 0.9,        # launched after bench.fit, inside bench.window only
        "unjoined": 0.5})
    assert ln.count("q_update", 0.0, 10.0) == 1 and ln.count("finish", 0.0, 10.0) == 1
    assert ln.count("finish", 6.0, 10.0) == 0


def test_idle_split_on_synthetic_trace():
    ln = _synthetic()
    lo, hi = 0.0, 10.0
    idle = ln.idle(lo, hi)
    # device idle [0.3, 1) [3, 5) [6, 8.6), read on the host at +0.5 s
    assert idle == pytest.approx({"io_wait": 0.2, "chunk": 0.5, "q_update": 0.5,
                                  "fit": 2.0, "finish": 1.0, "bench.fit": 1.0,
                                  "none": 0.1})
    assert sum(idle.values()) / (hi - lo) == pytest.approx(ln.trace.idle_share(lo, hi), abs=1e-9)


@pytest.fixture(scope="module")
def small():
    return launches.load(os.path.join(DATA, "fit_small"))


def test_recorded_programs_join_their_launches_one_to_one(small):
    assert sum(len(ev) for ev in small.trace.modules.values()) == len(small.programs) == 364
    got = [p.launch for p in small.programs]
    assert None not in got and len(set(got)) == 364
    # the device's clock runs ahead of the host's by a steady skew
    assert 0.0005 < small.skew < 0.005
    assert all(p.launch <= p.event.start + small.skew for p in small.programs)
    assert {s.name for s in small.spans} == {"bench.window", "bench.fit", "bench.featurize"}


def test_recorded_idle_by_span_sums_to_the_idle_share(small):
    lo, hi = small.trace.window("bench.window")
    idle = small.idle(lo, hi)
    assert set(idle) <= {"bench.fit", "bench.featurize", "none"}
    assert sum(idle.values()) / (hi - lo) == pytest.approx(small.trace.idle_share(lo, hi), abs=1e-9)


@pytest.fixture(scope="module")
def spans():
    return launches.load(os.path.join(DATA, "fit_spans"))


def test_recorded_spans_charge_each_phase(spans):
    lo, hi = spans.trace.window("bench.window")
    assert None not in [p.launch for p in spans.programs]
    names = {s.name for s in spans.spans}
    assert {"fit", "pass", "io_wait", "chunk", "merge", "q_update", "finish"} <= names
    assert (spans.count("q_update", lo, hi), spans.count("finish", lo, hi),
            spans.count("merge", lo, hi)) == (2, 2, 4)
    label = {}
    for p in spans.programs:
        label.setdefault(p.event.name.split("(")[0], set()).add(spans.timeline.at(p.launch))
    for name, where in label.items():
        if fitphases.CHUNK_UPDATE.search(name):
            assert where == {"chunk"}, name
    assert label["jit_eigh"] == {"q_update"}
    assert label["jit_svd"] == {"finish"}
    charged = spans.charged(lo, hi)
    busy = sum(charged.values())
    assert charged.get("none", 0.0) + charged.get("unjoined", 0.0) < 0.01 * busy
    idle = spans.idle(lo, hi)
    assert sum(idle.values()) / (hi - lo) == pytest.approx(spans.trace.idle_share(lo, hi), abs=1e-9)
    assert set(idle) <= names | {"bench.fit", "bench.featurize", "none"}


def _ctx(tmp_path, name):
    """What a metric reader sees of a run whose profile is ``testdata/<name>``."""
    prof = tmp_path / "profile"
    prof.mkdir()
    shutil.copy(os.path.join(DATA, name, "trace.xplane.pb"), prof / "t.xplane.pb")
    tr = devtrace.load(str(prof))
    run = types.SimpleNamespace(out_dir=str(tmp_path), records={})
    lo, hi = tr.window("bench.window")
    return harness.ReadContext(run=run, window_s=hi - lo, window=(lo, hi), window_epoch=(0, 0),
                               devtrace=tr, spans=[], compile_s=0.0, peaks={})


def _read(ctx):
    return {m: harness.load_module(os.path.join(BENCH, "metrics", m + ".py")).read(ctx)
            for m in READERS}


def test_readers_on_the_recorded_spans(tmp_path):
    ctx = _ctx(tmp_path, "fit_spans")
    got = _read(ctx)
    ln = launches.for_run(ctx.run)
    lo, hi = ctx.window
    charged, idle = ln.charged(lo, hi), ln.idle(lo, hi)
    assert got["q_update_ms"] == pytest.approx(1e3 * charged["q_update"] / 2)
    assert got["finalize_ms"] == pytest.approx(1e3 * charged["finish"] / 2)
    assert got["source_idle_pct"] == pytest.approx(100 * idle["io_wait"] / (hi - lo))
    assert got["boundary_idle_pct"] == pytest.approx(
        100 * sum(idle.get(n, 0.0) for n in launches.BOUNDARY) / (hi - lo))
    assert all(v > 0 for v in got.values())
    # an eigh of each view per Q update, the SVD in the finish
    eigh = sum(e.dur for e in ln.trace.modules_in(lo, hi) if e.name.startswith("jit_eigh"))
    assert 1e3 * eigh / 2 <= got["q_update_ms"]


def test_readers_find_nothing_without_program_spans(tmp_path):
    """A program without ``rcca.*`` annotations (the trace of the parent)
    gives no reading, and no error."""
    assert _read(_ctx(tmp_path, "fit_small")) == dict.fromkeys(READERS)
