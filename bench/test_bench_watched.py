"""The bounded fit driver and the host-layer readers, on the CPU.

* ``fit_watched`` ends the process with the reason on standard error
  when a fit does not return within its bound (a stubbed fit that waits
  forever, in a subprocess), fails a warm-up whose rho is not finite,
  and otherwise runs a tiny hashed cell as ``fit`` does;
* ``featurize_ms`` sums the program's ``featurize`` spans per chunk
  folded, ``h2d_ms`` the time copies were in flight by the runtime's
  events (``transfers.py``); each reads nothing where there is nothing
  to read;
* the program records ``featurize`` and ``h2d`` spans only under
  ``RCCA_TRACE``, and no ``h2d`` span for chunks already on the device.
"""

import os
import shutil
import subprocess
import sys
import textwrap
import time
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
from test_bench_harness import HASHED_FIT_LIMITS, TINY, _write, on_cpu  # noqa: E402

STUB = textwrap.dedent("""
    import os, sys, threading, types
    import numpy as np
    sys.path.insert(0, {bench!r})
    import harness
    fw = harness.load_module(os.path.join({bench!r}, "drivers", "fit_watched.py"))
    fw.WARMUP_READ_S = fw.WINDOW_FIT_S = 2.0
    calls = []

    def stub_fit(win, n=16, merge_group=None):
        calls.append(n)
        if len(calls) == {hang_at}:
            threading.Event().wait()  # a device program that never returns
        return types.SimpleNamespace(rho=np.ones(3, np.float32))

    fw.fit = types.SimpleNamespace(
        setup=lambda run: {{"fit": stub_fit}}, window=lambda run, st: st["fit"](None),
        StopWindow=fw.fit.StopWindow, _Window=fw.fit._Window)
    run = types.SimpleNamespace(cell=types.SimpleNamespace(config={{"q": 1}}))
    fw.window(run, fw.setup(run))
    print("returned")
""")


@pytest.mark.parametrize("hang_at,what", [(1, "the warm-up fit"), (2, "a fit of the window")])
def test_fit_that_never_returns_ends_the_run(hang_at, what):
    t0 = time.monotonic()
    out = subprocess.run([sys.executable, "-c", STUB.format(bench=BENCH, hang_at=hang_at)],
                         capture_output=True, text=True, timeout=60)
    assert time.monotonic() - t0 < 30
    assert out.returncode == 3, out.stderr
    assert f"bench: {what} did not return in 2 s" in out.stderr
    assert "returned" not in out.stdout


def test_warmup_rho_not_finite_fails_the_run():
    fw = harness.load_module(os.path.join(BENCH, "drivers", "fit_watched.py"))
    nan = types.SimpleNamespace(rho=np.array([0.9, np.nan], np.float32))
    fw.fit = types.SimpleNamespace(setup=lambda run: {"fit": lambda win, **kw: nan},
                                   StopWindow=fw.fit.StopWindow, _Window=fw.fit._Window)
    run = types.SimpleNamespace(cell=types.SimpleNamespace(config={"q": 1}))
    with pytest.raises(harness.BenchError, match="not finite"):
        fw.setup(run)


def test_watched_cell_runs_and_is_correct(tmp_path):
    """A tiny hashed cell on ``fit_watched``, built as new files only."""
    root = str(tmp_path)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    bench = os.path.join(root, "bench")
    shutil.copytree(os.path.join(BENCH, "drivers"), os.path.join(bench, "drivers"))
    config = {"name": "tiny_hashed", "d": 2048, "chunk": 64, "n": 512, "k": 4, "p": 28,
              "center": False, **TINY}
    traffic = {"driver": "fit_watched", "corpus": "hashed_docs",
               "docs": {"vocab": 5000, "doc_len": 12, "zipf": 1.3, "noise": 0.2}}
    _write(os.path.join(bench, "configs", "tiny_hashed.json"), config)
    _write(os.path.join(bench, "traffic", "tiny_watched.json"), traffic)
    _write(os.path.join(bench, "limits", "tiny_watched.json"), HASHED_FIT_LIMITS)
    _write(os.path.join(root, "BENCHMARK.json"), {
        "workloads": [{"name": "tiny_watched", "config": "tiny_hashed",
                       "traffic": "tiny_watched", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
                        "source": "host_clock"},
                       {"name": "fit_rows_per_s", "unit": "rows/s", "better": "higher",
                        "bound": 0.1, "source": "host_clock"}],
        "per_layer": []})
    with pytest.MonkeyPatch.context() as mp:
        on_cpu(mp)
        line = harness.run_cell(root, "tiny_watched", 2**31 + 17, 2.0, False,
                                t_start=time.perf_counter(), bench_dir=bench,
                                log=lambda s: None)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"setup_s", "fit_rows_per_s"} <= set(line["metrics"])


# -- the readers


def _ctx(spans, chunks):
    run = types.SimpleNamespace(records={"chunks_folded": chunks})
    return harness.ReadContext(run=run, window_s=10.0, window=(0.0, 10.0),
                               window_epoch=(100.0, 110.0), devtrace=None, spans=spans,
                               compile_s=0.0, peaks={})


def _span(name, t, dur):
    return {"ev": "span", "name": name, "t": t, "dur": dur}


def test_featurize_reader_per_chunk():
    reader = harness.load_module(os.path.join(BENCH, "metrics", "featurize_ms.py"))
    spans = [_span("featurize", 101.0, 0.25), _span("featurize", 102.0, 0.5),
             _span("featurize", 109.9, 0.2),      # half inside the window
             _span("featurize", 99.0, 0.5),       # before it
             _span("io_wait", 103.0, 1.0), {"ev": "ctr", "name": "featurize", "t": 104.0}]
    assert reader.read(_ctx(spans, 4)) == pytest.approx(1e3 * (0.25 + 0.5 + 0.1) / 4)
    assert reader.read(_ctx([_span("io_wait", 103.0, 1.0)], 4)) is None
    assert reader.read(_ctx([], 4)) is None
    assert reader.read(_ctx(spans, 0)) is None


def test_copies_in_flight_from_runtime_events():
    """Each copy runs from its linearization to the first completion at
    or after its issue that no earlier issue took."""
    import transfers

    L, I, D = transfers.LINEARIZE, transfers.ISSUE, transfers.DONE
    events = [(D, 0.5, 0.5),                        # completes a copy issued before the trace
              (L, 1.0, 1.3), (L, 1.0, 1.31), (I, 1.3, 1.3), (I, 1.31, 1.31),
              (D, 1.34, 1.34), (D, 1.38, 1.38),
              (L, 2.0, 2.3), (I, 2.3, 2.3), (D, 2.35, 2.35),
              ("H2D Dispatch", 2.3, 2.3)]
    spans = sorted((e.start, e.end) for e in transfers.in_flight(events))
    assert spans == pytest.approx([(1.0, 1.3), (1.0, 1.31), (1.3, 1.34), (1.31, 1.38),
                                   (2.0, 2.3), (2.3, 2.35)])
    assert transfers.seconds_in_flight(events, 0.0, 10.0) == pytest.approx(0.38 + 0.35)
    assert transfers.seconds_in_flight(events, 1.2, 2.1) == pytest.approx(0.18 + 0.1)
    assert transfers.seconds_in_flight(events, 3.0, 4.0) is None
    assert transfers.seconds_in_flight([(I, 1.0, 1.0)], 0.0, 10.0) is None


def test_copies_in_recorded_trace():
    """The hashed fits of ``testdata/fit_small`` copy every chunk of each
    view from the host: every issued copy completes after its issue."""
    import jax

    import transfers

    pd = jax.profiler.ProfileData.from_file(
        os.path.join(BENCH, "testdata", "fit_small", "trace.xplane.pb"))
    events = transfers.from_profile(pd)
    copies = transfers.in_flight(events)
    issued = sum(1 for n, _, _ in events if n == transfers.ISSUE)
    assert issued > 0 and sum(1 for e in copies if e.name == "dma") == issued
    assert all(e.end >= e.start for e in copies)


def test_h2d_reader_per_chunk(tmp_path, monkeypatch):
    import transfers

    reader = harness.load_module(os.path.join(BENCH, "metrics", "h2d_ms.py"))
    L, I, D = transfers.LINEARIZE, transfers.ISSUE, transfers.DONE
    events = [(L, 1.0, 1.3), (I, 1.3, 1.3), (D, 1.34, 1.34)]
    monkeypatch.setattr(transfers, "for_run", lambda run: events)
    assert reader.read(_ctx([], 2)) == pytest.approx(1e3 * 0.34 / 2)
    assert reader.read(_ctx([], 0)) is None
    monkeypatch.setattr(transfers, "for_run", lambda run: None)
    assert reader.read(_ctx([], 2)) is None
    monkeypatch.setattr(transfers, "for_run", lambda run: [])
    assert reader.read(_ctx([], 2)) is None


# -- the program's spans


def _span_names(trace_dir):
    from repro import obs

    if not os.path.isdir(trace_dir):
        return []
    return [e["name"] for e in obs.load_events(trace_dir) if e.get("ev") == "span"]


@pytest.mark.parametrize("traced", [False, True])
def test_featurize_and_h2d_spans_only_when_traced(tmp_path, monkeypatch, traced):
    import jax

    from repro.core.rcca import RCCAConfig, randomized_cca_iterator, randomized_cca_streaming
    from repro.data import HashingFeaturizer

    trace_dir = str(tmp_path / "trace")
    if traced:
        monkeypatch.setenv("RCCA_TRACE", trace_dir)
    else:
        monkeypatch.delenv("RCCA_TRACE", raising=False)
    rng = np.random.default_rng(0)
    docs = rng.integers(1, 500, size=(2, 4, 32, 8))
    f = HashingFeaturizer(256, seed=3)
    cfg, key = RCCAConfig(k=2, p=6, q=1, nu=0.01), jax.random.PRNGKey(0)

    def source():
        for i in range(4):
            yield f.featurize_batch(docs[0, i]), f.featurize_batch(docs[1, i])

    randomized_cca_iterator(source, 256, 256, cfg, key, n_chunks=4, engine="jnp")
    names = _span_names(trace_dir)
    if not traced:
        assert names == []
        return
    # 2 passes x 4 chunks: one featurize per view, one h2d per chunk
    assert names.count("featurize") == 16 and names.count("h2d") == 8
    from repro import obs

    events = [e for e in obs.load_events(trace_dir) if e.get("ev") == "span"]
    feat = [e for e in events if e["name"] == "featurize"]
    assert all(e["attrs"]["rows"] == 32 and e["attrs"]["bytes"] == 32 * 256 * 4
               and 0 < e["attrs"]["nnz"] <= 32 * 8 for e in feat)
    assert all(e["attrs"]["bytes"] == 2 * 32 * 256 * 4
               for e in events if e["name"] == "h2d")

    # chunks already on the device: no copy, no h2d span
    A = jax.numpy.asarray(np.stack([f.featurize_batch(d) for d in docs[0]]))
    B = jax.numpy.asarray(np.stack([f.featurize_batch(d) for d in docs[1]]))
    resident_dir = str(tmp_path / "resident")
    monkeypatch.setenv("RCCA_TRACE", resident_dir)
    randomized_cca_streaming(A, B, cfg, key, engine="jnp")
    names = _span_names(resident_dir)
    assert "chunk" in names and "h2d" not in names
