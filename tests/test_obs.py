"""Trace integrity for repro.obs (the unified tracing/roofline layer).

The contract under test, in order of importance:

1. **Tracing off is free and invisible** — with ``RCCA_TRACE`` unset a
   fit produces bitwise-identical results to a traced one and writes no
   trace files (the hard acceptance bar: observability must not perturb
   the pass arithmetic).
2. **Spans nest and cover** — a multi-process Hybrid fit yields one
   trace file per process whose spans have valid parent references,
   child time contained in the parent window, and top-level spans
   covering ≥ 95% of each process's traced wall (less would mean a
   phase of the fit runs outside any span).
3. **The roofline is the KernelPlan cost model** — per-kernel
   ``kernel_cost`` counters in a trace reproduce
   :func:`repro.kernels.ops.chunk_cost` exactly.
4. **A killed worker leaves a parseable trace** — hard ``os._exit``
   mid-pass must not corrupt the stream beyond one torn final line,
   which the reader skips.
5. **The Hybrid/Sharded device fold overlaps its gather** — the
   mesh-path batch gather streams through the ChunkPrefetcher, so the
   ``mesh_gather`` io counter shows reads hidden behind device compute.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.cluster.worker import KILL_ENV
from repro.core.rcca import RCCAConfig
from repro.data import PlantedCCAData
from repro.exec import Cluster, Hybrid, Local, Sharded
from repro.exec import fit as exec_fit
from repro.kernels import ops as kernel_ops
from repro.obs import load_events
from repro.obs import report as obs_report
from repro.store import ingest_planted

N, DA, DB, CHUNK = 1024, 24, 16, 128  # 8 chunks
G = 2  # 4 merge groups
CFG = RCCAConfig(k=3, p=5, q=1, nu=0.01, center=True)
KEY = 7


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    data = PlantedCCAData(n=N, da=DA, db=DB, rank=4, noise=0.4,
                          seed=13, chunk=CHUNK)
    return ingest_planted(str(tmp_path_factory.mktemp("obs") / "store"), data)


def _fit(store, tmp_path, topology=Local(), **kw):
    return exec_fit(store, CFG, jax.random.PRNGKey(KEY), topology=topology,
                    engine="jnp", merge_group=G, **kw)


def assert_bit_identical(r1, r2):
    for name in ("Xa", "Xb", "rho", "Qa", "Qb"):
        a1, a2 = np.asarray(getattr(r1, name)), np.asarray(getattr(r2, name))
        assert np.array_equal(a1, a2), f"{name} differs"


# ---------------------------------------------------------------------------
# 1. tracing off: bitwise identical, no files
# ---------------------------------------------------------------------------


def test_trace_off_is_bitwise_invisible(store, tmp_path, monkeypatch):
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv("RCCA_TRACE", trace_dir)
    traced = _fit(store, tmp_path)
    assert os.path.isdir(trace_dir) and os.listdir(trace_dir)

    monkeypatch.delenv("RCCA_TRACE")
    off_dir = str(tmp_path / "off")
    monkeypatch.chdir(tmp_path)  # a stray default rcca_trace/ would land here
    plain = _fit(store, tmp_path)
    assert_bit_identical(traced, plain)
    assert not os.path.exists(off_dir)
    assert not os.path.exists(str(tmp_path / "rcca_trace"))


# ---------------------------------------------------------------------------
# 2. hybrid fit: spans nest, parents resolve, coverage >= 95%
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topology", [Local(), Sharded()],
                         ids=["stream", "mesh"])
def test_pass_boundary_spans(store, tmp_path, monkeypatch, topology):
    """Each fit has q ``q_update`` spans under their power pass, one
    ``finish`` under the fit, and q + 1 ``merge`` spans, each under the
    boundary that consumes the pass's stats."""
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv("RCCA_TRACE", trace_dir)
    _fit(store, tmp_path, topology=topology)
    monkeypatch.delenv("RCCA_TRACE")
    spans = [ev for ev in load_events(trace_dir) if ev.get("ev") == "span"]
    by_sid = {sp["sid"]: sp for sp in spans}
    named = lambda name: [sp for sp in spans if sp["name"] == name]
    parent = lambda sp: by_sid[sp["parent"]]
    site = {"Local": "stream", "Sharded": "mesh"}[type(topology).__name__]

    q_updates, finishes, merges = (named(n) for n in ("q_update", "finish", "merge"))
    assert len(q_updates) == CFG.q and len(finishes) == 1
    assert len(merges) == CFG.q + 1
    for sp in q_updates:
        p = parent(sp)
        assert p["name"] == "pass" and p["attrs"]["kind"] == "power"
        assert sp["attrs"]["pass_idx"] == p["attrs"]["pass_idx"]
    (fin,) = finishes
    assert parent(fin)["name"] == "fit" and parent(fin)["attrs"]["site"] == site
    assert [parent(m)["name"] for m in merges] == ["q_update"] * CFG.q + ["finish"]
    assert [m["attrs"]["pass_idx"] for m in merges] == list(range(CFG.q + 1))
    assert {sp["attrs"]["site"] for sp in q_updates + finishes + merges} == {site}


def test_spans_are_profiler_annotations_only_when_tracing(tmp_path, monkeypatch):
    """Under RCCA_TRACE a span is also a ``rcca.<name>`` profiler
    annotation, entered and exited around the span; unset, none."""
    from repro import obs

    log = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    monkeypatch.delenv("RCCA_TRACE", raising=False)
    with obs.span("q_update", pass_idx=0):
        pass
    assert log == []

    monkeypatch.setenv("RCCA_TRACE", str(tmp_path / "trace"))
    with obs.span("q_update", pass_idx=0):
        with obs.span("merge"):
            pass
    assert log == [("enter", "rcca.q_update"), ("enter", "rcca.merge"),
                   ("exit", "rcca.merge"), ("exit", "rcca.q_update")]
    (rec,) = [ev for ev in load_events(str(tmp_path / "trace"))
              if ev["name"] == "q_update"]
    assert rec["attrs"] == {"pass_idx": 0}

    # the report CLI and untraced processes load the module without jax
    src = os.path.dirname(os.path.dirname(os.path.dirname(obs.__file__)))
    code = ("import sys, repro.obs.trace, repro.obs.report; "
            "sys.exit('jax' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code],
                          env=dict(os.environ, PYTHONPATH=src)).returncode == 0


@pytest.fixture(scope="module")
def hybrid_trace(store, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("hybrid")
    trace_dir = str(tmp / "trace")
    os.environ["RCCA_TRACE"] = trace_dir  # inherited by worker subprocesses
    try:
        res = _fit(store, tmp,
                   topology=Hybrid(n_workers=2, devices_per_worker=2),
                   cluster_dir=str(tmp / "cl"), worker_timeout=300)
    finally:
        del os.environ["RCCA_TRACE"]
    return trace_dir, res


def test_hybrid_spans_nest_and_cover(hybrid_trace):
    trace_dir, _ = hybrid_trace
    events = load_events(trace_dir)
    spans = [ev for ev in events if ev.get("ev") == "span"]
    pids = {ev["pid"] for ev in spans}
    # coordinator + at least one worker process per pass
    assert len(pids) >= 3
    by_pid = {}
    for sp in spans:
        by_pid.setdefault(sp["pid"], {})[sp["sid"]] = sp
    for pid, sids in by_pid.items():
        for sp in sids.values():
            if sp["parent"] is None:
                continue
            parent = sids.get(sp["parent"])
            assert parent is not None, \
                f"pid {pid}: span {sp['name']} has dangling parent"
            # child window inside the parent window (50ms clock slack:
            # t is wall, dur is monotonic)
            assert sp["t"] >= parent["t"] - 0.05
            assert sp["t"] + sp["dur"] <= parent["t"] + parent["dur"] + 0.05
    # roles stamped via set_context reach every record
    roles = {sp.get("ctx", {}).get("role") for sp in spans}
    assert "coordinator" in roles
    assert any(r and r.startswith("worker") for r in roles)

    report = obs_report.analyze(trace_dir)
    for pid, proc in report["processes"].items():
        assert proc["coverage"]["fraction"] >= 0.95, \
            f"pid {pid} ({proc['role']}): only " \
            f"{proc['coverage']['fraction']:.0%} of the traced window is " \
            "inside top-level spans"
    # the coordinator decomposes into the protocol phases
    coord = next(p for p in report["processes"].values()
                 if p["role"] == "coordinator")
    for phase in ("fit", "pass", "publish", "barrier", "merge"):
        assert phase in coord["phases"], f"missing {phase} span"
    # one trace serves the race detector too
    assert "protocol" in report
    assert report["protocol"]["violations"] == []


def test_hybrid_gather_overlaps_prefetch(hybrid_trace):
    """The device-parallel group fold streams its batch gather through
    the ChunkPrefetcher: reads happen on the producer thread while the
    devices fold the previous batch, so stall < read time."""
    trace_dir, _ = hybrid_trace
    gather = [ev for ev in load_events(trace_dir)
              if ev.get("ev") == "ctr" and ev.get("name") == "io"
              and ev.get("fields", {}).get("site") == "mesh_gather"]
    assert gather, "hybrid workers emitted no mesh_gather io counter"
    chunks = sum(ev["fields"]["chunks"] for ev in gather)
    assert chunks == N // CHUNK * 2  # every chunk, both passes
    stall = sum(ev["fields"]["io_stall_s"] for ev in gather)
    read = sum(ev["fields"]["read_s"] for ev in gather)
    # local reads are near-instant, so allow scheduling noise; the
    # strict overlap assertion runs against a slow reader below
    assert stall <= read + 0.05


# ---------------------------------------------------------------------------
# 3. roofline counters == the KernelPlan cost model
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["jnp", "kernels"])
def test_kernel_cost_counters_match_cost_model(store, tmp_path, monkeypatch,
                                               engine):
    trace_dir = str(tmp_path / f"trace_{engine}")
    monkeypatch.setenv("RCCA_TRACE", trace_dir)
    exec_fit(store, CFG, jax.random.PRNGKey(KEY), engine=engine,
             merge_group=G)
    monkeypatch.delenv("RCCA_TRACE")

    counted = {}
    for ev in load_events(trace_dir):
        if ev.get("ev") != "ctr" or ev.get("name") != "kernel_cost":
            continue
        f = ev["fields"]
        t = counted.setdefault(f["kernel"], {"calls": 0, "flops": 0,
                                             "bytes": 0})
        for k in t:
            t[k] += f[k]

    n_chunks = N // CHUNK
    kt = CFG.sketch
    expected = {}
    for kind in ("power", "final"):
        cost = kernel_ops.chunk_cost(kind, CHUNK, DA, DB, kt, "float32",
                                     engine=engine)
        for part in cost["kernels"]:
            t = expected.setdefault(part["kernel"], {"calls": 0, "flops": 0,
                                                     "bytes": 0})
            for k in t:
                t[k] += part[k] * n_chunks
    assert counted == expected


# ---------------------------------------------------------------------------
# 4. killed worker: parseable trace, torn-line tolerance
# ---------------------------------------------------------------------------


def test_killed_worker_leaves_parseable_trace(store, tmp_path):
    trace_dir = str(tmp_path / "trace")
    os.environ["RCCA_TRACE"] = trace_dir
    try:
        res = exec_fit(store, CFG, jax.random.PRNGKey(KEY),
                       topology=Cluster(n_workers=2),
                       cluster_dir=str(tmp_path / "cl"), engine="jnp",
                       merge_group=G, worker_timeout=300,
                       env_overrides={0: {KILL_ENV: "0:2"}})
    finally:
        del os.environ["RCCA_TRACE"]
    assert res.diagnostics["cluster"]["passes"][0]["redispatched_groups"]

    # simulate the torn final line a mid-write kill can leave
    files = sorted(os.listdir(trace_dir))
    with open(os.path.join(trace_dir, files[0]), "a") as f:
        f.write('{"ev": "span", "name": "torn')

    events = load_events(trace_dir)
    assert all(isinstance(ev, dict) for ev in events)
    report = obs_report.analyze(trace_dir)
    assert report["redispatches"] >= 1
    assert report["protocol"]["violations"] == []
    # the repair round's publishes are in the trace despite the kill
    publishes = [ev for ev in events if ev.get("ev") == "span"
                 and ev["name"] == "publish"
                 and ev.get("ctx", {}).get("role", "").startswith("worker")]
    assert len(publishes) >= store.n_chunks // G  # every group published


# ---------------------------------------------------------------------------
# 5. sharded mesh fold overlaps a slow reader
# ---------------------------------------------------------------------------


class _SlowReader:
    """Store delegate whose chunk reads cost a visible ~2ms each."""

    def __init__(self, reader):
        self._reader = reader

    def __getattr__(self, name):
        return getattr(self._reader, name)

    def get_chunk(self, i):
        time.sleep(0.002)
        return self._reader.get_chunk(i)


def test_mesh_gather_hides_slow_reads(store, tmp_path, monkeypatch):
    from repro.exec import PassEngine

    eng = PassEngine(CFG, engine="jnp", topology=Sharded(), merge_group=G)
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv("RCCA_TRACE", trace_dir)
    slow = eng.run_mesh(_SlowReader(store), jax.random.PRNGKey(KEY))
    monkeypatch.delenv("RCCA_TRACE")
    plain = eng.run_mesh(store, jax.random.PRNGKey(KEY))
    assert_bit_identical(slow, plain)

    report = obs_report.analyze(trace_dir)
    gather = report["io"]["mesh_gather"]
    assert gather["chunks"] == N // CHUNK * 2
    # the prefetch thread reads ahead while the mesh folds: some read
    # time is hidden, so the consumer stalled for less than the reads
    assert gather["io_stall_s"] < gather["read_s"]
    assert gather["overlap"] > 0


# ---------------------------------------------------------------------------
# trajectory schema
# ---------------------------------------------------------------------------


def test_trajectory_build_and_validate(tmp_path):
    from repro.obs import trajectory

    results = tmp_path / "results"
    results.mkdir()
    (results / "BENCH_x.json").write_text(json.dumps({
        "bench": "x", "schema": 1, "meta": {"commit": "abc"},
        "speedup": 2.0,
        "results": [{"name": "r0", "us": 10.0, "note": "text ignored"}],
    }))
    # legacy artifact: no schema/meta stamp — still folded, meta=None
    (results / "BENCH_y.json").write_text(json.dumps({
        "bench": "y", "wall_s": 1.5}))
    out = trajectory.write(str(results))
    traj = json.loads((results / "TRAJECTORY.json").read_text())
    assert trajectory.validate(traj) == []
    assert out.endswith("TRAJECTORY.json")
    by_bench = {e["bench"]: e for e in traj["entries"]}
    assert by_bench["x"]["metrics"] == {"speedup": 2.0, "r0.us": 10.0}
    assert by_bench["x"]["meta"] == {"commit": "abc"}
    assert by_bench["y"]["meta"] is None
    assert by_bench["x"]["deltas"] == {}  # first trajectory: no previous

    # regression deltas against the previous trajectory
    (results / "BENCH_x.json").write_text(json.dumps({
        "bench": "x", "speedup": 3.0,
        "results": [{"name": "r0", "us": 10.0}]}))
    traj2 = trajectory.build(str(results))
    d = {e["bench"]: e["deltas"] for e in traj2["entries"]}["x"]
    assert d["speedup"] == {"prev": 2.0, "cur": 3.0, "rel": 0.5}
    assert "r0.us" not in d  # unchanged metrics carry no delta

    # malformed trajectories are named, not swallowed
    assert trajectory.validate({"schema": 99, "entries": []})
    assert trajectory.validate({"schema": 1, "entries": [{"bench": "z"}]})
    (results / "TRAJECTORY.json").write_text("{not json")
    errs = trajectory.validate_file(str(results / "TRAJECTORY.json"))
    assert errs and "not valid JSON" in errs[0]
