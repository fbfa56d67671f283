"""The harness end to end on the CPU, on throwaway cells.

Each test builds a checkout in a temporary directory: ``BENCHMARK.json``
with tiny cells, their configuration, traffic and limits files, and
copies of the drivers.  That a cell runs from there shows that a cell,
configuration, traffic mix or metric is added as files alone.  The look
for a chip, the table of peaks and the compile cache are stubbed out for
the run (:func:`on_cpu`); only ``correct`` is read, since no metric of a
CPU run is a device number.
"""

import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402

# Tiny-size limits, between the sound readings (rho_gap, x_cross_gap,
# x_feas_gap <= 1.2e-6; y_rel_err <= 2.0e-7; emb_rel_err <= 1.4e-7 on
# seeds 1-3, 2**31 + 3 and 2**31 + 11 on a CPU) and the control's (>= 3.2e-6,
# >= 1.7e-6 and >= 3.7e-6): see test_bench_control.py.
FIT_LIMITS = {"limits": {"rho_gap": 2e-6, "x_cross_gap": 2e-6, "x_feas_gap": 2e-6}}
HASHED_FIT_LIMITS = {"limits": dict(FIT_LIMITS["limits"], y_rel_err=6e-7)}
SERVE_LIMITS = {"limits": {"emb_rel_err": 1e-6, "wrong_version": 0}}
TINY = {"reduced": [], "assumed": {}, "q": 1, "nu": 0.01, "dtype": "float32",
        "matmul_precision": "highest", "reference": "rcca_plain"}
CELLS = {
    "tiny_fit": ({"name": "tiny_hashed", "d": 2048, "chunk": 64, "n": 256, "k": 4, "p": 28,
                  "center": False, **TINY},
                 {"driver": "fit", "corpus": "hashed_docs",
                  "docs": {"vocab": 5000, "doc_len": 12, "zipf": 1.3, "noise": 0.2}},
                 HASHED_FIT_LIMITS),
    "tiny_dense": ({"name": "tiny_dense", "d": 256, "chunk": 128, "n": 1024, "k": 8, "p": 24,
                    "center": True, **TINY},
                   {"driver": "fit", "corpus": "planted_activations",
                    "activations": {"rank": 8, "rho_top": 0.9, "rho_decay": 0.9, "noise": 1.0,
                                    "feature_spread": 1.0, "mean_scale": 0.5}},
                   FIT_LIMITS),
    "tiny_serve": ({"name": "tiny_hashed", "d": 2048, "chunk": 64, "n": 256, "k": 4, "p": 28,
                    "center": False, **TINY},
                   {"driver": "serve", "rate": 40, "pool": 100, "popularity_zipf": 1.1,
                    "share_a": 0.5, "handlers": 4,
                    "docs": {"vocab": 5000, "doc_len": 12, "zipf": 1.3, "noise": 0.2}},
                   SERVE_LIMITS),
}
SECONDS = {"tiny_fit": 2.0, "tiny_dense": 2.0, "tiny_serve": 1.0}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout whose benchmark holds only throwaway cells."""
    root = str(tmp_path_factory.mktemp("checkout"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    bench = os.path.join(root, "bench")
    shutil.copytree(os.path.join(BENCH, "drivers"), os.path.join(bench, "drivers"))
    workloads = []
    for cell, (config, traffic, limits) in CELLS.items():
        _write(os.path.join(bench, "configs", config["name"] + ".json"), config)
        _write(os.path.join(bench, "traffic", cell + ".json"), traffic)
        _write(os.path.join(bench, "limits", cell + ".json"), limits)
        workloads.append({"name": cell, "config": config["name"], "traffic": cell,
                          "chips": 1, "why": "test"})
    metric = {"unit": "x", "better": "lower", "bound": 0.25, "source": "host_clock"}
    _write(os.path.join(root, "BENCHMARK.json"), {
        "workloads": workloads,
        "end_to_end": [dict(metric, name="setup_s"),
                       dict(metric, name="fit_rows_per_s", workloads=["tiny_fit", "tiny_dense"]),
                       dict(metric, name="serve_p95_ms", workloads=["tiny_serve"])],
        "per_layer": []})
    return root, bench


def on_cpu(mp):
    """Let ``harness.run_cell`` drive a run on the CPU: the CPU stands in
    for the chip, with no peaks and no persistent compile cache."""
    import jax

    import peaks

    def cpu(chips):
        d0 = jax.devices()[0]
        return {"platform": d0.platform, "kind": d0.device_kind, "count": len(jax.devices())}

    mp.setattr(harness, "find_chip", cpu)
    mp.setattr(harness, "use_compile_cache", lambda root: None)
    mp.setattr(peaks, "peaks_for", lambda kind: {})


def run(checkout, cell, seed=2**31 + 11):
    root, bench = checkout
    with pytest.MonkeyPatch.context() as mp:
        on_cpu(mp)
        return harness.run_cell(root, cell, seed, SECONDS[cell], False,
                                t_start=time.perf_counter(), bench_dir=bench,
                                log=lambda s: None)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_throwaway_cell_runs_and_is_correct(checkout, cell):
    line = run(checkout, cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert list(line)[-1] == "checks"


def test_look_for_chip_refuses_the_cpu():
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.find_chip(1)


def test_unknown_cell_is_refused(checkout):
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.load_cell(checkout[0], "no_such_cell", checkout[1])


# -- the timed path broken underneath: `correct` must come out false


def _state_unchanged(mp):
    import repro.core.rcca as rcca

    for name in ("update_power_stats_kernel", "update_final_stats_kernel"):
        mp.setattr(rcca, name, lambda s, a, b, Qa, Qb: s)


def _half_batch(mp):
    import repro.core.rcca as rcca

    for name in ("update_power_stats_kernel", "update_final_stats_kernel"):
        orig = getattr(rcca, name)
        mp.setattr(rcca, name, lambda s, a, b, Qa, Qb, f=orig:
                   f(s, a[: a.shape[0] // 2], b[: b.shape[0] // 2], Qa, Qb))


def _fit_answer_altered(mp):
    import repro.core.rcca as rcca

    orig = rcca.finalize_result

    def altered(*args, **kw):
        res = orig(*args, **kw)
        return res._replace(Xa=res.Xa.at[:, 0].multiply(1.01))

    mp.setattr(rcca, "finalize_result", altered)


def _serve_answer_altered(mp):
    import jax

    import repro.serve.projector as projector

    mp.setattr(projector, "_project_jit",
               lambda dim, k, b: jax.jit(lambda X, x: (x @ X).at[0, 0].add(1e-3)))


def _serve_stale_answer(mp):
    import repro.serve.projector as projector

    orig = projector.BatchedProjector._run_batch
    last = {}

    def stale(self, model, batch):
        orig(self, model, batch)
        for t in batch:  # every answer repeats the previous batch's first one
            prev = last.get(t.view)
            last[t.view] = t.emb
            if prev is not None:
                t.emb = prev

    mp.setattr(projector.BatchedProjector, "_run_batch", stale)


FAULTS = [("tiny_fit", _state_unchanged), ("tiny_fit", _half_batch),
          ("tiny_fit", _fit_answer_altered), ("tiny_dense", _state_unchanged),
          ("tiny_dense", _half_batch), ("tiny_dense", _fit_answer_altered),
          ("tiny_serve", _serve_answer_altered), ("tiny_serve", _serve_stale_answer)]


@pytest.mark.parametrize("cell,fault", FAULTS, ids=[f"{c}-{f.__name__[1:]}" for c, f in FAULTS])
def test_broken_timed_path_is_not_correct(checkout, monkeypatch, cell, fault):
    fault(monkeypatch)
    line = run(checkout, cell)
    assert not line["correct"], line["checks"]
