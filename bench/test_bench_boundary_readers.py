"""The pass-boundary readers ``init_idle_pct`` and
``boundary_launches_per_fit`` on the hand-made trace and the two chip
recordings of ``test_bench_launches.py``."""

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import launches  # noqa: E402
from devtrace import Event  # noqa: E402
from launches import Launches  # noqa: E402
from test_bench_launches import _ctx, _synthetic  # noqa: E402

READERS = ("init_idle_pct", "boundary_launches_per_fit")


def _read(ctx):
    return {m: harness.load_module(os.path.join(BENCH, "metrics", m + ".py")).read(ctx)
            for m in READERS}


def test_boundary_readers_on_synthetic_trace(monkeypatch):
    """``_synthetic`` with two ``acc_init`` spans: one over idle device
    time, one around the launch of ``jit_transpose``."""
    ln = _synthetic()
    ln = Launches(trace=ln.trace, programs=ln.programs, skew=ln.skew,
                  spans=ln.spans + [Event("acc_init", 7.0, 7.5), Event("acc_init", 9.3, 9.5)])
    monkeypatch.setattr(launches, "for_run", lambda run: ln)
    got = _read(types.SimpleNamespace(run=None, window=(0.0, 10.0)))
    # idle read on the host in [6.5, 9.1): 0.5 s of it under acc_init;
    # launched under merge, q_update, finish and acc_init: one each, one finish
    assert got == pytest.approx({"init_idle_pct": 5.0, "boundary_launches_per_fit": 4.0})


def test_boundary_readers_on_the_recorded_spans(tmp_path):
    """The recording's two fits launch 108 programs under ``q_update``,
    88 under ``finish`` and none under ``merge`` (each program's
    innermost enclosing span, found by hand), and it has no
    ``acc_init`` span."""
    got = _read(_ctx(tmp_path, "fit_spans"))
    assert got == {"init_idle_pct": None, "boundary_launches_per_fit": (108 + 88) / 2}


def test_boundary_readers_find_nothing_without_program_spans(tmp_path):
    """The parent-like recording, with no ``rcca.*`` annotations, gives
    no reading and no error."""
    assert _read(_ctx(tmp_path, "fit_small")) == dict.fromkeys(READERS)
