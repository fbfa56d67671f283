"""Whole fits as the ``fit`` driver runs them, each bounded in time.

A fit whose device program never returns (the TPU's ``svd`` of a NaN
matrix does not) cannot be interrupted from Python: the process would
sit in the first read of rho until something outside kills it.  This
driver delegates ``setup``, ``window``, ``check`` and ``control`` to
``fit.py`` and puts a watchdog thread over what waits on the device:

* after ``fit.setup``, one more 2-chunk fit (as the set-up warm-up
  runs), whose rho is read back within ``WARMUP_READ_S``; a rho that
  is not finite fails the run;
* each fit of the window, rho read included, within ``WINDOW_FIT_S``.

When a bound passes, the thread prints ``bench: <what> did not return
in <s> s`` on standard error and ends the process with exit code 3.  A
guarded call that raises leaves its bound armed, so a process whose
teardown waits on a stuck device ends too.  ``faulthandler`` backs the
thread up ``BACKSTOP_S`` later, for a wait that holds the interpreter
lock: it prints every thread's stack and exits with code 1.
"""

from __future__ import annotations

import contextlib
import faulthandler
import math
import os
import sys
import threading
import time

import numpy as np

import harness

fit = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "fit.py"))

# Bounds, each at least 5x the longest honest time read on the chip
# (TPU v5 lite, europarl_fit: d 2^18, k~ 970, 16 chunks of 512 rows;
# 2 merge groups; 10 runs).  The warm-up fit and its rho read took
# 2.13-2.27 s, a whole fit of the window 16.3-17.9 s.
WARMUP_READ_S = 60.0
WINDOW_FIT_S = 120.0
BACKSTOP_S = 60.0


def _expire(what: str, seconds: float) -> None:
    sys.stderr.write(f"bench: {what} did not return in {seconds:g} s\n")
    sys.stderr.flush()
    os._exit(3)


@contextlib.contextmanager
def deadline(what: str, seconds: float):
    """End the process if the block does not finish within ``seconds``;
    the window's closing ``StopWindow`` counts as finishing."""
    timer = threading.Timer(seconds, _expire, (what, seconds))
    timer.daemon = True
    timer.start()
    faulthandler.dump_traceback_later(seconds + BACKSTOP_S, exit=True)
    try:
        yield
    except fit.StopWindow:
        _disarm(timer)
        raise
    _disarm(timer)


def _disarm(timer) -> None:
    timer.cancel()
    faulthandler.cancel_dump_traceback_later()


def _guarded(fit_fn):
    def guarded_fit(win, *args, **kwargs):
        with deadline("a fit of the window", WINDOW_FIT_S):
            res = fit_fn(win, *args, **kwargs)
            np.asarray(res.rho)
        return res
    return guarded_fit


def setup(run) -> dict:
    st = fit.setup(run)
    t0 = time.perf_counter()
    with deadline("the warm-up fit", WARMUP_READ_S):
        res = st["fit"](fit._Window(2, run.cell.config["q"]), n=2, merge_group=1)
        rho = np.asarray(res.rho)
    print(f"warm-up fit read back in {time.perf_counter() - t0:.3f} s", file=sys.stderr)
    if not all(math.isfinite(float(r)) for r in rho.ravel()):
        raise harness.BenchError(f"the warm-up fit's rho is not finite: {rho.tolist()}")
    st["fit"] = _guarded(st["fit"])
    return st


def window(run, st) -> dict:
    return fit.window(run, st)


def check(run, st) -> dict:
    return fit.check(run, st)


def control(run) -> dict:
    return fit.control(run)
