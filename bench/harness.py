"""Runs one cell once: load, warm up, measure, check, report.

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  It names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the traffic names its driver
(``bench/drivers/<driver>.py``), and the numbers its check compares are
held to ``bench/limits/<cell>.json``.  A per-layer metric is read by
``bench/metrics/<metric>.py``.  Everything is found by name, so a new
cell, configuration, traffic mix or metric is new files plus entries in
``BENCHMARK.json``.

A driver module provides:

* ``setup(run) -> state``: inputs from the seed, the program's objects,
  warm-up of every shape the window uses;
* ``window(run, state) -> dict``: measures for ``run.seconds``; returns
  the end-to-end readings it took (``fit_rows_per_s``, ...) plus
  ``attempted``, ``failed`` and ``window_s``;
* ``check(run, state) -> dict``: once the window has closed and the
  program's state is freed, the numbers compared with the plain
  reference, ``{name: value}``, and ``"missing"`` answers;
* ``control(run) -> dict``: the same numbers for the plain reference put
  in the program's place at the precision below the configuration's
  (``bench/control.py``; the benchmark's runs never call it).

A metric reader provides ``read(ctx) -> float | None``; ``None`` means
it found nothing to read, and the metric is left out of the line.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


class BenchError(RuntimeError):
    """The run cannot report: no chip, an unknown cell, a missing file."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BenchError(f"missing file {path}") from e


def load_module(path: str):
    """Import a file of the benchmark by path (names may hold dots)."""
    if not os.path.isfile(path):
        raise BenchError(f"missing module {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path).replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str


def _for_cell(metrics: List[dict], cell: str, e2e_names: set) -> List[dict]:
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif "moves" not in m or m["moves"] in e2e_names:
            out.append(m)
    return out


def load_cell(root: str, workload: str, bench_dir: Optional[str] = None) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``; its files live
    under ``bench_dir`` (default: the ``bench/`` beside this module)."""
    bench_dir = bench_dir or HERE
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    e2e = _for_cell(spec["end_to_end"], workload, set())
    names = {m["name"] for m in e2e}
    per_layer = _for_cell(spec["per_layer"], workload, names)
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_json(os.path.join(bench_dir, "configs", w["config"] + ".json")),
        traffic=load_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")),
        limits=load_json(os.path.join(bench_dir, "limits", workload + ".json")),
        end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


@dataclasses.dataclass
class Run:
    """What a driver and the metric readers see of one run."""

    root: str
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    out_dir: str
    records: Dict[str, Any] = dataclasses.field(default_factory=dict)
    t_start: float = 0.0     # the process's start, time.perf_counter clock


@dataclasses.dataclass
class ReadContext:
    """What a per-layer metric reader gets."""

    run: Run
    window_s: float
    window: tuple            # (start, end) on the trace's clock, seconds
    window_epoch: tuple      # (start, end) on the epoch clock of RCCA_TRACE
    devtrace: Any            # bench/devtrace.Trace, or None
    spans: List[dict]        # the program's RCCA_TRACE records in the window
    compile_s: float         # tracing + lowering + compiling inside the window
    peaks: dict

    @property
    def records(self) -> dict:
        return self.run.records

    @property
    def config(self) -> dict:
        return self.run.cell.config


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, by interval."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.intervals: List[tuple] = []

    def install(self) -> None:
        import jax

        def listen(event, duration, **_):
            if event in self.EVENTS:
                end = time.perf_counter()
                self.intervals.append((end - duration, end))

        jax.monitoring.register_event_duration_secs_listener(listen)

    def seconds_within(self, lo: float, hi: float) -> float:
        return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in self.intervals)


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the environment says, with every program kept."""
    import jax

    path = os.path.join(root, ".bench_cache", "jax")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_chip(chips: int) -> dict:
    """The devices, or BenchError when they are no TPU or too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices; "
                         "the benchmark measures only on the chip")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def import_program(root: str) -> None:
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"no program under {src}: run from a checkout of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


def _read_spans(path: str, lo_epoch: float, hi_epoch: float) -> List[dict]:
    out = []
    for fp in sorted(glob.glob(os.path.join(path, "*.jsonl"))):
        with open(fp, errors="replace") as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                t = rec.get("t", 0.0)
                if lo_epoch <= t <= hi_epoch:
                    out.append(rec)
    return out


def _finite(v):
    """A number as JSON carries it: NaN and infinities become null."""
    return float(v) if v is not None and math.isfinite(v) else None


def checks_line(limits: dict, numbers: dict) -> tuple:
    """``(all within limits, {name: {"value", "limit"}})``; a number with
    no limit, a limit with no number, and a number that is not finite
    fail."""
    out, ok = {}, True
    for name, limit in limits["limits"].items():
        value = _finite(numbers.get(name))
        out[name] = {"value": value, "limit": limit}
        if value is None or not value <= limit:
            ok = False
    for name, value in numbers.items():
        if name not in out:
            out[name] = {"value": _finite(value), "limit": None}
            ok = False
    return ok, out


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench_dir: Optional[str] = None,
             log: Callable[[str], None] = lambda s: print(s, file=sys.stderr, flush=True)
             ) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``t_start`` is the process's start on the ``time.perf_counter``
    clock.
    """
    cell = load_cell(root, workload, bench_dir)
    import jax

    t_import = time.perf_counter() - t_start
    use_compile_cache(root)
    device = find_chip(cell.chips)
    log(f"jax imported at {t_import:.3f} s, devices at {time.perf_counter() - t_start:.3f} s")
    from peaks import UnknownDevice, peaks_for  # noqa: E402  (bench/ on sys.path)

    try:
        peaks = peaks_for(device["kind"])
    except UnknownDevice as e:
        raise BenchError(str(e)) from e
    import_program(root)
    clock = CompileClock()
    clock.install()

    out_dir = os.path.join(root, ".bench_out", workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    run = Run(root=root, cell=cell, seed=seed, seconds=seconds, trace=trace,
              out_dir=out_dir, t_start=t_start)
    driver = load_module(os.path.join(cell.bench_dir, "drivers",
                                      cell.traffic["driver"] + ".py"))
    log(f"set-up: {cell.traffic['driver']} driver, {workload}, seed {seed}, "
        f"chip ready at {time.perf_counter() - t_start:.3f} s")
    state = driver.setup(run)
    # Set-up's objects (traces, lowered modules) leave the collector's
    # reach, so that its passes in the window do not grow with them.
    gc.collect()
    gc.freeze()
    log(f"set-up done at {time.perf_counter() - t_start:.3f} s")

    rcca_dir = os.path.join(out_dir, "rcca_trace")
    prof_dir = os.path.join(out_dir, "profile")
    if trace:
        os.environ["RCCA_TRACE"] = rcca_dir
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
    t0_epoch = time.time()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            measured = driver.window(run, state)
    finally:
        if trace:
            jax.profiler.stop_trace()
            os.environ.pop("RCCA_TRACE", None)
    window_s = measured["window_s"]
    peak = memory_peak_bytes()
    log(f"window {window_s:.3f} s, setup {setup_s:.3f} s, peak {peak} B, "
        f"compiling in the window {clock.seconds_within(t0, t0 + window_s):.3f} s")

    numbers = driver.check(run, state)
    del state
    missing = numbers.pop("missing", 0)
    ok, checks = checks_line(cell.limits, numbers)
    correct = bool(ok and not missing and measured["attempted"] > 0)

    e2e = dict(measured, setup_s=setup_s)
    device = dict(device, memory_peak_bytes=peak)
    line: Dict[str, Any] = {"correct": correct, "attempted": measured["attempted"],
                            "failed": measured["failed"] + missing}
    if not trace:
        line["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                           for m in cell.end_to_end if m["name"] in e2e}
    else:
        import devtrace

        tr = devtrace.load(prof_dir)
        lo, hi = tr.window("bench.window")
        spans = _read_spans(rcca_dir, t0_epoch, t0_epoch + window_s + 1.0)
        ctx = ReadContext(run=run, window_s=window_s, window=(lo, hi),
                          window_epoch=(t0_epoch, t0_epoch + window_s), devtrace=tr,
                          spans=spans, compile_s=clock.seconds_within(t0, t0 + window_s),
                          peaks=peaks)
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(os.path.join(cell.bench_dir, "metrics", m["name"] + ".py"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        device["busy_s"] = tr.busy_seconds(lo, hi)
        device["window_s"] = hi - lo
        line["breakdown"] = tr.breakdown(lo, hi, spans_epoch=spans)
    line["device"] = device
    line["checks"] = checks
    log(f"correct {correct} attempted {line['attempted']} failed {line['failed']}")
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return line
