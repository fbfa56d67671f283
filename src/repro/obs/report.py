"""Offline analysis of an ``RCCA_TRACE`` directory: timeline + roofline.

    python -m repro.obs report rcca_trace [--json out.json]

Reads every per-process ``trace-*.jsonl`` file and reconstructs:

* **timeline** — per process (coordinator / workers / driver), the
  top-level span tree with per-span self-time (duration minus child
  spans), so the wall-clock of a fit decomposes into named phases:
  pass > chunk / io_wait / gather / mesh_fold / publish / barrier /
  merge.
* **coverage** — the fraction of each process's traced window that
  falls inside top-level spans.  The acceptance bar for the
  instrumentation is ≥ 0.95: less means some phase of the fit runs
  outside any span and the profile is lying by omission.
* **roofline** — per-kernel cost-model totals (flops / bytes / calls,
  from the same :class:`~repro.kernels.plan.KernelPlan` geometry the
  launches use, via the ``kernel_cost`` counters) and their arithmetic
  intensity.  No rate: on an asynchronous device a span's host time is
  dispatch time, not kernel time; device time comes from a
  ``jax.profiler`` trace, where each span is an ``rcca.<name>``
  annotation.
* **io overlap** — per prefetch site, the fraction of read time hidden
  behind compute: ``(read_s - io_stall_s) / read_s`` from the ``io``
  counters the prefetcher emits on close.
* **host→device copies** — per ``form`` of the ``h2d`` spans
  (``hashed``: slots and signs; ``dense``: the rows), the copies made,
  the dense rows' bytes they stand for and the bytes actually copied.
* **merge share** — merge-tree seconds as a fraction of the
  coordinator's fit wall, the scaling number the cluster benchmarks
  track.
* **protocol** — RCCA2xx race-detector verdict over the mirrored
  ``proto`` records (one trace serves both the profiler and the
  checker).
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

from repro.obs.trace import load_events


def _spans_by_pid(events: List[dict]) -> Dict[int, List[dict]]:
    out: Dict[int, List[dict]] = {}
    for ev in events:
        if ev.get("ev") == "span":
            out.setdefault(int(ev.get("pid", 0)), []).append(ev)
    return out


def _self_times(spans: List[dict]) -> None:
    """Annotate each span dict with ``self`` = dur − Σ direct-child durs
    (clamped at 0 — children overlapping their parent's edges are a
    clock artifact, not negative work)."""
    child_sum: Dict[Any, float] = {}
    for sp in spans:
        if sp.get("parent") is not None:
            child_sum[sp["parent"]] = (child_sum.get(sp["parent"], 0.0)
                                       + float(sp.get("dur", 0.0)))
    for sp in spans:
        sp["self"] = max(0.0, float(sp.get("dur", 0.0))
                         - child_sum.get(sp.get("sid"), 0.0))


def _role(spans: List[dict]) -> str:
    for sp in spans:
        ctx = sp.get("ctx") or {}
        if "role" in ctx:
            return str(ctx["role"])
    return "proc"


def _coverage(spans: List[dict]) -> Dict[str, float]:
    """Top-level span seconds vs. the process's traced window."""
    t0 = min(float(sp["t"]) for sp in spans)
    t1 = max(float(sp["t"]) + float(sp.get("dur", 0.0)) for sp in spans)
    top = [sp for sp in spans if sp.get("parent") is None]
    covered = sum(float(sp.get("dur", 0.0)) for sp in top)
    window = max(t1 - t0, 1e-12)
    return {"window_s": window, "covered_s": covered,
            "fraction": min(1.0, covered / window)}


def analyze(path: str) -> Dict[str, Any]:
    """Full report dict for a trace file or directory."""
    events = load_events(path)
    by_pid = _spans_by_pid(events)
    report: Dict[str, Any] = {"trace": path, "n_events": len(events)}

    # -- timeline + coverage ------------------------------------------
    procs: Dict[str, Any] = {}
    trace_t0 = min((float(sp["t"]) for sps in by_pid.values() for sp in sps),
                   default=0.0)
    for pid, spans in sorted(by_pid.items()):
        _self_times(spans)
        phases: Dict[str, Dict[str, float]] = {}
        for sp in spans:
            ph = phases.setdefault(sp["name"], {"n": 0, "s": 0.0,
                                                "self_s": 0.0})
            ph["n"] += 1
            ph["s"] += float(sp.get("dur", 0.0))
            ph["self_s"] += float(sp["self"])
        top = [
            {"name": sp["name"], "t": round(float(sp["t"]) - trace_t0, 4),
             "dur": round(float(sp.get("dur", 0.0)), 4),
             "attrs": sp.get("attrs", {})}
            for sp in sorted((s for s in spans if s.get("parent") is None),
                             key=lambda s: float(s["t"]))
        ]
        procs[str(pid)] = {
            "role": _role(spans),
            "top_spans": top,
            "phases": {k: {"n": v["n"], "s": round(v["s"], 4),
                           "self_s": round(v["self_s"], 4)}
                       for k, v in sorted(phases.items())},
            "coverage": {k: round(v, 4) if isinstance(v, float) else v
                         for k, v in _coverage(spans).items()},
        }
    report["processes"] = procs
    fracs = [p["coverage"]["fraction"] for p in procs.values()]
    report["coverage"] = round(min(fracs), 4) if fracs else 0.0

    # -- roofline -----------------------------------------------------
    kernels: Dict[str, Dict[str, float]] = {}
    for ev in events:
        if ev.get("ev") == "ctr" and ev.get("name") == "kernel_cost":
            f = ev.get("fields", {})
            k = kernels.setdefault(str(f.get("kernel", "?")),
                                   {"calls": 0, "flops": 0, "bytes": 0})
            k["calls"] += int(f.get("calls", 0))
            k["flops"] += int(f.get("flops", 0))
            k["bytes"] += int(f.get("bytes", 0))
    report["roofline"] = {
        "kernels": {
            k: dict(v, intensity=round(v["flops"] / v["bytes"], 3)
                    if v["bytes"] else None)
            for k, v in sorted(kernels.items())
        },
    }

    # -- io overlap ---------------------------------------------------
    io: Dict[str, Dict[str, float]] = {}
    for ev in events:
        if ev.get("ev") == "ctr" and ev.get("name") == "io":
            f = ev.get("fields", {})
            s = io.setdefault(str(f.get("site", "?")),
                              {"chunks": 0, "bytes": 0,
                               "read_s": 0.0, "io_stall_s": 0.0})
            s["chunks"] += int(f.get("chunks", 0))
            s["bytes"] += int(f.get("bytes", 0))
            s["read_s"] += float(f.get("read_s", 0.0))
            s["io_stall_s"] += float(f.get("io_stall_s", 0.0))
    report["io"] = {
        site: dict(v, read_s=round(v["read_s"], 4),
                   io_stall_s=round(v["io_stall_s"], 4),
                   overlap=round((v["read_s"] - v["io_stall_s"])
                                 / v["read_s"], 4) if v["read_s"] else None)
        for site, v in sorted(io.items())
    }

    # -- host→device copies -------------------------------------------
    h2d: Dict[str, Dict[str, int]] = {}
    for spans in by_pid.values():
        for sp in spans:
            if sp["name"] == "h2d":
                a = sp.get("attrs", {})
                c = h2d.setdefault(str(a.get("form", "dense")),
                                   {"copies": 0, "bytes": 0, "wire_bytes": 0})
                c["copies"] += 1
                c["bytes"] += int(a.get("bytes", 0))
                c["wire_bytes"] += int(a.get("wire_bytes", a.get("bytes", 0)))
    report["h2d"] = dict(sorted(h2d.items()))

    # -- merge share --------------------------------------------------
    merge_s = fit_s = 0.0
    for spans in by_pid.values():
        for sp in spans:
            # a single-process fit's own merges (site stream / mesh) are
            # not the coordinator's tree
            if sp["name"] == "merge" and (sp.get("attrs", {}).get("site")
                                          not in ("stream", "mesh")):
                merge_s += float(sp.get("dur", 0.0))
            elif sp["name"] == "fit" and (sp.get("attrs", {}).get("site")
                                          == "coordinator"):
                fit_s += float(sp.get("dur", 0.0))
    report["merge"] = {"merge_s": round(merge_s, 4),
                       "fit_s": round(fit_s, 4),
                       "share": round(merge_s / fit_s, 4) if fit_s else None}

    # -- worker liveness ----------------------------------------------
    # heartbeat-age samples the coordinator's barrier loop emits (~1 Hz
    # per live worker): per-shard max/last age sits next to the compute
    # spans, so a stale-but-alive worker is visible in the same report
    # that shows where the time went
    beats: Dict[int, Dict[str, Any]] = {}
    for ev in events:
        if ev.get("ev") == "ctr" and ev.get("name") == "heartbeat":
            f = ev.get("fields", {})
            s = beats.setdefault(int(f.get("shard", -1)),
                                 {"samples": 0, "max_age_s": 0.0,
                                  "last_age_s": 0.0, "passes": set()})
            age = float(f.get("age_s", 0.0))
            s["samples"] += 1
            s["max_age_s"] = max(s["max_age_s"], age)
            s["last_age_s"] = age
            s["passes"].add(int(f.get("pass_idx", -1)))
    report["liveness"] = {
        str(shard): {"samples": v["samples"],
                     "max_age_s": round(v["max_age_s"], 3),
                     "last_age_s": round(v["last_age_s"], 3),
                     "passes": sorted(v["passes"])}
        for shard, v in sorted(beats.items())
    }

    # -- redispatches + protocol verdict ------------------------------
    report["redispatches"] = sum(
        int(ev.get("fields", {}).get("groups", 0)) for ev in events
        if ev.get("ev") == "ctr" and ev.get("name") == "redispatch")
    proto = [ev for ev in events if ev.get("ev") == "proto"]
    if proto:
        from repro.analysis.protocol import check_trace
        # per-process trace files concatenate in filename order; the
        # wall timestamp recovers the cross-process serialization the
        # invariants are stated over (the single-file
        # RCCA_PROTOCOL_TRACE stream stays the canonical witness)
        proto.sort(key=lambda ev: float(ev.get("t", 0.0)))
        violations = check_trace(proto, where=path)
        report["protocol"] = {"events": len(proto),
                              "violations": [str(v) for v in violations]}
    return report


def render(report: Dict[str, Any]) -> str:
    """Human-readable multi-section text of an :func:`analyze` dict."""
    out: List[str] = []
    out.append(f"trace: {report['trace']}  ({report['n_events']} events, "
               f"{len(report['processes'])} processes)")
    out.append("")
    out.append("timeline")
    for pid, proc in report["processes"].items():
        cov = proc["coverage"]
        out.append(f"  [{proc['role']} pid={pid}]  window "
                   f"{cov['window_s']:.3f}s, coverage {cov['fraction']:.1%}")
        for sp in proc["top_spans"]:
            attrs = sp["attrs"]
            tag = " ".join(f"{k}={attrs[k]}" for k in sorted(attrs)
                           if k in ("site", "pass_idx", "kind", "engine",
                                    "schedule"))
            out.append(f"    +{sp['t']:8.3f}s  {sp['name']:<12} "
                       f"{sp['dur']:8.3f}s  {tag}")
        for name, ph in proc["phases"].items():
            out.append(f"      {name:<12} n={ph['n']:<5d} "
                       f"sum={ph['s']:9.3f}s  self={ph['self_s']:9.3f}s")
    out.append("")
    out.append(f"span coverage (min over processes): "
               f"{report['coverage']:.1%}")
    out.append("")
    out.append("roofline — cost-model kernel totals")
    out.append(f"  {'kernel':<20} {'calls':>7} {'flops':>14} {'bytes':>14} "
               f"{'flops/byte':>10}")
    for k, v in report["roofline"]["kernels"].items():
        inten = f"{v['intensity']:.2f}" if v["intensity"] else "-"
        out.append(f"  {k:<20} {v['calls']:>7d} {v['flops']:>14d} "
                   f"{v['bytes']:>14d} {inten:>10}")
    out.append("")
    out.append("io overlap")
    for site, v in report["io"].items():
        ov = f"{v['overlap']:.1%}" if v["overlap"] is not None else "-"
        out.append(f"  {site:<14} chunks={v['chunks']:<6d} "
                   f"read={v['read_s']:.3f}s stall={v['io_stall_s']:.3f}s "
                   f"overlap={ov}")
    out.append("")
    out.append("host→device copies")
    for form, v in report["h2d"].items():
        out.append(f"  {form:<14} copies={v['copies']:<6d} "
                   f"bytes={v['bytes']:d} wire_bytes={v['wire_bytes']:d}")
    m = report["merge"]
    share = f"{m['share']:.1%}" if m["share"] is not None else "-"
    out.append("")
    out.append(f"merge tree: {m['merge_s']:.3f}s of {m['fit_s']:.3f}s "
               f"coordinator fit wall ({share})")
    if report.get("liveness"):
        out.append("")
        out.append("worker liveness (heartbeat ages seen at the barrier)")
        for shard, v in report["liveness"].items():
            passes = ",".join(str(p) for p in v["passes"])
            out.append(f"  shard {shard:>3}  samples={v['samples']:<5d} "
                       f"max_age={v['max_age_s']:.3f}s "
                       f"last_age={v['last_age_s']:.3f}s  passes=[{passes}]")
    if report["redispatches"]:
        out.append(f"redispatched groups: {report['redispatches']}")
    if "protocol" in report:
        p = report["protocol"]
        verdict = "OK" if not p["violations"] else "VIOLATIONS"
        out.append(f"protocol: {p['events']} events -> {verdict}")
        for v in p["violations"]:
            out.append(f"  {v}")
    return "\n".join(out)


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs report", description=__doc__)
    ap.add_argument("trace", help="trace file or directory (RCCA_TRACE dir)")
    ap.add_argument("--json", default=None,
                    help="also write the full report dict to this path")
    args = ap.parse_args(argv)
    report = analyze(args.trace)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
    print(render(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
