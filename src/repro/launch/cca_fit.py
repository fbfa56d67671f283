"""The paper's end-to-end driver: distributed RandomizedCCA fit.

    PYTHONPATH=src python -m repro.launch.cca_fit --smoke --ckpt-dir /tmp/cca

Streams a (synthetic-Europarl) paired-view corpus through Algorithm 1's
q+1 data passes.  Two execution modes:

- ``--mode dist``: all rows resident, shard_map over the host mesh —
  the production mode whose production-mesh lowering the dry-run checks;
- ``--mode stream``: out-of-core iterator with per-chunk jitted updates
  and mid-pass CHECKPOINTING (kill/resume fault tolerance for passes
  over data too large for memory).

Data source: synthetic generation by default, or an on-disk view store
(``repro.store``) via ``--data <store-path>`` — ``--ingest`` writes the
synthetic corpus there first.  Store-backed stream mode runs the async
prefetching PassRunner (``--prefetch`` depth, 0 = synchronous reads,
``auto`` = calibrated) and resumes a killed run from its pass cursor
with ``--resume``.  ``--workers N`` instead fans the store-backed fit
out over N worker PROCESSES through the ``repro.cluster`` coordinator
(``--cluster-dir`` for the shared coordination directory) — the result
is bit-identical to the single-process stream mode:

    python -m repro.launch.cca_fit --smoke --mode stream \
        --data /tmp/store --ingest --ckpt-dir /tmp/cca
    # kill it mid-pass, then:
    python -m repro.launch.cca_fit --smoke --mode stream \
        --data /tmp/store --ckpt-dir /tmp/cca --resume

``--topology {local,sharded,cluster,hybrid}`` is the unified spelling
of the execution layout (repro.exec): ``sharded`` folds merge groups
one-per-device over the local mesh, ``hybrid`` = cluster workers ×
per-worker device meshes (``--devices-per-worker``).  Every topology
is bit-identical on the same store.

Reports the paper's metrics: Σ canonical correlations (train objective),
feasibility residuals, and — at smoke scale — agreement with the exact
dense CCA oracle.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs.europarl_cca import config as europarl_config
from repro.configs.europarl_cca import smoke_config as europarl_smoke
from repro.core import exact_cca, feasibility_errors
from repro.core.rcca import DEFAULT_ENGINE, randomized_cca_iterator
from repro.core.rcca_dist import dist_randomized_cca
from repro.data import PlantedCCAData
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh


def main(argv=None) -> dict:
    """Run the fit; return what it reported (``sum_rho``, and where the
    corpus fits the evaluation budget ``feasibility`` and, at smoke
    scale, ``oracle_gap``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", default="dist", choices=["dist", "stream"])
    ap.add_argument("--engine", default=DEFAULT_ENGINE, choices=["kernels", "jnp"],
                    help="data-pass engine: fused Pallas kernels (default; "
                         "interpret-mode off-TPU) or the pure-jnp oracle path")
    ap.add_argument("--omega", default="materialized",
                    choices=["materialized", "seeded", "seeded-materialized"],
                    help="Gaussian-sketch provenance: 'seeded' runs the "
                         "first data pass from an 8-byte counter-PRNG seed "
                         "(kernels engine generates Omega tiles in-kernel; "
                         "cluster rounds ship the seed, not the (d, k~) "
                         "bases); 'seeded-materialized' materializes the "
                         "same tile-PRNG Omega up front — the bitwise "
                         "oracle of the seeded path")
    ap.add_argument("--autotune", action="store_true",
                    help="before fitting, sweep the fused powerpass/projgram "
                         "block+bucket sizes for this workload's chunk shape "
                         "and persist them to the autotune cache (run once "
                         "per shape on the target hardware)")
    ap.add_argument("--k", type=int, default=None)
    ap.add_argument("--p", type=int, default=None)
    ap.add_argument("--q", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", default=None, metavar="STORE",
                    help="path to an on-disk view store (repro.store); "
                         "stream mode prefetches from it, dist mode "
                         "materializes it onto the mesh")
    ap.add_argument("--ingest", action="store_true",
                    help="write the synthetic workload corpus into --data "
                         "first (chunked — never materializes n × d)")
    ap.add_argument("--prefetch", default="2",
                    help="store prefetch pipeline depth (0 = synchronous, "
                         "'auto' = calibrate from the read/compute ratio)")
    ap.add_argument("--resume", action="store_true",
                    help="resume a killed store-backed run from the latest "
                         "pass cursor in --ckpt-dir")
    ap.add_argument("--workers", type=int, default=0,
                    help="run the store-backed fit across N worker "
                         "PROCESSES via the repro.cluster coordinator "
                         "(requires --data; bit-identical to the "
                         "single-process stream mode)")
    ap.add_argument("--cluster-dir", default=None,
                    help="shared coordination directory for --workers "
                         "(rounds/partials/cursors/heartbeats/logs; "
                         "default <store>.cluster)")
    ap.add_argument("--topology", default=None,
                    choices=["local", "sharded", "cluster", "hybrid"],
                    help="execution topology (repro.exec): local = "
                         "sequential stream, sharded = merge groups "
                         "one-per-device over the local mesh, cluster = "
                         "worker processes, hybrid = worker processes x "
                         "per-worker device meshes.  All topologies are "
                         "bit-identical on the same store (sharded/"
                         "cluster/hybrid need --data)")
    ap.add_argument("--devices-per-worker", type=int, default=4,
                    help="local devices each hybrid worker folds merge "
                         "groups over (spawned with the forced-host-"
                         "device XLA flag: a CPU rehearsal; cluster and "
                         "hybrid refuse TPU hosts)")
    ap.add_argument("--trace", default=None, metavar="DIR", nargs="?",
                    const="1",
                    help="record a repro.obs trace of the fit (spans + "
                         "roofline counters for every pass, worker, and "
                         "kernel; propagates to cluster workers) and "
                         "print the timeline/roofline report afterwards. "
                         "Optional DIR names the trace directory "
                         "(default rcca_trace/)")
    args = ap.parse_args(argv)
    args.prefetch = args.prefetch if args.prefetch == "auto" else int(args.prefetch)
    use_compile_cache()

    if args.trace:
        import os

        from repro import obs
        os.environ[obs.TRACE_ENV] = args.trace  # inherited by workers
        print(f"[cca] tracing -> {obs.trace_dir()}/ "
              "(timeline report after the fit)")

    wl = europarl_smoke() if args.smoke else europarl_config()
    rcca = wl.rcca
    if args.k is not None:
        import dataclasses
        rcca = dataclasses.replace(rcca, k=args.k)
    if args.p is not None:
        import dataclasses
        rcca = dataclasses.replace(rcca, p=args.p)
    if args.q is not None:
        import dataclasses
        rcca = dataclasses.replace(rcca, q=args.q)

    data = PlantedCCAData(n=wl.n, da=wl.da, db=wl.db, chunk=wl.chunk,
                          rank=max(rcca.k * 2, 16), seed=args.seed)
    key = jax.random.PRNGKey(args.seed)

    if args.topology is None and args.workers:
        args.topology = "cluster"
    if args.topology == "local":
        args.mode = "stream"  # Local IS the sequential streaming topology
    if args.topology in ("sharded", "cluster", "hybrid") and not args.data:
        raise SystemExit(f"--topology {args.topology} needs an on-disk "
                         "store: pass --data (these topologies cut a "
                         "view store into merge groups)")
    if args.workers and not args.data:
        raise SystemExit("--workers needs an on-disk store: pass --data "
                         "(the cluster coordinator shards a view store)")

    reader = None
    if args.data:
        from repro.store import ViewStoreReader, ingest_planted, store_exists

        if args.ingest or not store_exists(args.data):
            t_ing = time.time()
            reader = ingest_planted(args.data, data)
            print(f"[cca] ingested {reader.n} rows "
                  f"({reader.nbytes / 1e6:.1f} MB, {len(reader.shards)} shards) "
                  f"→ {args.data} in {time.time() - t_ing:.1f}s")
        else:
            reader = ViewStoreReader(args.data)
            print(f"[cca] view store {args.data}: n={reader.n} "
                  f"da={reader.da} db={reader.db} chunk={reader.chunk} "
                  f"({reader.nbytes / 1e6:.1f} MB on disk)")
        if (reader.n, reader.da, reader.db) != (wl.n, wl.da, wl.db):
            print(f"[cca] store geometry overrides workload: "
                  f"n={reader.n} da={reader.da} db={reader.db}")

    if args.autotune and args.engine == "kernels":
        # Sweep the chunk-shaped fused ops so the data passes pick up
        # tuned bucket sizes (caps bind at trace time — sweep BEFORE
        # the first pass compiles).  Zeros suffice: block timing is
        # data-independent.
        from repro.kernels import autotune as kernel_autotune
        c = min(wl.chunk, wl.n)
        kt = rcca.sketch
        a0 = jnp.zeros((c, wl.da), jnp.float32)
        b0 = jnp.zeros((c, wl.db), jnp.float32)
        qa0 = jnp.zeros((wl.da, kt), jnp.float32)
        qb0 = jnp.zeros((wl.db, kt), jnp.float32)
        # both view directions: the power pass calls (a,b,Qb) AND
        # (b,a,Qa), the final pass projgrams each view — asymmetric
        # da/db means four distinct cache keys
        pp = kernel_autotune.autotune_powerpass(a0, b0, qb0)
        pg = kernel_autotune.autotune_projgram(a0, qa0)
        if wl.da != wl.db:
            pp_b = kernel_autotune.autotune_powerpass(b0, a0, qa0)
            pg_b = kernel_autotune.autotune_projgram(b0, qb0)
        else:
            pp_b, pg_b = pp, pg  # same cache keys — one sweep covers both
        print(f"[cca] autotuned chunk ({c}, da={wl.da}, db={wl.db}, k~={kt}): "
              f"powerpass blocks a={pp} b={pp_b}, "
              f"projgram blocks a={pg} b={pg_b} "
              f"(cache: {kernel_autotune.cache_path()})")
        del a0, b0, qa0, qb0

    t0 = time.time()
    if args.topology in ("cluster", "hybrid"):
        from repro.cluster import ClusterCoordinator

        n_workers = args.workers or 2
        devices = args.devices_per_worker if args.topology == "hybrid" else 1
        cluster_dir = args.cluster_dir or args.data.rstrip("/") + ".cluster"
        if args.prefetch == "auto":
            print("[cca] --prefetch auto is per-process calibration; "
                  "cluster workers use a fixed depth 2 instead")
        coord = ClusterCoordinator(
            reader, rcca, cluster_dir, n_workers=n_workers,
            devices_per_worker=devices, engine=args.engine,
            omega=args.omega,
            prefetch=args.prefetch if args.prefetch != "auto" else 2)
        print(f"[cca] {args.topology} mode, engine={args.engine}, "
              f"omega={args.omega}, "
              f"workers={n_workers}x{devices}dev, groups={coord.n_groups}, "
              f"cluster_dir={cluster_dir}")
        res = coord.fit(key)
        print("[cca] cluster:", res.diagnostics["cluster"])
        A = B = None
        if reader.nbytes <= 2 << 30:
            A, B = reader.materialize()
    elif args.topology == "sharded":
        from repro.exec import PassEngine, Sharded

        eng = PassEngine(rcca, engine=args.engine, topology=Sharded(),
                         omega=args.omega)
        mesh = eng.topology.build_mesh()
        print(f"[cca] sharded mode, engine={args.engine}, omega={args.omega}, "
              f"devices={mesh.devices.size}, n={reader.n} "
              f"chunks={reader.n_chunks} (force more CPU devices with "
              f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        res = eng.run_mesh(reader, key)
        print("[cca] topology:", res.diagnostics["topology"])
        A = B = None
        if reader.nbytes <= 2 << 30:
            A, B = reader.materialize()
    elif args.mode == "dist":
        if args.omega != "materialized":
            # the resident-mode shard_map driver has no streaming pass
            # to de-materialize — Ω lives on the mesh either way
            print(f"[cca] --omega {args.omega} only affects the streaming "
                  "topologies; dist mode keeps the materialized sketch")
        A, B = reader.materialize() if reader is not None else data.materialize()
        mesh = make_host_mesh()
        print(f"[cca] dist mode, engine={args.engine}, "
              f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}, "
              f"n={wl.n} da={wl.da} db={wl.db} k={rcca.k} p={rcca.p} q={rcca.q}")
        res = dist_randomized_cca(jnp.asarray(A), jnp.asarray(B), rcca, key, mesh,
                                  engine=args.engine)
    elif reader is not None:
        from repro.store import PassRunner

        runner = PassRunner(reader, rcca, engine=args.engine,
                            prefetch=args.prefetch, ckpt_dir=args.ckpt_dir,
                            omega=args.omega)
        print(f"[cca] stream mode (store-backed), engine={args.engine}, "
              f"omega={args.omega}, prefetch={args.prefetch}, "
              f"n={reader.n} chunks={reader.n_chunks}")
        res = runner.fit(key, resume=args.resume)
        print("[cca] io:", res.diagnostics["io"])
        # evaluation materializes — only do it for corpora that fit
        A = B = None
        if reader.nbytes <= 2 << 30:
            A, B = reader.materialize()
    else:
        mgr = CheckpointManager(args.ckpt_dir, keep=2) if args.ckpt_dir else None
        state = {"count": 0}

        def on_chunk(pass_idx, chunk_idx, acc, Qa, Qb):
            state["count"] += 1
            if mgr and state["count"] % 16 == 0:
                mgr.save(
                    pass_idx * 10_000 + chunk_idx,
                    {"acc": acc.state(), "Qa": Qa, "Qb": Qb},
                    metadata={"pass_idx": pass_idx, "chunk_idx": chunk_idx},
                )

        print(f"[cca] stream mode, engine={args.engine}, omega={args.omega}, "
              f"n={wl.n} chunks={data.n_chunks}")
        res = randomized_cca_iterator(
            lambda: iter(data), wl.da, wl.db, rcca, key, on_pass_end=on_chunk,
            engine=args.engine, omega=args.omega,
        )
        A, B = data.materialize()  # for evaluation only

    dt = time.time() - t0
    rho = np.asarray(res.rho)
    print(f"[cca] done in {dt:.1f}s; sum rho = {rho.sum():.4f}; top-5 rho = {rho[:5]}")

    if args.trace:
        from repro import obs
        from repro.obs import report as obs_report
        print(obs_report.render(obs_report.analyze(obs.trace_dir())))

    report = {"sum_rho": float(rho.sum())}
    if A is None:
        print("[cca] corpus larger than the eval budget — skipping "
              "materialized feasibility/oracle checks")
        return report

    lam_a = float(res.diagnostics["lam_a"])
    lam_b = float(res.diagnostics["lam_b"])
    feas = feasibility_errors(jnp.asarray(A), jnp.asarray(B),
                              jnp.asarray(res.Xa), jnp.asarray(res.Xb), lam_a, lam_b)
    report["feasibility"] = {k: float(v) for k, v in feas.items()}
    print("[cca] feasibility:", report["feasibility"])

    if args.smoke:
        ex = exact_cca(jnp.asarray(A), jnp.asarray(B), rcca.k, lam_a, lam_b)
        gap = float(np.sum(np.asarray(ex.rho)) - rho.sum())
        report["oracle_gap"] = gap
        print(f"[cca] exact-oracle objective gap: {gap:.5f} "
              f"(exact {float(np.sum(np.asarray(ex.rho))):.4f})")
    return report


if __name__ == "__main__":
    main()
