"""Serving-loop driver: fit → publish → serve → drift → delta-refit → swap.

    PYTHONPATH=src python -m repro.launch.cca_serve --smoke \
        --store /tmp/cca_store --registry /tmp/cca_registry

One process walks the whole production story of ``repro.serve``:

1. ingest the first tranche of a (synthetic) paired-view corpus into a
   view store and fit it with :func:`repro.exec.fit_with_state` — the
   fit that keeps its accumulator state for later delta-refits;
2. publish the model as **v1** of a :class:`repro.serve.ModelRegistry`
   entry (atomic, content-hashed) and persist the
   :class:`~repro.exec.FitState` next to it;
3. serve traffic through a :class:`repro.serve.BatchedProjector`
   (request coalescing, padded device batches) while a
   :class:`repro.serve.DriftMonitor` watches paired held-out rows;
4. inject a distribution shift (the held-out pairing breaks — the
   cheapest honest stand-in for an upstream pipeline change): the
   canonical correlation collapses and the monitor emits the
   refit-needed signal;
5. the signal triggers the incremental path: the second corpus tranche
   is APPENDED to the store (atomic manifest re-publish), and
   :func:`repro.exec.delta_refit` folds only the delta through pass 0
   (mode="exact": bitwise what a cold fit of the grown corpus computes);
6. publish **v2** and hot-swap the projector at a batch boundary —
   zero dropped requests — then re-baseline the monitor and show the
   held-out correlation recovered on healthy traffic.

Every stage traces through ``repro.obs`` (``--trace``), so the swap,
the batch occupancies and the drift counters land in the same timeline
as the fit's passes.
"""

from __future__ import annotations

import argparse
import itertools
import os
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from repro.data import PlantedCCAData
from repro.core.rcca import DEFAULT_ENGINE, RCCAConfig
from repro.exec import FitState, Local, Sharded, delta_refit, fit_with_state
from repro.launch.compile_cache import use_compile_cache
from repro.serve import (BatchedProjector, CorpusIndex, DriftMonitor,
                         ModelRegistry)
from repro.store import (ViewStoreReader, extend_chunks, ingest_chunks,
                         store_exists)


def _fitstate_dir(registry_root: str, name: str) -> str:
    return os.path.join(registry_root, name, "fitstate")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpus (seconds on CPU) — the demo scale")
    ap.add_argument("--store", required=True,
                    help="view store path (created/extended here)")
    ap.add_argument("--registry", required=True,
                    help="model registry root (repro.serve.ModelRegistry)")
    ap.add_argument("--name", default="europarl-cca",
                    help="registry model name")
    ap.add_argument("--engine", default=DEFAULT_ENGINE,
                    choices=["kernels", "jnp"])
    ap.add_argument("--omega", default="materialized",
                    choices=["materialized", "seeded",
                             "seeded-materialized"])
    ap.add_argument("--topology", default="local",
                    choices=["local", "sharded"],
                    help="fit/refit topology (delta-refit over cluster "
                         "partials is a ROADMAP residual)")
    ap.add_argument("--k", type=int, default=None)
    ap.add_argument("--q", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--window", type=int, default=192,
                    help="drift-monitor window (held-out rows)")
    ap.add_argument("--threshold", type=float, default=0.8,
                    help="refit signal fires below this fraction of the "
                         "baseline correlation")
    ap.add_argument("--clients", type=int, default=16,
                    help="concurrent request threads during the swap")
    ap.add_argument("--prune-keep", type=int, default=None, metavar="N",
                    help="after the final publish, garbage-collect old "
                         "registry versions keeping the newest N (the "
                         "current version and its rollback chain are "
                         "always kept)")
    ap.add_argument("--trace", default=None, metavar="DIR", nargs="?",
                    const="1",
                    help="record a repro.obs trace (spans for fit + "
                         "serve batches, drift/swap/occupancy counters)")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.trace:
        from repro import obs
        os.environ[obs.TRACE_ENV] = args.trace
        print(f"[serve] tracing -> {obs.trace_dir()}/")

    # -- corpus geometry: two tranches + held-out traffic -----------------
    # the first tranche must end on a merge-group boundary (the
    # incremental-fit alignment contract: repro.exec.delta)
    if args.smoke:
        chunk, merge_group = 128, 2
        n0, n1, n_traffic = 1024, 1536, 1024
        cfg = RCCAConfig(k=4, p=8, q=1, nu=0.01, center=True)
    else:
        chunk, merge_group = 1024, 8
        n0, n1, n_traffic = 65536, 98304, 8192
        cfg = RCCAConfig(k=16, p=16, q=1, nu=0.01, center=True)
    if args.k is not None:
        import dataclasses
        cfg = dataclasses.replace(cfg, k=args.k)
    if args.q is not None:
        import dataclasses
        cfg = dataclasses.replace(cfg, q=args.q)
    da, db = (28, 20) if args.smoke else (160, 120)
    data = PlantedCCAData(n=n1 + n_traffic, da=da, db=db,
                          rank=max(cfg.k * 2, 8), noise=0.4,
                          seed=11 + args.seed, chunk=chunk)
    c0, c1 = n0 // chunk, n1 // chunk
    topology = Local() if args.topology == "local" else Sharded()
    key = jax.random.PRNGKey(args.seed)
    reg = ModelRegistry(args.registry)

    # -- 1+2: first tranche → stateful fit → publish v1 -------------------
    if not store_exists(args.store):
        ingest_chunks(args.store,
                      (data.get_chunk(i) for i in range(c0)), chunk=chunk)
    reader = ViewStoreReader(args.store)
    print(f"[serve] store {args.store}: n={reader.n} da={reader.da} "
          f"db={reader.db} ({reader.n_chunks} chunks)")
    t0 = time.time()
    res, state = fit_with_state(reader, cfg, key, topology=topology,
                                engine=args.engine, omega=args.omega,
                                merge_group=merge_group)
    v1 = reg.publish(args.name, res, fit_meta=state.meta)
    state.save(_fitstate_dir(args.registry, args.name))
    print(f"[serve] fit tranche 1 in {time.time() - t0:.1f}s; "
          f"published {args.name} v{v1} "
          f"(sum rho = {float(np.sum(np.asarray(res.rho))):.4f})")

    # -- 3: serve + monitor -----------------------------------------------
    model = reg.load(args.name)
    proj = BatchedProjector(model, max_batch=32)
    monitor = DriftMonitor(model, window=args.window,
                           threshold=args.threshold)
    index = CorpusIndex.from_store(model, reader, view="b")

    # held-out traffic: rows past every corpus tranche, enough to fill
    # the drift window
    parts = [data.get_chunk(i) for i in
             range(c1, c1 + -(-args.window // chunk))]
    xa_t = np.concatenate([a for a, _ in parts])
    xb_t = np.concatenate([b for _, b in parts])
    for lo in range(0, args.window, 64):
        monitor.observe(xa_t[lo:lo + 64], xb_t[lo:lo + 64])
    print(f"[serve] baseline held-out correlation: "
          f"{monitor.baseline:.4f} (window={args.window})")
    r = proj.project_a(xa_t[0])
    hits, _ = index.topk(r["emb"], k=5)
    print(f"[serve] sample request: v{r['version']} "
          f"top-5 cross-view rows {hits.tolist()}")

    # -- 4: inject shift → drift signal -----------------------------------
    perm = np.random.default_rng(7).permutation(xb_t.shape[0])
    shifted = xb_t[perm]  # pairing broken: upstream pipeline "change"
    mean = None
    for lo in range(0, args.window, 64):
        mean = monitor.observe(xa_t[lo:lo + 64], shifted[lo:lo + 64]) or mean
    print(f"[serve] injected shift: correlation {mean:.4f} "
          f"-> refit_needed={monitor.refit_needed}")
    if not monitor.refit_needed:
        raise SystemExit("drift monitor failed to flag the injected shift")

    # -- 5: append tranche 2 + delta-refit --------------------------------
    t0 = time.time()
    extend_chunks(args.store, (data.get_chunk(i) for i in range(c0, c1)))
    reader = ViewStoreReader(args.store)
    state = FitState.load(_fitstate_dir(args.registry, args.name))
    res2, state2 = delta_refit(state, reader, mode="exact",
                               topology=topology)
    d = res2.diagnostics["delta"]
    print(f"[serve] delta-refit in {time.time() - t0:.1f}s: "
          f"+{reader.n - n0} rows, delta_chunks={d['delta_chunks']}, "
          f"refolded={d['refolded_chunks']} "
          f"(sum rho = {float(np.sum(np.asarray(res2.rho))):.4f})")

    # -- 6: publish v2 + hot-swap under live traffic ----------------------
    v2 = reg.publish(args.name, res2, fit_meta=state2.meta, parent=v1)
    state2.save(_fitstate_dir(args.registry, args.name))
    model2 = reg.load(args.name)

    def client(i: int) -> int:
        return proj.project_a(xa_t[i % xa_t.shape[0]])["version"]

    with ThreadPoolExecutor(max_workers=8) as pool:
        futs = [pool.submit(client, i) for i in range(args.clients)]
        proj.swap(model2)
        futs += [pool.submit(client, i) for i in range(args.clients)]
        served = [f.result() for f in futs]
    versions = sorted(set(served))
    stats = proj.stats()
    print(f"[serve] hot-swap v{v1}->v{v2}: {len(served)} responses across "
          f"the flip (versions seen: {versions}, dropped: 0); "
          f"batches={stats['batches']} "
          f"mean_occupancy={stats['mean_occupancy']:.1f} "
          f"swaps={stats['swaps']}")

    # -- recovery: healthy traffic under the refreshed model --------------
    monitor.rebind(model2)
    recovered = None
    for lo in range(0, args.window, 64):
        recovered = monitor.observe(
            xa_t[lo:lo + 64], xb_t[lo:lo + 64]) or recovered
    print(f"[serve] post-swap held-out correlation: {recovered:.4f} "
          f"(refit_needed={monitor.refit_needed})")
    proj.close()

    if args.prune_keep is not None:
        pruned = reg.prune(args.name, keep=args.prune_keep)
        print(f"[serve] pruned versions {pruned} (keep={args.prune_keep})")

    if args.trace:
        from repro import obs
        from repro.obs import report as obs_report
        print(obs_report.render(obs_report.analyze(obs.trace_dir())))
    print(f"[serve] registry {args.registry}: {args.name} versions "
          f"{reg.versions(args.name)}, current v{reg.current_version(args.name)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
