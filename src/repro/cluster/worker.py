"""One shard of one data pass — the cluster's map task.

    python -m repro.cluster.worker --store /data/corpus \
        --cluster-dir /data/cluster --shard 3 --n-shards 8 --pass-idx 0

Runnable under any external scheduler (the coordinator's subprocess
spawn is just one such scheduler): everything a worker needs beyond its
shard identity comes from the store manifest and the pass ROUND the
coordinator published (Qa/Qb bases, engine, merge-group size, binding
metadata).  Under ``omega="seeded"`` the pass-0 round's Qa/Qb slots
hold the per-view (2,)-uint32 Ω seeds instead of bases: the kernels
engine generates Ω tiles inside the fused kernels (never materializing
the ``(d, k̃)`` array), the jnp engine re-derives Ω locally — either
way the worker stays stateless and the broadcast is 8 bytes per view.  The worker streams its merge groups — strided whole-group
assignment via ``ViewStoreReader.row_shard(group=...)``, prefetched
through :class:`~repro.store.prefetch.ChunkPrefetcher` — folds each
group's chunks through the ONE canonical fold loop
(``repro.exec.run_fold`` feeding a sink-mode
``SegmentedAccumulator``), and atomically publishes one partial per
group.

With ``--devices N > 1`` the worker is a HYBRID worker: it builds a
1-D mesh over its local devices and folds whole merge groups
one-per-device under shard_map (``repro.exec.fold_groups_on_mesh``) —
each group's left-fold runs on a single device with the exact
per-chunk update arithmetic, so the published partials are bitwise
identical to the sequential worker's and the coordinator's tree merge
(and the final result) cannot tell the layouts apart.  On hosts
without accelerators the coordinator forces
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` into the
worker's environment, so the layout is exercisable anywhere.

Fault tolerance:

- a per-worker CURSOR (current group fold + next chunk) is checkpointed
  through ``repro.ckpt`` every ``ckpt_every`` chunks, so a killed
  worker re-run with the same shard id resumes MID-SHARD: published
  groups are skipped, the in-flight group continues from the cursor,
  and ``row_shard(start=...)`` seeks the store so the folded prefix is
  never re-read.  (Device-parallel workers publish whole groups and
  resume at group granularity — published groups are skipped, the rest
  are redone.)
- partials already published (by a previous incarnation or by a repair
  worker that took over this shard) are detected by their binding
  metadata and skipped — publishing is idempotent and merge-safe
  because partial content is a deterministic function of (store,
  round, group);
- a per-shard HEARTBEAT beacon is touched at start and at every
  merge-group boundary / cursor save; the coordinator re-dispatches
  shards whose beacon goes stale (a stuck-but-alive worker) without
  waiting for the wall-clock pass timeout.

``RCCA_CLUSTER_KILL_AT=<pass>:<chunk>`` simulates a hard crash right
after folding that chunk (tests/test_cluster_failures.py) — the CLI
dies with ``os._exit``, skipping every cleanup path, exactly like a
lost machine.  ``RCCA_CLUSTER_HANG_AT=<pass>:<chunk>`` instead wedges
the worker in a sleep loop at that chunk (heartbeat goes stale, the
process stays alive) — the stuck-worker case only heartbeats detect.
"""

from __future__ import annotations

import argparse
import os
import time
from typing import Optional, Sequence

import numpy as np

import jax

from repro import obs
from repro.ckpt import CheckpointManager
from repro.core.rcca import (jit_seeded_update_fn, jit_update_fn,
                             seeded_update_fn, stats_init_fn, update_fn)
from repro.exec import (SegmentedAccumulator, SpanCombiner,
                        fold_groups_on_mesh, n_full_chunks, run_fold)
from repro.launch.compile_cache import use_compile_cache
from repro.store import ViewStoreReader, prefetched, shard_chunks

from . import partials as pt

KILL_ENV = "RCCA_CLUSTER_KILL_AT"
HANG_ENV = "RCCA_CLUSTER_HANG_AT"


class WorkerKilled(RuntimeError):
    """Injected crash (see :data:`KILL_ENV`)."""


def _parse_injection(env: str, pass_idx: int) -> Optional[int]:
    spec = os.environ.get(env)
    if not spec:
        return None
    p, _, c = spec.partition(":")
    return int(c) if int(p) == pass_idx else None


def _cost_fn(kind: str, engine: str, kt: int, q_dtype, seeded: bool):
    if not obs.enabled():
        return None
    from repro.obs.cost import chunk_cost_fn

    return chunk_cost_fn(kind, engine, kt, q_dtype, seeded=seeded)


def _hang_forever(shard: int, chunk_idx: int) -> None:
    print(f"[worker {shard}] injected hang at chunk {chunk_idx}", flush=True)
    while True:  # stuck-but-alive: no beats, no exit
        time.sleep(0.5)


def run_worker(store: str, cluster_dir: str, shard: int, n_shards: int,
               pass_idx: int, *, groups: Optional[Sequence[int]] = None,
               prefetch: int = 2, ckpt_every: int = 4,
               round_wait_s: float = 30.0,
               kill_at_chunk: Optional[int] = None,
               hang_at_chunk: Optional[int] = None,
               devices: int = 1) -> int:
    """Process one shard of one pass; returns the number of partials
    this invocation published.  ``groups`` overrides the strided
    assignment (the coordinator's re-dispatch path); ``devices > 1``
    folds merge groups one-per-device over the local mesh (the Hybrid
    topology's worker side)."""
    reader = ViewStoreReader(store)
    Qa, Qb, meta = pt.read_round(cluster_dir, pass_idx, wait_s=round_wait_s)
    if meta["fingerprint"] != reader.fingerprint():
        raise ValueError(
            f"round for pass {pass_idx} was published against a different "
            f"store (fingerprint {meta['fingerprint'][:12]}… != "
            f"{reader.fingerprint()[:12]}…)")
    if kill_at_chunk is None:
        kill_at_chunk = _parse_injection(KILL_ENV, pass_idx)
    if hang_at_chunk is None:
        hang_at_chunk = _parse_injection(HANG_ENV, pass_idx)

    kind, engine = meta["kind"], meta["engine"]
    obs.set_context(fit_id=meta.get("fit_id"), role=f"worker{shard:03d}",
                    shard=shard)
    G = int(meta["merge_group"])
    n_chunks = reader.n_chunks
    n_groups = -(-n_chunks // G)
    # k̃ comes from the binding metadata, not the payload shape: a
    # seeded pass-0 round's Qa/Qb slots hold (2,)-uint32 seeds
    algo = meta["algo"]
    kt = int(algo["k"]) + int(algo["p"])
    q_dtype = np.dtype(algo["dtype"])
    seeds = meta.get("omega", "materialized") == "seeded" and pass_idx == 0
    if seeds and engine != "kernels":
        # jnp engine: re-derive Ω locally from the 8-byte seed (still
        # stateless — nothing but the round was read), then run the
        # standard update path
        from repro.kernels import rand as krand

        Qa = krand.dense_omega(Qa, reader.da, kt, q_dtype)
        Qb = krand.dense_omega(Qb, reader.db, kt, q_dtype)
        seeds = False
    init_fn = stats_init_fn(kind, reader.da, reader.db, kt)
    if seeds:  # kernels engine: Ω tiles generated inside the kernels
        upd = jit_seeded_update_fn(kind, kt, q_dtype)
        upd_raw = seeded_update_fn(kind, kt, q_dtype)
    else:
        upd = jit_update_fn(kind, engine)
        upd_raw = update_fn(kind, engine)
    Qa, Qb = jax.device_put(Qa), jax.device_put(Qb)
    pt.touch_heartbeat(cluster_dir, shard, pass_idx)

    expect = {k: meta.get(k) for k in pt.BINDING_KEYS}
    # combiner-on-the-way-out: pre-merge runs of `combine` consecutive
    # groups into one span partial before publishing (shrinks the
    # coordinator's merge fan-in by that factor); 1 = off, the
    # historical per-group protocol
    combine = int(meta.get("combine", 1))
    if groups is None:
        owned = [g for g in range(n_groups)
                 if (g // combine) % n_shards == shard]
    else:
        owned = sorted(int(g) for g in groups)

    def group_done(g: int) -> bool:
        """Published already — individually or inside a combined span
        (check every aligned span that could contain g)."""
        s = 1
        while s <= combine:
            if pt.binding_matches(
                    pt.partial_meta(cluster_dir, pass_idx, g - g % s, s),
                    expect):
                return True
            s <<= 1
        return False

    todo = [g for g in owned if not group_done(g)]
    if not todo:
        return 0
    state = {"published": 0}

    def publish_span(g: int, span: int, stats) -> None:
        """The (combined) group sink: beat, publish-if-new, count."""
        with obs.span("publish", group=int(g), span=int(span)):
            jax.block_until_ready(stats)
            if not pt.binding_matches(  # idempotent re-publication guard
                    pt.partial_meta(cluster_dir, pass_idx, g, span), expect):
                pt.write_partial(cluster_dir, pass_idx, g, stats, expect,
                                 shard=shard, n_shards=n_shards, span=span)
            state["published"] += 1
            pt.touch_heartbeat(cluster_dir, shard, pass_idx)

    combiner = SpanCombiner(combine, publish_span)

    def publish(g: int, stats) -> None:
        if combine > 1:
            combiner.emit(g, stats)
        else:
            publish_span(g, 1, stats)

    # -- device-parallel (hybrid) shard ----------------------------------
    if devices > 1:
        n_dev = len(jax.devices())
        if n_dev < devices:
            raise RuntimeError(
                f"worker asked for {devices} devices but only {n_dev} "
                "visible — the spawner must set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={devices} (or "
                "provide real accelerators) before jax initializes")
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:devices]), ("dev",))

        def emit(g: int, stats) -> None:
            publish(g, stats)
            # failure injection at group granularity: the device fold
            # publishes whole groups, so "after chunk c" means "after
            # the group containing c"
            last_chunk = min(n_chunks, (g + 1) * G) - 1
            if hang_at_chunk is not None and last_chunk >= hang_at_chunk:
                _hang_forever(shard, last_chunk)
            if kill_at_chunk is not None and last_chunk >= kill_at_chunk:
                raise WorkerKilled(
                    f"injected kill after group {g} (chunk {last_chunk})")

        with obs.span("worker_pass", pass_idx=int(pass_idx), kind=kind,
                      shard=shard, site="hybrid"):
            fold_groups_on_mesh(
                lambda i: reader.get_chunk(i), todo, upd_raw,
                upd, init_fn, Qa, Qb, mesh=mesh, merge_group=G,
                n_chunks=n_chunks, full_chunks=n_full_chunks(reader),
                emit=emit, prefetch=prefetch,
                span_attrs={"kind": kind, "engine": engine,
                            "pass_idx": int(pass_idx)},
                cost_fn=_cost_fn(kind, engine, kt, q_dtype, seeds))
            combiner.flush()  # trailing short run (end of stream)
        return state["published"]

    # -- sequential shard --------------------------------------------------

    # resume position
    mgr = CheckpointManager(pt.worker_cursor_dir(cluster_dir, shard, pass_idx),
                            keep=2)
    start_chunk = todo[0] * G
    current = init_fn()
    cur_meta = mgr.metadata(mgr.latest_step())
    if pt.binding_matches(cur_meta, expect) and cur_meta.get("shard") == shard:
        nxt, g0 = int(cur_meta["next_chunk"]), int(cur_meta["group"])
        # the cursor only helps if it sits mid-way through the FIRST
        # group still missing its partial — anything else (stale cursor,
        # a hole left by a repair worker) is redone from group start
        if todo[0] == g0 and g0 * G < nxt < min(n_chunks, (g0 + 1) * G):
            tree, _ = mgr.restore({"current": init_fn()})
            current = tree["current"]
            start_chunk = nxt

    # stream (striping in G*combine-chunk runs keeps whole combine-runs
    # on one worker, so the combiner sees unbroken aligned runs)
    if groups is None:
        idxs = list(shard_chunks(shard, n_shards, n_chunks,
                                 start=start_chunk, group=G * combine))
        src = reader.row_shard(shard, n_shards, start=start_chunk,
                               group=G * combine)
    else:
        idxs = [c for g in todo for c in range(g * G, min(n_chunks, (g + 1) * G))
                if c >= start_chunk]
        src = (reader.get_chunk(i) for i in iter(idxs))
    src = prefetched(src, depth=prefetch)

    todo_set = set(todo)
    counters = {"since_cursor": 0}

    def cb(chunk_idx: int, acc: SegmentedAccumulator) -> None:
        counters["since_cursor"] += 1
        end_of_group = (chunk_idx + 1) % G == 0 or chunk_idx + 1 == n_chunks
        if counters["since_cursor"] % ckpt_every == 0 or end_of_group:
            mgr.save(chunk_idx, {"current": acc.current},
                     metadata={**expect, "next_chunk": chunk_idx + 1,
                               "group": (chunk_idx + 1) // G,
                               "shard": shard})
            pt.touch_heartbeat(cluster_dir, shard, pass_idx)
        if hang_at_chunk is not None and chunk_idx >= hang_at_chunk:
            _hang_forever(shard, chunk_idx)
        if kill_at_chunk is not None and chunk_idx >= kill_at_chunk:
            raise WorkerKilled(f"injected kill at chunk {chunk_idx}")

    acc = SegmentedAccumulator(init_fn, n_chunks, G, sink=publish)
    acc.current = current
    with obs.span("worker_pass", pass_idx=int(pass_idx), kind=kind,
                  shard=shard, site="worker"):
        try:
            # published-by-someone-else groups are read-and-dropped, not
            # folded (the stream already carries them; folding them would
            # double-publish and corrupt the cursor's group accounting)
            run_fold(((i, ab) for i, ab in zip(idxs, src)
                      if i // G in todo_set),
                     upd, acc, Qa, Qb, on_chunk=cb,
                     span_attrs={"kind": kind, "engine": engine,
                                 "pass_idx": int(pass_idx)},
                     cost_fn=_cost_fn(kind, engine, kt, q_dtype, seeds))
            combiner.flush()  # trailing short run (end of stream)
        finally:
            src.close()
    return state["published"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--store", required=True,
                    help="view store path or URI (repro.store)")
    ap.add_argument("--cluster-dir", required=True)
    ap.add_argument("--shard", type=int, required=True)
    ap.add_argument("--n-shards", type=int, required=True)
    ap.add_argument("--pass-idx", type=int, required=True)
    ap.add_argument("--groups", default=None,
                    help="comma-separated merge-group ids overriding the "
                         "strided assignment (coordinator re-dispatch)")
    ap.add_argument("--devices", type=int, default=1,
                    help="local devices to fold merge groups over "
                         "(>1 = the Hybrid topology's device-parallel "
                         "worker; needs that many visible jax devices)")
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--ckpt-every", type=int, default=4)
    ap.add_argument("--round-wait-s", type=float, default=30.0)
    args = ap.parse_args(argv)
    use_compile_cache()
    groups = None
    if args.groups:
        groups = [int(g) for g in args.groups.split(",")]
    try:
        n = run_worker(args.store, args.cluster_dir, args.shard, args.n_shards,
                       args.pass_idx, groups=groups, prefetch=args.prefetch,
                       ckpt_every=args.ckpt_every,
                       round_wait_s=args.round_wait_s, devices=args.devices)
    except WorkerKilled as e:
        print(f"[worker {args.shard}] {e}", flush=True)
        os._exit(3)  # hard death: no cleanup, like a lost machine
    print(f"[worker {args.shard}] pass {args.pass_idx}: "
          f"published {n} partial(s)", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
