"""Two-pass map/combine/reduce coordinator — the cluster's driver.

Hadoop-shaped execution of Algorithm 1 (the paper's "suitable for
distributed processing frameworks in which iteration is expensive"
claim as a subsystem): for each of the q+1 data passes the coordinator

1. publishes the pass ROUND (Qa/Qb bases + binding metadata) under the
   cluster directory,
2. spawns one worker process per shard (``python -m
   repro.cluster.worker`` — any external scheduler could do the same);
   with ``devices_per_worker > 1`` each worker folds its merge groups
   one-per-device over a local mesh (the HYBRID topology — the spawner
   forces ``--xla_force_host_platform_device_count`` into the worker
   environment, a CPU rehearsal of the layout),
3. runs the BARRIER: polls for per-merge-group partials, re-dispatching
   the merge groups of dead, stale-heartbeat or straggling workers to
   fresh repair workers (at-most-once per group id — duplicates are
   byte-identical and ignored),
4. STREAMS the deterministic fixed-order pairwise tree directly from
   the on-disk partials (``SegmentedAccumulator.push_group`` in group
   order — only O(log G) group partials are ever resident, so huge
   k̃·d partial sets merge in bounded memory) and either rotates the
   bases (``power_update_Q``) or finishes (``finalize_result``).

Because workers fold whole merge groups with the same per-chunk updates
through the one canonical fold (``repro.exec``), and the merge tree is
the same fixed structure the single-process drivers use, the
coordinator's result is BIT-IDENTICAL to ``randomized_cca_streaming``
on the same store for any worker count AND any devices-per-worker
layout (tests/test_cluster.py, tests/test_exec_topologies.py), under
injected worker kills (tests/test_cluster_failures.py) and injected
worker hangs caught by the heartbeat monitor.

One process per chip: a TPU belongs to the first process that opens
it, and the coordinator opens its default backend for the bases and the
merge.  On a TPU host its workers could then never reach a chip, so the
coordinator refuses to start there (fit with ``Local`` or ``Sharded``,
or rehearse the cluster on the host's CPU with ``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
import uuid
from typing import Dict, List, Optional

import jax

from repro import obs
from repro.analysis import sanitize
from repro.analysis.protocol import trace_event
from repro.core.rcca import (
    DEFAULT_ENGINE,
    RCCAConfig,
    RCCAResult,
    algo_meta,
    finalize_result,
    init_Q,
    omega_seeds,
    power_update_Q,
    resolve_engine,
    resolve_omega,
    stats_init_fn,
)
from repro.exec import MERGE_GROUP_CHUNKS, SegmentedAccumulator
from repro.store import ViewStoreReader

from . import partials as pt


class ClusterCoordinator:
    """Drive a multi-worker two-pass fit over a view store.

    Parameters
    ----------
    store:          view store path/URI, or an open ``ViewStoreReader``.
    cfg:            :class:`RCCAConfig` hyper-parameters.
    cluster_dir:    shared directory for rounds/partials/cursors/
                    heartbeats/logs — on a real cluster this lives on
                    the DFS all workers mount; kill/resume state never
                    leaves it.
    n_workers:      worker processes per pass.
    devices_per_worker: local devices each worker folds merge groups
                    over (>1 = the Hybrid topology; workers are spawned
                    with the forced-host-device XLA flag, a CPU
                    rehearsal of the layout).  Results are bitwise
                    invariant to this knob.
    engine:         data-pass engine, binding for every partial.
    merge_group:    chunks per merge group (the partial granularity).
                    MUST equal the single-process driver's value for
                    bit-identical results (default: the shared
                    ``repro.exec.MERGE_GROUP_CHUNKS``).
    combine_groups: combiner-on-the-way-out span (power of two): each
                    worker pre-merges runs of this many consecutive
                    merge groups through its own pairwise stack and
                    publishes ONE span partial per run, shrinking the
                    coordinator's merge fan-in (and the partials
                    directory) by that factor.  Results are bitwise
                    invariant to this knob — a combined span is exactly
                    one subtree of the canonical reduction.  1 (the
                    default) is the historical per-group protocol.
    omega:          Ω provenance (``rcca.OMEGA_MODES``), binding for
                    every round and partial.  ``"seeded"`` publishes
                    the pass-0 round with the per-view (2,)-uint32
                    seeds in the Qa/Qb slots — an 8-byte broadcast
                    instead of the ``(d, k̃)`` bases; workers re-derive
                    (jnp) or in-kernel generate (kernels) Ω from it.
    prefetch:       per-worker chunk prefetch depth.
    worker_timeout: seconds a pass may run before live workers are
                    declared stragglers, killed and their missing
                    groups re-dispatched.
    heartbeat_timeout: seconds a worker's heartbeat beacon may go
                    stale before the worker is declared stuck and
                    killed (re-dispatch happens through the normal
                    dead-worker path).  ``None`` disables the monitor
                    and leaves only the wall-clock ``worker_timeout``.
                    Set it comfortably above per-group fold time (the
                    beacon beats at start and every group/cursor save).
    max_redispatch: repair rounds per pass before giving up.
    env_overrides:  {shard: {env}} merged into that shard's initial
                    worker process — the failure-injection hook
                    (repair workers never inherit it).
    """

    def __init__(self, store, cfg: RCCAConfig, cluster_dir: str, *,
                 n_workers: int = 2, devices_per_worker: int = 1,
                 engine: str = DEFAULT_ENGINE,
                 merge_group: int = MERGE_GROUP_CHUNKS,
                 combine_groups: int = 1,
                 omega: str = "materialized", prefetch: int = 2,
                 ckpt_every: int = 4, worker_timeout: float = 600.0,
                 heartbeat_timeout: Optional[float] = None,
                 max_redispatch: int = 3,
                 env_overrides: Optional[Dict[int, dict]] = None,
                 python: str = sys.executable):
        if isinstance(store, ViewStoreReader):
            self.reader, self.store_path = store, store.path
        else:
            self.reader, self.store_path = ViewStoreReader(store), store
        self.cfg = cfg
        self.cluster_dir = cluster_dir
        self.n_workers = int(n_workers)
        self.devices_per_worker = int(devices_per_worker)
        self.engine = resolve_engine(engine)
        self.merge_group = int(merge_group)
        self.combine_groups = int(combine_groups)
        self.omega = resolve_omega(omega)
        self.prefetch = int(prefetch)
        self.ckpt_every = int(ckpt_every)
        self.worker_timeout = worker_timeout
        self.heartbeat_timeout = heartbeat_timeout
        self.max_redispatch = int(max_redispatch)
        self.env_overrides = env_overrides or {}
        self.python = python
        if self.n_workers < 1:
            raise ValueError("need at least one worker")
        if self.devices_per_worker < 1:
            raise ValueError("need at least one device per worker")
        if self.combine_groups < 1 or \
                self.combine_groups & (self.combine_groups - 1):
            raise ValueError(
                f"combine_groups must be a power of two (a combined span "
                f"must be one subtree of the canonical pairwise "
                f"reduction), got {self.combine_groups}")
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "ClusterCoordinator cannot run on a TPU host: this process "
                "holds the TPU once it computes the bases, and a chip "
                "serves one process at a time, so its worker processes "
                "could never open one.  Fit with the Local or Sharded "
                "topology, or rehearse the cluster on the CPU with "
                "JAX_PLATFORMS=cpu.")
        os.makedirs(os.path.join(cluster_dir, "logs"), exist_ok=True)
        # (pass_idx, group) → error for stale-partial removals that
        # failed — surfaced in diagnostics, retried at every pass sweep
        self._clean_pending: Dict[tuple, str] = {}

    # -- process management -----------------------------------------------

    @property
    def n_groups(self) -> int:
        return -(-self.reader.n_chunks // self.merge_group)

    def _spawn(self, shard: int, pass_idx: int, *, groups=None,
               extra_env: Optional[dict] = None) -> subprocess.Popen:
        cmd = [self.python, "-m", "repro.cluster.worker",
               "--store", self.store_path,
               "--cluster-dir", self.cluster_dir,
               "--shard", str(shard),
               "--n-shards", str(self.n_workers),
               "--pass-idx", str(pass_idx),
               "--prefetch", str(self.prefetch),
               "--ckpt-every", str(self.ckpt_every)]
        if self.devices_per_worker > 1:
            cmd += ["--devices", str(self.devices_per_worker)]
        if groups is not None:
            cmd += ["--groups", ",".join(str(g) for g in groups)]
        env = dict(os.environ)
        # workers must import repro wherever the scheduler runs them
        src_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = src_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        if self.devices_per_worker > 1:
            # hybrid workers need their device mesh before jax wakes up;
            # the flag forces the CPU platform's device count, so hybrid
            # is a CPU rehearsal (TPU hosts are refused in __init__)
            flag = ("--xla_force_host_platform_device_count="
                    f"{self.devices_per_worker}")
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
        if extra_env:
            env.update(extra_env)
        log = open(os.path.join(self.cluster_dir, "logs",
                                f"w{shard:03d}_p{pass_idx:05d}.log"), "ab")
        try:
            return subprocess.Popen(cmd, env=env, stdout=log,
                                    stderr=subprocess.STDOUT)
        finally:
            log.close()  # the child holds its own descriptor

    def _owned(self, shard: int) -> List[int]:
        return [g for g in range(self.n_groups)
                if (g // self.combine_groups) % self.n_workers == shard]

    # -- one pass ---------------------------------------------------------

    def _kill_stale(self, procs: Dict[int, subprocess.Popen], pass_idx: int,
                    spawned_at: Dict[int, float]) -> List[int]:
        """Heartbeat monitor: kill live workers whose beacon (or, if
        they never beat, whose spawn) is older than the staleness
        threshold.  The kill turns them into ordinary dead workers, so
        the existing re-dispatch path picks their groups up — long
        before the wall-clock pass timeout fires."""
        if self.heartbeat_timeout is None:
            return []
        stale = []
        now = obs.monotonic()
        for shard, p in procs.items():
            if p.poll() is not None:
                continue
            # age is bounded by time-since-spawn: a beacon left behind by
            # an earlier fit in the same cluster_dir (same shard/pass key)
            # must never condemn a freshly spawned worker that hasn't had
            # time to beat yet
            since_spawn = now - spawned_at.get(shard, now)
            age = pt.heartbeat_age(self.cluster_dir, shard, pass_idx)
            age = since_spawn if age is None else min(age, since_spawn)
            if age > self.heartbeat_timeout:
                p.kill()
                stale.append(shard)
        return stale

    def _run_pass(self, pass_idx: int, kind: str, Qa, Qb,
                  expect: dict) -> tuple:
        """Spawn → barrier → streamed tree merge (+ per-pass diagnostics)."""
        t0 = obs.monotonic()
        # stale-partial hygiene BEFORE the barrier polls: retry removals
        # that failed in earlier passes, then sweep this pass's group
        # range for leftovers of other fits.  Failures are never
        # swallowed — they land in diagnostics and stay queued.
        for p_old, g_old in list(self._clean_pending):
            if pt.clear_stale_partial(self.cluster_dir, p_old, g_old) is None:
                del self._clean_pending[(p_old, g_old)]
        for g, err in pt.sweep_stale_partials(
                self.cluster_dir, pass_idx, self.n_groups, expect).items():
            self._clean_pending[(pass_idx, g)] = err
        with obs.span("publish", pass_idx=int(pass_idx), kind=kind):
            pt.write_round(self.cluster_dir, pass_idx, Qa, Qb,
                           {**expect, "n_shards": self.n_workers,
                            "combine": self.combine_groups})
            procs = {s: self._spawn(s, pass_idx,
                                    extra_env=self.env_overrides.get(s))
                     for s in range(self.n_workers) if self._owned(s)}
        spawned_at = {s: obs.monotonic() for s in procs}
        n_spawned = len(procs)
        redispatched: List[int] = []
        stale_shards: List[int] = []
        attempts = 0
        deadline = (obs.monotonic() + self.worker_timeout
                    if self.worker_timeout else None)
        barrier = obs.span("barrier", pass_idx=int(pass_idx), kind=kind)
        barrier.__enter__()
        last_liveness = -1.0
        while True:
            plan, missing = pt.collect_coverage(self.cluster_dir, pass_idx,
                                                self.n_groups, expect)
            if not missing:
                break
            # liveness telemetry (~1 Hz): heartbeat ages of the live
            # workers, so `repro.obs report` can show per-shard health
            # next to the compute spans
            now = obs.monotonic()
            if now - last_liveness >= 1.0:
                last_liveness = now
                for shard, p in procs.items():
                    if p.poll() is not None:
                        continue
                    age = pt.heartbeat_age(self.cluster_dir, shard, pass_idx)
                    since = now - spawned_at.get(shard, now)
                    age = since if age is None else min(age, since)
                    obs.counter("heartbeat", shard=int(shard),
                                age_s=round(age, 3), pass_idx=int(pass_idx),
                                missing_groups=len(missing))
            stale_shards.extend(self._kill_stale(procs, pass_idx, spawned_at))
            timed_out = deadline is not None and obs.monotonic() > deadline
            if timed_out:
                for p in procs.values():  # stragglers: kill, then re-dispatch
                    if p.poll() is None:
                        p.kill()
            all_done = all(p.poll() is not None for p in procs.values())
            if all_done or timed_out:
                attempts += 1
                if attempts > self.max_redispatch:
                    raise RuntimeError(
                        f"pass {pass_idx}: merge groups {missing} still "
                        f"missing after {self.max_redispatch} re-dispatch "
                        f"round(s) — see {self.cluster_dir}/logs")
                # re-dispatch the dead/stale shards' groups to a fresh
                # repair worker (a "survivor" process; its shard id is
                # outside the strided range so cursors never collide)
                redispatched.extend(missing)
                obs.counter("redispatch", pass_idx=int(pass_idx),
                            groups=len(missing), attempt=attempts)
                repair = self.n_workers + attempts - 1
                procs = {repair: self._spawn(repair, pass_idx, groups=missing)}
                spawned_at = {repair: obs.monotonic()}
                n_spawned += 1
                deadline = (obs.monotonic() + self.worker_timeout
                            if self.worker_timeout else None)
            time.sleep(0.05)
        barrier.__exit__(None, None, None)
        for p in procs.values():
            p.poll()
        t_merge = obs.monotonic()
        r = self.reader
        # Streamed reduce: push each on-disk partial straight into the
        # fixed pairwise tree in group order and drop it — O(log G)
        # stats pytrees resident no matter how many groups the pass has
        # (the binding is re-validated per partial at merge time, the
        # at-most-once guard against a racing stale publisher).
        sanitize.set_context(pass_idx=int(pass_idx), kind=kind,
                             site="coordinator_merge")
        acc = SegmentedAccumulator(
            stats_init_fn(kind, r.da, r.db, self.cfg.sketch),
            r.n_chunks, self.merge_group)
        merge_span = obs.span("merge", pass_idx=int(pass_idx), kind=kind,
                              groups=self.n_groups, partials=len(plan))
        merge_span.__enter__()
        g = 0
        while g < self.n_groups:
            span, _ = plan[g]
            loaded = pt.read_partial(self.cluster_dir, pass_idx, g, span)
            assert loaded is not None, g
            stats, meta = loaded
            if not pt.binding_matches(meta, expect):  # at-most-once guard
                raise RuntimeError(f"stale partial for group {g} at merge time")
            trace_event("merge",
                        pt.partial_path(self.cluster_dir, pass_idx, g, span),
                        fit_id=expect["fit_id"], pass_idx=int(pass_idx),
                        group=int(g), span=int(span))
            # the sanctioned entry into the canonical tree: spans in
            # ascending group order, fold order owned by the accumulator
            # (a combined span is one subtree — bitwise identical to its
            # groups pushed individually)
            acc.push_group_span(g, stats, span)  # rcca: noqa[RCCA001]
            g += span
        merged = acc.result()
        merge_span.__exit__(None, None, None)
        sanitize.observe("pass_end", merged)
        now = obs.monotonic()
        obs.counter("workers", pass_idx=int(pass_idx), spawned=n_spawned)
        diag = {"wall_s": round(now - t0, 4),
                "merge_s": round(now - t_merge, 4),
                "merge_fan_in": len(plan),
                "workers_spawned": n_spawned,
                "redispatched_groups": sorted(set(redispatched)),
                "stale_heartbeat_shards": sorted(set(stale_shards)),
                "stale_clean_failures": {
                    f"p{p:05d}_g{g:05d}": e
                    for (p, g), e in sorted(self._clean_pending.items())}}
        return merged, diag

    # -- driving ----------------------------------------------------------

    def _materialize_omega(self, seed_a, seed_b):
        """(2,)-uint32 seeds → the tile-PRNG Ω bases, at a pass
        boundary where the coordinator itself needs the arrays
        (centering corrections, q = 0 finalize)."""
        from repro.kernels import rand as krand

        r, cfg = self.reader, self.cfg
        return (krand.dense_omega(seed_a, r.da, cfg.sketch, cfg.dtype),
                krand.dense_omega(seed_b, r.db, cfg.sketch, cfg.dtype))

    def fit(self, key: jax.Array) -> RCCAResult:
        """All q+1 passes across ``n_workers`` processes →
        :class:`RCCAResult`, bit-identical to the single-process
        drivers on the same store."""
        # fit identity only (binds partials to THIS fit across worker
        # respawns); never reaches the arithmetic or the merge order
        fit_id = uuid.uuid4().hex  # rcca: noqa[RCCA004]
        obs.set_context(fit_id=fit_id, role="coordinator")
        with obs.span("fit", site="coordinator", engine=self.engine,
                      n_workers=self.n_workers,
                      devices_per_worker=self.devices_per_worker):
            return self._fit(key, fit_id)

    def _fit(self, key: jax.Array, fit_id: str) -> RCCAResult:
        r, cfg = self.reader, self.cfg
        sanitize.reset()
        seeded = self.omega == "seeded"
        if seeded:
            # pass-0 rounds ship the 8-byte seeds in the Qa/Qb slots;
            # workers re-derive (jnp) or in-kernel generate (kernels) Ω
            Qa, Qb = omega_seeds(key)
        else:
            Qa, Qb = init_Q(key, r.da, r.db, cfg, omega=self.omega)
        passes = []
        for pass_idx in range(cfg.q + 1):
            kind = "final" if pass_idx == cfg.q else "power"
            expect = pt.binding_meta(
                fit_id=fit_id, pass_idx=pass_idx, kind=kind,
                engine=self.engine, fingerprint=r.fingerprint(),
                merge_group=self.merge_group, algo=algo_meta(cfg),
                omega=self.omega)
            with obs.span("pass", pass_idx=pass_idx, kind=kind,
                          site="coordinator"):
                stats, diag = self._run_pass(pass_idx, kind, Qa, Qb, expect)
                passes.append(diag)
                # n is an f32 accumulator: allow its rounding at huge row
                # counts while still catching whole wrong/duplicate chunks
                if abs(float(stats.n) - r.n) > max(1.0, 1e-6 * r.n):
                    raise RuntimeError(
                        f"pass {pass_idx} merged {float(stats.n):.0f} rows, "
                        f"store has {r.n} — a merge group folded the wrong "
                        "chunks")
                if kind == "power":
                    if seeded and pass_idx == 0 and cfg.center:
                        Qa, Qb = self._materialize_omega(Qa, Qb)
                    Qa, Qb = power_update_Q(stats, Qa, Qb, cfg)
        if seeded and cfg.q == 0:  # finalize needs the actual Ω
            Qa, Qb = self._materialize_omega(Qa, Qb)
        res = finalize_result(stats, Qa, Qb, cfg, r.da, r.db)
        res.diagnostics["cluster"] = {
            "n_workers": self.n_workers,
            "devices_per_worker": self.devices_per_worker,
            "topology": "hybrid" if self.devices_per_worker > 1 else "cluster",
            "n_groups": self.n_groups,
            "merge_group": self.merge_group,
            "combine_groups": self.combine_groups,
            "omega": self.omega,
            "fit_id": fit_id,
            "passes": passes,
        }
        if sanitize.enabled():
            res.diagnostics["sanitize"] = sanitize.snapshot()
            sanitize.dump()
        return res
