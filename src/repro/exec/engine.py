"""The one pass engine every execution mode drives.

Five drivers used to re-implement (or fork) the chunk → merge-group →
tree pass structure (`randomized_cca_streaming`/`_iterator`,
`dist_randomized_cca`, ``store.PassRunner``, the ``repro.cluster``
worker/coordinator); this module is the single implementation they are
now shells over:

- :func:`run_fold` — THE canonical chunk-fold loop: left-fold (a, b)
  chunks into a :class:`~repro.exec.accumulate.SegmentedAccumulator`
  (tree mode for single-process passes, sink mode for cluster workers
  publishing per-group partials), with the per-chunk callback hook that
  cursor checkpointing, in-flight bounding and failure injection all
  hang off;
- :func:`fold_groups_on_mesh` — the device-parallel form of the same
  fold: whole merge groups are folded one-per-device under ``shard_map``
  (a ``lax.scan`` over the group's chunks on each device), and the
  per-group sums are emitted in ascending group order.  Because a merge
  group is the canonical reduction unit and each group's left-fold runs
  on a single device with the exact per-chunk update arithmetic, the
  emitted partials are bitwise identical to the sequential fold — the
  keystone of the ``Sharded`` and ``Hybrid`` topologies;
- :class:`PassEngine` — owns the q+1 pass schedule, source opening and
  seek (resume), accumulator structure/restore, and the per-topology
  pass fold;
- :func:`fit` — the one entry point over a view store for any
  :mod:`~repro.exec.topology`.

Every mode accumulates in the same canonical order, so their results
agree bitwise — see :mod:`repro.exec.accumulate` for the argument.
"""

from __future__ import annotations

import functools
import inspect
import math
from typing import Callable, Iterable, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.analysis import sanitize
from repro.data.hashing import HashedRows

import jax

from .accumulate import MERGE_GROUP_CHUNKS, SegmentedAccumulator
from .topology import Local, Sharded, Topology, as_topology


# --------------------------------------------------------------------------
# pass schedule (shared by every driver, including the resident-mesh one)
# --------------------------------------------------------------------------


def pass_schedule(q: int) -> Iterable[Tuple[int, str]]:
    """The q+1 data passes of Algorithm 1: ``q`` range-finder ("power")
    passes followed by one "final" pass.  Yields (pass_idx, kind)."""
    for pass_idx in range(q):
        yield pass_idx, "power"
    yield q, "final"


# --------------------------------------------------------------------------
# chunk sources
# --------------------------------------------------------------------------


def open_source(source_factory, start_chunk: int):
    """Instantiate the chunk source for one pass.

    Seek-aware factories opt in by naming their first positional
    parameter ``start`` (e.g. ``repro.store.PassRunner._source``); they
    are asked to begin at ``start_chunk`` directly, so a resumed pass
    never reads the skipped prefix from disk.  Anything else keeps the
    legacy contract: ``source_factory()`` yields from chunk 0 and the
    fold loop filters.  (Opt-in is by name, not arity — a factory that
    merely happens to take a defaulted positional must not silently
    receive a chunk index.)
    """
    try:
        params = list(inspect.signature(source_factory).parameters.values())
        seekable = bool(params) and params[0].name == "start" and \
            params[0].kind in (params[0].POSITIONAL_ONLY,
                               params[0].POSITIONAL_OR_KEYWORD)
    except (TypeError, ValueError):
        seekable = False
    if seekable:
        return source_factory(start_chunk), start_chunk
    return source_factory(), 0


class StackedChunks:
    """Random-access adapter over stacked in-memory chunk arrays
    ``(nc, c, d)`` — what ``randomized_cca_streaming`` consumes.  Every
    chunk is full-size, so all merge groups are uniform."""

    def __init__(self, A_chunks, B_chunks):
        if A_chunks.shape[0] != B_chunks.shape[0] or \
                A_chunks.shape[1] != B_chunks.shape[1]:
            raise ValueError(
                f"paired chunk stacks required, got {A_chunks.shape} / "
                f"{B_chunks.shape}")
        self.A, self.B = A_chunks, B_chunks
        self.n_chunks = int(A_chunks.shape[0])
        self.chunk = int(A_chunks.shape[1])
        self.n = self.n_chunks * self.chunk
        self.da = int(A_chunks.shape[2])
        self.db = int(B_chunks.shape[2])

    def get_chunk(self, i: int):
        return self.A[i], self.B[i]

    def iter_chunks(self, start: int = 0):
        for i in range(start, self.n_chunks):
            yield self.get_chunk(i)


def n_full_chunks(access) -> int:
    """Chunks of ``access`` that carry a full ``chunk`` rows — every
    chunk except a short tail.  Merge groups made only of full chunks
    are "uniform" and eligible for the device-parallel fold."""
    if access.n % access.chunk == 0:
        return access.n_chunks
    return access.n_chunks - 1


# --------------------------------------------------------------------------
# THE chunk-fold loop (sequential form)
# --------------------------------------------------------------------------


def _on_host(x) -> bool:
    if isinstance(x, HashedRows):
        return isinstance(x.slots, np.ndarray)
    return isinstance(x, np.ndarray)


def on_device(a, b, **span_attrs):
    """The chunk ``(a, b)`` on the device.  Host views are copied
    explicitly: a numpy array as it is, a ``HashedRows`` as its slots and
    signs (its dense rows are built inside the chunk update).  The copy
    is an ``h2d`` span with the bytes of the dense rows (``bytes``), the
    bytes copied (``wire_bytes``) and ``form`` ``"hashed"`` when a host
    view is ``HashedRows``, else ``"dense"``.  A chunk already on
    the device passes through with no copy and no span."""
    host = [x for x in (a, b) if _on_host(x)]
    if not host:
        return a, b
    hashed = any(isinstance(x, HashedRows) for x in host)
    with obs.span("h2d", bytes=sum(math.prod(x.shape) * x.dtype.itemsize for x in host),
                  wire_bytes=sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(host)),
                  form="hashed" if hashed else "dense", **span_attrs):
        return jax.device_put((a, b))


def run_fold(indexed_chunks, update_fn, acc: SegmentedAccumulator, Qa, Qb, *,
             start_chunk: int = 0, on_chunk=None, span_attrs=None,
             cost_fn=None) -> SegmentedAccumulator:
    """The canonical chunk-fold loop — the only one in the codebase.

    ``indexed_chunks`` yields ``(chunk_idx, (a, b))`` with GLOBAL chunk
    indices (sequential drivers enumerate their source; cluster workers
    zip their strided index assignment).  Chunks below ``start_chunk``
    are skipped (non-seekable resume).  Each chunk left-folds into
    ``acc``'s current merge group; ``acc`` closes groups at the
    canonical boundaries — into its pairwise tree (single-process) or
    its sink (worker partial publication).  ``on_chunk(chunk_idx, acc)``
    runs after every fold: cursor checkpointing, in-flight bounding,
    heartbeats and failure injection all live there, OUTSIDE the fold.

    Under ``RCCA_TRACE`` the loop records an ``io_wait`` span around
    each source pull and a ``chunk`` span around each fold (the
    ``on_chunk`` callback rides inside it — in-flight bounding IS the
    device-compute wait), stamped with ``span_attrs``; when
    ``cost_fn(a, b)`` is given, its per-kernel cost-model totals are
    emitted as one ``kernel_cost`` counter at loop end.  A chunk that
    arrives as host memory is copied to the device before its fold
    (:func:`on_device`, an ``h2d`` span when traced), in both loops, so
    tracing changes no work.
    """
    if not obs.enabled():
        for chunk_idx, (a, b) in indexed_chunks:
            if chunk_idx < start_chunk:
                continue
            a, b = on_device(a, b)
            acc.update(chunk_idx, update_fn, a, b, Qa, Qb)
            if on_chunk is not None:
                on_chunk(chunk_idx, acc)
        acc.flush_tail()
        return acc

    base = dict(span_attrs or {})
    it = iter(indexed_chunks)
    kernel_parts: list = []
    while True:
        with obs.span("io_wait", **base):
            item = next(it, None)
        if item is None:
            break
        chunk_idx, (a, b) = item
        if chunk_idx < start_chunk:
            continue
        attrs = dict(base, chunk=chunk_idx)
        a, b = on_device(a, b, **attrs)
        if cost_fn is not None:
            cost = cost_fn(a, b)
            if cost.get("schedule") is not None:
                attrs["schedule"] = cost["schedule"]
            kernel_parts.extend(cost["kernels"])
        with obs.span("chunk", **attrs):
            acc.update(chunk_idx, update_fn, a, b, Qa, Qb)
            if on_chunk is not None:
                on_chunk(chunk_idx, acc)
    acc.flush_tail()
    if kernel_parts:
        from repro.obs.cost import merge_kernel_costs
        for part in merge_kernel_costs(kernel_parts):
            obs.counter("kernel_cost", **dict(base, **part))
    return acc


# --------------------------------------------------------------------------
# the device-parallel form: whole merge groups under shard_map
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _mesh_group_fold(update_fn, init_fn, mesh, axis: str):
    """The jitted one-group-per-device fold program.  Memoized on the
    (update, init, mesh) identity so repeated passes of a fit — and the
    per-batch calls within a pass — reuse one trace instead of
    recompiling the identical shard_map program every time (callers
    hoist their per-kind functions for exactly this reason)."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels.compat import shard_map

    def body(a_blk, b_blk, qa, qb):
        def step(s, ab):
            return update_fn(s, ab[0], ab[1], qa, qb), None
        s, _ = jax.lax.scan(step, init_fn(), (a_blk[0], b_blk[0]))
        return jax.tree_util.tree_map(lambda x: x[None], s)

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P()),
        out_specs=P(axis), check_rep=False))


def fold_groups_on_mesh(get_chunk, groups: Sequence[int], update_fn,
                        update_fn_jit, init_fn, Qa, Qb, *, mesh,
                        merge_group: int, n_chunks: int, full_chunks: int,
                        emit: Callable[[int, object], None],
                        prefetch: int = 2, span_attrs=None,
                        cost_fn=None) -> None:
    """Fold whole merge groups one-per-device and emit their sums in
    ascending group order.

    Uniform groups (exactly ``merge_group`` full-size chunks) are
    batched ``D`` at a time — one group per device of the 1-D ``mesh`` —
    and folded by a ``lax.scan`` over the group's chunks inside
    ``shard_map``.  The scan body is the exact per-chunk update, so each
    group's sum is bitwise identical to the sequential left-fold (the
    same per-device arithmetic; no cross-device collective ever touches
    a partial).  The at-most-one ragged tail group falls back to the
    sequential fold with the jitted per-chunk update — the same
    function, the same result, on chunks whose shapes the uniform batch
    cannot carry.

    A short batch is padded by REPLICATING its first group so the
    shard_map program keeps one shape; padded outputs are discarded.
    ``emit(g, stats)`` may raise to abort (worker kill injection) —
    groups already emitted stay emitted, exactly like a crashed worker.

    Uniform-group chunks stream through a
    :class:`~repro.store.prefetch.ChunkPrefetcher` (``prefetch`` is its
    read-ahead depth; 0 falls back to the metered synchronous reader),
    so the next batch's reads overlap the current batch's device fold.
    The prefetcher consumes the flat ascending chunk order the gather
    loop below pops (padding only replicates an id already fetched), so
    the reads — and therefore the folded values — are bitwise unchanged
    from the old synchronous gather.  Under ``RCCA_TRACE`` each batch
    records ``gather`` and ``mesh_fold`` spans, plus one ``io`` counter
    from the prefetcher and a ``kernel_cost`` counter for the folded
    chunks.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"group-parallel fold needs a 1-D mesh, got axes {mesh.axis_names}")
    axis = mesh.axis_names[0]
    D = mesh.devices.size
    G = int(merge_group)

    groups = sorted(int(g) for g in groups)
    uniform = [g for g in groups if (g + 1) * G <= full_chunks]
    ragged = [g for g in groups if (g + 1) * G > full_chunks]

    base = dict(span_attrs or {})
    if uniform:
        # function-level import: repro.store imports repro.exec at
        # package load, so the reverse edge must stay lazy
        from repro.store.prefetch import prefetched

        fold_batch = _mesh_group_fold(update_fn, init_fn, mesh, axis)
        shard = NamedSharding(mesh, P(axis))
        need = (c for g in uniform for c in range(g * G, (g + 1) * G))
        src = prefetched((get_chunk(c) for c in need), depth=prefetch,
                         device_put=False, site="mesh_gather")
        chunk_cost = None
        folded = 0
        try:
            for lo in range(0, len(uniform), D):
                ids = uniform[lo:lo + D]
                padded = ids + [ids[0]] * (D - len(ids))
                blocks = {}
                with obs.span("gather", **dict(base, groups=len(ids))):
                    # dict.fromkeys, not set(): deterministic first-seen
                    # order — and the pad duplicate is never re-fetched
                    for g in dict.fromkeys(padded):
                        pairs = [next(src) for _ in range(G)]
                        blocks[g] = (
                            np.stack([np.asarray(a) for a, _ in pairs]),
                            np.stack([np.asarray(b) for _, b in pairs]))
                        if cost_fn is not None and chunk_cost is None:
                            chunk_cost = cost_fn(blocks[g][0][0],
                                                 blocks[g][1][0])
                    a_blk = jax.device_put(
                        np.stack([blocks[g][0] for g in padded]), shard)
                    b_blk = jax.device_put(
                        np.stack([blocks[g][1] for g in padded]), shard)
                fattrs = dict(base, groups=len(ids))
                if (chunk_cost is not None
                        and chunk_cost.get("schedule") is not None):
                    fattrs["schedule"] = chunk_cost["schedule"]
                with obs.span("mesh_fold", **fattrs):
                    out = fold_batch(a_blk, b_blk, Qa, Qb)
                    for i, g in enumerate(ids):
                        emit(g, jax.tree_util.tree_map(
                            lambda x, _i=i: x[_i], out))
                folded += len(ids) * G
        finally:
            src.close()
        if chunk_cost is not None and folded:
            from repro.obs.cost import merge_kernel_costs
            scaled = [dict(k, calls=k["calls"] * folded,
                           flops=k["flops"] * folded,
                           bytes=k["bytes"] * folded)
                      for k in chunk_cost["kernels"]]
            for part in merge_kernel_costs(scaled):
                obs.counter("kernel_cost", **dict(base, **part))

    for g in ragged:
        lo = g * G
        hi = min(n_chunks, (g + 1) * G)
        acc = SegmentedAccumulator(init_fn, n_chunks, G, sink=emit)
        run_fold(((c, get_chunk(c)) for c in range(lo, hi)),
                 update_fn_jit, acc, Qa, Qb,
                 span_attrs=base or None, cost_fn=cost_fn)


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------


class PassEngine:
    """Drive Algorithm 1's q+1 data passes under one topology.

    The engine owns what the five historical drivers each re-implemented:
    chunk iteration and source seeking, the
    :class:`~repro.exec.accumulate.SegmentedAccumulator` group fold, the
    canonical pairwise-tree reduce, resume-state restoration, and the
    per-chunk callback hook everything else (cursor checkpointing,
    prefetch metering, in-flight bounding) is wired through.

    ``topology`` selects the pass fold: :class:`Local` folds a
    sequential chunk stream; :class:`Sharded` (``col_axis=None``) folds
    whole merge groups one-per-device over the local mesh — bitwise the
    same result.  ``Cluster``/``Hybrid`` fits are driven by
    ``repro.cluster.ClusterCoordinator`` (see :func:`fit`), which calls
    back into this module for the worker-side fold.

    ``omega`` selects Ω provenance (see ``repro.core.rcca.OMEGA_MODES``).
    With ``omega="seeded"`` and the kernels engine, pass 0 runs the
    seeded per-chunk update: the (2,)-uint32 per-view seeds ride in the
    Qa/Qb operand slots (same fold/cursor/round plumbing) and the
    ``(d, k̃)`` Ω array is never materialized — tiles are generated
    inside the Pallas kernels.  The jnp engine materializes Ω locally
    from the same seeds (its documented fallback), and
    ``"seeded-materialized"`` materializes the same tile-PRNG Ω up
    front for every engine — the bitwise oracle of the seeded path.
    """

    def __init__(self, cfg, *, engine: Optional[str] = None,
                 topology: Topology = Local(),
                 merge_group: int = MERGE_GROUP_CHUNKS,
                 omega: str = "materialized"):
        from repro.core.rcca import DEFAULT_ENGINE, resolve_engine, resolve_omega

        self.cfg = cfg
        self.engine = resolve_engine(DEFAULT_ENGINE if engine is None else engine)
        self.topology = topology
        self.merge_group = int(merge_group)
        self.omega = resolve_omega(omega)

    # -- per-pass pieces --------------------------------------------------

    @property
    def seeds_in_slots(self) -> bool:
        """True when pass 0's Qa/Qb operand slots carry seeds, not
        arrays (seeded mode under the kernels engine)."""
        return self.omega == "seeded" and self.engine == "kernels"

    def _init_payload(self, key, da: int, db: int):
        """Pass-0 Qa/Qb payload: seeds for the in-kernel path, arrays
        otherwise (each omega mode's own generator)."""
        from repro.core.rcca import init_Q, omega_seeds

        if self.seeds_in_slots:
            return omega_seeds(key)
        if self.omega == "seeded":
            # jnp engine: materialize the tile-PRNG Ω locally — a
            # worker needs only the seed to re-derive it (stateless).
            return init_Q(key, da, db, self.cfg, omega="seeded")
        return init_Q(key, da, db, self.cfg, omega=self.omega)

    def _boundary_Q(self, Qa, Qb, pass_idx: int, da: int, db: int):
        """Materialize Ω at a pass boundary when the slots carry seeds
        and downstream actually needs the arrays (centering correction,
        or the q = 0 finalize).  Ya is already a (da, k̃) array at every
        boundary, so this stays in the same memory class as the stats —
        the in-pass data path is what never materializes Ω."""
        from repro.kernels import rand as krand

        if not self.seeds_in_slots or pass_idx != 0:
            return Qa, Qb
        return (krand.dense_omega(Qa, da, self.cfg.sketch, self.cfg.dtype),
                krand.dense_omega(Qb, db, self.cfg.sketch, self.cfg.dtype))

    def _updaters(self, seeded: bool):
        """Jitted per-kind chunk updates for one pass flavor family."""
        from repro.core.rcca import jit_seeded_update_fn, jit_update_fn

        kinds = ("power", "final")
        if seeded:
            return {k: jit_seeded_update_fn(k, self.cfg.sketch, self.cfg.dtype)
                    for k in kinds}
        return {k: jit_update_fn(k, self.engine) for k in kinds}

    def _init_fn(self, kind: str, da: int, db: int):
        from repro.core.rcca import stats_init_fn

        return stats_init_fn(kind, da, db, self.cfg.sketch)

    def _finish(self, fstats, Qa, Qb, da: int, db: int):
        from repro.core.rcca import finalize_result

        return finalize_result(fstats, Qa, Qb, self.cfg, da, db)

    # The pass boundaries.  Under RCCA_TRACE each is a ``q_update`` or
    # ``finish`` span, with the pass's ``merge`` (the tree's result)
    # nested where the boundary first needs the stats.

    def _q_update(self, acc, Qa, Qb, pass_idx: int, da: int, db: int, *,
                  site: str):
        """Qa, Qb for the pass after power pass ``pass_idx``."""
        from repro.core.rcca import power_update_Q

        with obs.span("q_update", pass_idx=pass_idx, site=site):
            if self.cfg.center:  # μ corrections need the actual Ω
                Qa, Qb = self._boundary_Q(Qa, Qb, pass_idx, da, db)
            with obs.span("merge", pass_idx=pass_idx, site=site):
                stats = acc.result()
            return power_update_Q(stats, Qa, Qb, self.cfg)

    def _finalize(self, acc, Qa, Qb, pass_idx: int, da: int, db: int, *,
                  site: str):
        """The fit's result after the final pass ``pass_idx``."""
        with obs.span("finish", site=site):
            Qa, Qb = self._boundary_Q(Qa, Qb, pass_idx, da, db)  # q = 0
            with obs.span("merge", pass_idx=pass_idx, site=site):
                stats = acc.result()
            return self._finish(stats, Qa, Qb, da, db)

    def cost_fn(self, kind: str, seeded: bool):
        """Cost-model ``(a, b) -> flops/bytes`` closure for one pass's
        chunk updates, or ``None`` when tracing is off."""
        if not obs.enabled():
            return None
        from repro.obs.cost import chunk_cost_fn

        return chunk_cost_fn(kind, self.engine, int(self.cfg.sketch),
                             self.cfg.dtype, seeded=seeded)

    # -- sequential (Local) ----------------------------------------------

    def run_stream(self, source_factory, da: int, db: int, key, *,
                   n_chunks: Optional[int] = None, resume_state=None,
                   on_pass_end=None, on_pass_complete=None):
        """All q+1 passes over a sequential chunk source → RCCAResult.

        This is the exact contract ``randomized_cca_iterator`` has
        always exposed — see its docstring for the resume-state and
        seekable-factory details; it is now a shell over this method.
        ``on_pass_complete(pass_idx, kind, acc, Qa, Qb)`` fires once per
        pass after its fold finishes, with the accumulator and the
        Qa/Qb payload the pass consumed (seeds on a seeded pass 0) —
        the capture point ``repro.exec.delta`` persists FitState from.
        """
        with obs.span("fit", site="stream", engine=self.engine):
            return self._run_stream(source_factory, da, db, key,
                                    n_chunks=n_chunks,
                                    resume_state=resume_state,
                                    on_pass_end=on_pass_end,
                                    on_pass_complete=on_pass_complete)

    def _run_stream(self, source_factory, da, db, key, *,
                    n_chunks=None, resume_state=None, on_pass_end=None,
                    on_pass_complete=None):
        cfg = self.cfg
        sanitize.reset()
        Qa, Qb = self._init_payload(key, da, db)
        upd = self._updaters(False)
        upd_seeded = self._updaters(True) if self.seeds_in_slots else None

        start_pass, start_chunk, acc_state = 0, 0, None
        if resume_state is not None:
            start_pass = int(resume_state["pass_idx"])
            start_chunk = int(resume_state["chunk_idx"])
            acc_state = resume_state["acc"]
            Qa, Qb = resume_state["Qa"], resume_state["Qb"]

        for pass_idx, kind in pass_schedule(cfg.q):
            if pass_idx < start_pass:
                continue
            sanitize.set_context(pass_idx=pass_idx, kind=kind, site="stream")
            seeded = upd_seeded is not None and pass_idx == 0
            with obs.span("pass", pass_idx=pass_idx, kind=kind,
                          site="stream"):
                with obs.span("acc_init", pass_idx=pass_idx, site="stream"):
                    acc = SegmentedAccumulator.structure(
                        self._init_fn(kind, da, db), n_chunks,
                        self.merge_group, start_chunk)
                    if acc_state is not None:
                        acc.load_state(acc_state)
                        acc_state = None
                source, offset = open_source(source_factory, start_chunk)
                cb = None
                if on_pass_end is not None:
                    cb = (lambda ci, a_, _p=pass_idx, _qa=Qa, _qb=Qb:
                          on_pass_end(_p, ci, a_, _qa, _qb))
                fn = upd_seeded[kind] if seeded else upd[kind]
                run_fold(enumerate(source, start=offset), fn, acc, Qa, Qb,
                         start_chunk=start_chunk, on_chunk=cb,
                         span_attrs={"kind": kind, "engine": self.engine,
                                     "pass_idx": pass_idx},
                         cost_fn=self.cost_fn(kind, seeded))
                start_chunk = 0
                if sanitize.enabled():
                    sanitize.observe("pass_end", acc.result())
                if on_pass_complete is not None:
                    on_pass_complete(pass_idx, kind, acc, Qa, Qb)
                if kind == "power":
                    Qa, Qb = self._q_update(acc, Qa, Qb, pass_idx, da, db,
                                            site="stream")

        res = self._finalize(acc, Qa, Qb, pass_idx, da, db, site="stream")
        if sanitize.enabled():
            res.diagnostics["sanitize"] = sanitize.snapshot()
            sanitize.dump()
        return res

    # -- device-parallel (Sharded) ---------------------------------------

    def run_mesh(self, access, key, *, mesh=None, prefetch: int = 2,
                 on_pass_complete=None):
        """All q+1 passes with merge groups folded one-per-device over
        the local mesh (the in-process ``Sharded`` topology) — bitwise
        identical to :meth:`run_stream` on the same chunks.

        ``access`` needs random chunk access (``get_chunk``, ``n``,
        ``chunk``, ``n_chunks``, ``da``, ``db``) — a
        ``ViewStoreReader`` or :class:`StackedChunks`.  Mid-pass cursor
        checkpointing is a sequential-stream feature; device-parallel
        passes restart at pass granularity.  ``prefetch`` is the gather
        read-ahead depth (see :func:`fold_groups_on_mesh`).
        ``on_pass_complete`` is the same per-pass capture hook as
        :meth:`run_stream`.
        """
        with obs.span("fit", site="mesh", engine=self.engine):
            return self._run_mesh(access, key, mesh=mesh, prefetch=prefetch,
                                  on_pass_complete=on_pass_complete)

    def _run_mesh(self, access, key, *, mesh=None, prefetch: int = 2,
                  on_pass_complete=None):
        from repro.core.rcca import seeded_update_fn, update_fn

        topo = self.topology if isinstance(self.topology, Sharded) else Sharded()
        if topo.col_axis is not None:
            raise ValueError(
                "streaming fits need col_axis=None — feature-sharded "
                "(col_axis) execution is the resident-mode path through "
                "repro.core.rcca_dist.dist_randomized_cca")
        mesh = mesh if mesh is not None else topo.build_mesh()
        cfg = self.cfg
        sanitize.reset()
        da, db = access.da, access.db
        nc = access.n_chunks
        n_groups = -(-nc // self.merge_group)
        Qa, Qb = self._init_payload(key, da, db)

        # per-kind functions hoisted out of the pass loop: repeated
        # power passes must hit one trace of the mesh fold program, not
        # recompile it per pass (see _mesh_group_fold's memoization)
        kinds = ("power", "final")
        upd_raw = {k: update_fn(k, self.engine) for k in kinds}
        upd_jit = self._updaters(False)
        sd_raw = sd_jit = None
        if self.seeds_in_slots:
            sd_raw = {k: seeded_update_fn(k, cfg.sketch, cfg.dtype)
                      for k in kinds}
            sd_jit = self._updaters(True)
        init_fns = {k: self._init_fn(k, da, db) for k in kinds}

        for pass_idx, kind in pass_schedule(cfg.q):
            sanitize.set_context(pass_idx=pass_idx, kind=kind, site="mesh")
            seeded = sd_raw is not None and pass_idx == 0
            raw = sd_raw[kind] if seeded else upd_raw[kind]
            jit = sd_jit[kind] if seeded else upd_jit[kind]
            with obs.span("pass", pass_idx=pass_idx, kind=kind, site="mesh"):
                with obs.span("acc_init", pass_idx=pass_idx, site="mesh"):
                    acc = SegmentedAccumulator(init_fns[kind], nc,
                                               self.merge_group)
                fold_groups_on_mesh(
                    access.get_chunk, range(n_groups), raw,
                    jit, init_fns[kind], Qa, Qb, mesh=mesh,
                    merge_group=self.merge_group, n_chunks=nc,
                    full_chunks=n_full_chunks(access), emit=acc.push_group,
                    prefetch=prefetch,
                    span_attrs={"kind": kind, "engine": self.engine,
                                "pass_idx": pass_idx},
                    cost_fn=self.cost_fn(kind, seeded))
                if sanitize.enabled():
                    sanitize.observe("pass_end", acc.result())
                if on_pass_complete is not None:
                    on_pass_complete(pass_idx, kind, acc, Qa, Qb)
                if kind == "power":
                    Qa, Qb = self._q_update(acc, Qa, Qb, pass_idx, da, db,
                                            site="mesh")

        res = self._finalize(acc, Qa, Qb, pass_idx, da, db, site="mesh")
        if sanitize.enabled():
            res.diagnostics["sanitize"] = sanitize.snapshot()
            sanitize.dump()
        res.diagnostics["topology"] = {
            "name": "sharded", "devices": int(mesh.devices.size),
            "n_groups": n_groups, "merge_group": self.merge_group,
        }
        return res

    # -- dispatch ----------------------------------------------------------

    def run(self, access, key, **kwargs):
        """Topology dispatch over a random-access chunk source."""
        if isinstance(self.topology, Local):
            return self.run_stream(
                lambda start: access.iter_chunks(start), access.da, access.db,
                key, n_chunks=access.n_chunks, **kwargs)
        if isinstance(self.topology, Sharded):
            return self.run_mesh(access, key, **kwargs)
        raise ValueError(
            f"{type(self.topology).__name__} fits are multi-process — "
            "drive them through repro.exec.fit (it needs the store path "
            "and a cluster directory)")


# --------------------------------------------------------------------------
# the one entry point
# --------------------------------------------------------------------------


def fit(store, cfg, key, *, topology: Topology = Local(),
        engine: Optional[str] = None, merge_group: int = MERGE_GROUP_CHUNKS,
        omega: str = "materialized",
        cluster_dir: Optional[str] = None, prefetch=2,
        ckpt_dir: Optional[str] = None, resume: bool = False,
        **cluster_kwargs):
    """Fit RandomizedCCA over a view store under any topology.

    ``store`` is a ``ViewStoreReader`` or a store path/URI.  ``Local``
    runs the prefetching, cursor-checkpointed ``store.PassRunner``;
    ``Sharded`` the in-process device-parallel engine; ``Cluster`` and
    ``Hybrid`` the multi-process coordinator (``cluster_dir`` required —
    extra keyword arguments are forwarded to it).  Every topology
    returns a bitwise-identical ``RCCAResult`` on the same store.

    ``omega`` selects Ω provenance (``repro.core.rcca.OMEGA_MODES``):
    ``"seeded"`` runs the first data pass from an 8-byte seed — the
    kernels engine generates Ω tiles in-kernel and cluster rounds ship
    the seed instead of the ``(d, k̃)`` bases.
    """
    from repro.core.rcca import DEFAULT_ENGINE
    from repro.store import PassRunner, ViewStoreReader

    topo = as_topology(topology)
    reader = store if isinstance(store, ViewStoreReader) else ViewStoreReader(store)
    engine = DEFAULT_ENGINE if engine is None else engine

    if isinstance(topo, Local):
        runner = PassRunner(reader, cfg, engine=engine,
                            prefetch=prefetch, ckpt_dir=ckpt_dir,
                            merge_group=merge_group, omega=omega)
        return runner.fit(key, resume=resume)

    if isinstance(topo, Sharded):
        eng = PassEngine(cfg, engine=engine, topology=topo,
                         merge_group=merge_group, omega=omega)
        return eng.run_mesh(reader, key,
                            prefetch=prefetch if isinstance(prefetch, int)
                            else 2)

    # Cluster / Hybrid
    from repro.cluster import ClusterCoordinator

    if cluster_dir is None:
        raise ValueError(
            f"{topo.name} topology needs cluster_dir= (the shared "
            "rounds/partials/heartbeats directory)")
    coord = ClusterCoordinator(
        reader, cfg, cluster_dir, n_workers=topo.n_workers,
        devices_per_worker=topo.devices_per_worker,
        engine=engine, merge_group=merge_group, omega=omega,
        prefetch=prefetch if isinstance(prefetch, int) else 2,
        **cluster_kwargs)
    return coord.fit(key)
