"""Canonical pass accumulation: chunk → merge group → pairwise tree.

This is the bit-reproducibility backbone every execution topology
shares (it moved here from ``repro.core.rcca`` when the pass loop was
unified under :mod:`repro.exec`): chunks left-fold into fixed-size
MERGE GROUPS; group sums reduce through a fixed PAIRWISE TREE whose
shape is a function of the group INDEX alone.  Any assignment of whole
merge groups to workers or devices, merged in group order, therefore
reproduces the single-process reduction bitwise — which is the whole
correctness argument of the :class:`~repro.exec.topology.Cluster`,
:class:`~repro.exec.topology.Sharded` and
:class:`~repro.exec.topology.Hybrid` topologies.

Everything here is generic over the statistics pytree: a "stats" value
is any pytree of arrays whose merge is elementwise addition (the exact
map/reduce combiner of a sum-of-per-row-statistics pass — PowerStats
and FinalStats in ``repro.core.rcca`` are the two instances).
"""

from __future__ import annotations

import operator
from typing import Any, Callable, Dict, Iterable, List, Mapping, Optional

import jax

from repro.analysis import sanitize

#: A "stats" value is any pytree of arrays whose merge is elementwise
#: addition; generic code here treats it opaquely.
Stats = Any

#: Chunks per merge group — the granularity of the canonical reduction
#: and therefore of cluster partials and device-parallel group folds.
#: A store-pass constant, NOT a function of the worker/device count:
#: bit-reproducibility across topologies holds exactly because the
#: grouping never moves.
MERGE_GROUP_CHUNKS = 8


def merge_stats(x: Stats, y: Stats) -> Stats:
    """Combine two accumulators over disjoint row sets: elementwise
    addition on every pytree leaf.  Exact as algebra (every field is a
    plain sum over rows); the fp ADD still rounds — which is why the
    reduction ORDER below is canonical."""
    return jax.tree_util.tree_map(operator.add, x, y)


class PairwiseStack:
    """Fixed-structure pairwise reduction over a sequence of partials.

    The binary-counter scheme of pairwise summation: pushing partial
    ``m`` merges stack tops of equal weight, so after ``m`` pushes the
    stack mirrors the binary digits of ``m`` and the reduction tree is a
    function of the partial INDEX alone — not of who computed each
    partial or when it arrived.  This is what makes the cluster merge
    bit-reproducible: any assignment of whole merge groups to workers,
    merged in group order, reproduces the single-process reduction
    bitwise.  Live memory is O(log #groups) stats pytrees.
    """

    def __init__(self, stack: Optional[Iterable[Stats]] = None,
                 counts: Optional[Iterable[int]] = None):
        self.stack: List[Stats] = list(stack) if stack is not None else []
        self.counts: List[int] = list(counts) if counts is not None else []

    @staticmethod
    def depth_after(m: int) -> int:
        """Stack depth after ``m`` pushes (= popcount(m)) — lets a
        checkpoint restore rebuild the like-tree from a chunk index."""
        return bin(m).count("1")

    def push(self, s: Stats) -> None:
        self.push_span(s, 1)

    def push_span(self, s: Stats, count: int) -> None:
        """Push a pre-merged ALIGNED DYADIC span: ``s`` is the canonical
        pairwise sum of ``count`` consecutive leaves where ``count`` is a
        power of two and the span starts at a multiple of ``count``.
        Such a span is exactly one subtree of the binary-counter
        reduction, so pushing it as a single weight-``count`` entry
        reproduces ``count`` individual pushes bitwise — this is what
        lets cluster workers pre-merge their own groups before
        publishing (combiner-on-the-way-out) without moving the tree.
        Alignment is the caller's contract (see
        ``SegmentedAccumulator.push_group_span``)."""
        if count < 1 or count & (count - 1):
            raise ValueError(f"span weight must be a power of two, got {count}")
        self.stack.append(s)
        self.counts.append(count)
        while len(self.counts) >= 2 and self.counts[-1] == self.counts[-2]:
            hi = self.stack.pop()
            self.stack[-1] = merge_stats(self.stack[-1], hi)
            self.counts[-1] += self.counts.pop()

    def result(self) -> Optional[Stats]:
        """Fold the leftover unequal-weight entries newest→oldest (the
        deterministic completion of the tree)."""
        if not self.stack:
            return None
        res = self.stack[-1]
        for s in reversed(self.stack[:-1]):
            res = merge_stats(s, res)
        return res


class SegmentedAccumulator:
    """Canonical accumulation of one data pass: chunks left-fold into
    the current ``group`` accumulator; each completed group (every
    ``group_chunks`` chunks, plus the ragged tail) either enters a
    :class:`PairwiseStack` or — when a ``sink`` is given — is handed to
    the sink keyed by its GLOBAL group index (the cluster worker's
    publish path).  Single-process drivers, cluster workers, the
    device-parallel group fold and the coordinator merge all share this
    structure, which is the whole bit-reproducibility argument of the
    execution topologies.
    """

    def __init__(self, init_fn: Callable[[], Stats], n_chunks: Optional[int],
                 group_chunks: int = MERGE_GROUP_CHUNKS,
                 sink: Optional[Callable[[int, Stats], None]] = None):
        if group_chunks <= 0:
            raise ValueError("merge group size must be positive")
        self.init_fn = init_fn
        self.n_chunks = None if n_chunks is None else int(n_chunks)
        self.group_chunks = int(group_chunks)
        self.sink = sink
        self.current = init_fn()
        self._tree = PairwiseStack()
        self.groups_done = 0
        self._in_group = 0  # chunks folded into ``current`` so far
        self._last_chunk = -1  # global index of the last folded chunk

    # -- geometry ---------------------------------------------------------

    @property
    def n_groups(self) -> int:
        return -(-self.n_chunks // self.group_chunks)

    @staticmethod
    def groups_completed(next_chunk: int, n_chunks: Optional[int],
                         group_chunks: int) -> int:
        """Merge groups fully folded once chunks [0, next_chunk) are in
        — with a known length, the ragged tail group completes with the
        last chunk."""
        if n_chunks is not None and next_chunk >= n_chunks:
            return -(-n_chunks // group_chunks)
        return next_chunk // group_chunks

    # -- folding ----------------------------------------------------------

    def update(self, chunk_idx: int, update_fn: Callable[..., Stats],
               a: Any, b: Any, Qa: Any, Qb: Any) -> None:
        """Fold one chunk, closing the merge group at its boundary."""
        self.current = update_fn(self.current, a, b, Qa, Qb)
        self.end_chunk(chunk_idx)

    def end_chunk(self, chunk_idx: int) -> None:
        self._in_group += 1
        self._last_chunk = chunk_idx
        nxt = chunk_idx + 1
        if nxt % self.group_chunks == 0 or nxt == self.n_chunks:
            self._push_current()

    def flush_tail(self) -> None:
        """Close a ragged tail group at end of stream — for sources of
        unknown length (a known ``n_chunks`` closes it in end_chunk)."""
        if self._in_group:
            self._push_current()

    def _push_current(self) -> None:
        if sanitize.enabled():  # merge-group boundary: the contract's unit
            sanitize.observe(
                f"group:{self._last_chunk // self.group_chunks}",
                self.current)
        if self.sink is not None:
            self.sink(self._last_chunk // self.group_chunks, self.current)
        else:
            self._tree.push(self.current)
        self.current = self.init_fn()
        self.groups_done += 1
        self._in_group = 0

    def push_group(self, group_idx: int, stats: Stats) -> None:
        """Feed a pre-computed merge-group sum (a cluster partial or a
        device-folded group) — MUST be called in ascending group order
        with no gaps."""
        self.push_group_span(group_idx, stats, 1)

    def push_group_span(self, group_idx: int, stats: Stats,
                        span: int) -> None:
        """Feed a pre-merged span of ``span`` consecutive merge groups
        starting at ``group_idx`` (a worker-combined cluster partial).
        ``span`` must be a power of two and the span aligned
        (``group_idx % span == 0``) so it is exactly one subtree of the
        canonical pairwise reduction — then the merge is bitwise
        identical to pushing the ``span`` groups individually.  Spans
        must still arrive in ascending group order with no gaps."""
        if group_idx != self.groups_done:
            raise ValueError(
                f"merge groups must arrive in order: got {group_idx}, "
                f"expected {self.groups_done}")
        if span < 1 or span & (span - 1):
            raise ValueError(f"span must be a power of two, got {span}")
        if group_idx % span:
            raise ValueError(
                f"span of {span} groups at {group_idx} is unaligned — "
                "not a subtree of the canonical reduction")
        if self.n_chunks is not None and group_idx + span > self.n_groups:
            raise ValueError(
                f"span [{group_idx}, {group_idx + span}) overruns the "
                f"{self.n_groups}-group corpus")
        if sanitize.enabled():
            key = (f"group:{group_idx}" if span == 1
                   else f"span:{group_idx}x{span}")
            sanitize.observe(key, stats)
        self._tree.push_span(stats, span)
        self.groups_done += span

    def result(self) -> Stats:
        r = self._tree.result()
        return self.init_fn() if r is None else r

    # -- checkpointing ----------------------------------------------------

    def state(self) -> Dict[str, Any]:
        """Checkpointable pytree snapshot (jax arrays are immutable, so
        no copies are needed — only the containers are frozen)."""
        return {"current": self.current, "stack": tuple(self._tree.stack)}

    def load_state(self, state: Mapping[str, Any]) -> None:
        self.current = state["current"]
        self._tree.stack = list(state["stack"])
        # counts are implied by groups_done's binary digits (descending)
        m = self.groups_done
        self._tree.counts = [1 << i for i in reversed(range(m.bit_length()))
                             if m >> i & 1]
        if len(self._tree.counts) != len(self._tree.stack):
            raise ValueError(
                f"accumulator state has {len(self._tree.stack)} stack "
                f"entries; {self.groups_done} completed groups imply "
                f"{len(self._tree.counts)}")

    @classmethod
    def structure(cls, init_fn: Callable[[], Stats], n_chunks: Optional[int], group_chunks: int,
                  next_chunk: int) -> "SegmentedAccumulator":
        """Zero-filled accumulator with the stack shape implied by a
        resume position — the like-tree for repro.ckpt restores."""
        acc = cls(init_fn, n_chunks, group_chunks)
        acc.groups_done = cls.groups_completed(next_chunk, n_chunks, group_chunks)
        acc._in_group = max(0, next_chunk - acc.groups_done * group_chunks)
        acc._last_chunk = next_chunk - 1
        depth = PairwiseStack.depth_after(acc.groups_done)
        acc.load_state({"current": acc.current,
                        "stack": tuple(init_fn() for _ in range(depth))})
        return acc


class SpanCombiner:
    """Combiner-on-the-way-out: pre-merge runs of consecutive merge
    groups into aligned dyadic spans before they leave a worker.

    Sits between a :class:`SegmentedAccumulator` sink and the publish
    path: ``emit(g, stats)`` buffers consecutive groups of a run
    through a local :class:`PairwiseStack`; once ``span`` groups are in
    (or the run breaks — a jump to the worker's next run, or end of
    stream via :meth:`flush`), the buffered groups leave as
    ``sink(g0, count, merged)`` span partials.  Because the local stack
    is the same binary-counter reduction the coordinator would have
    run, each emitted entry is exactly one subtree of the canonical
    tree: an aligned run of 5 groups leaves as spans of 4 + 1, bitwise
    identical to 5 individual partials merged downstream.  Groups that
    start unaligned (a repair worker's arbitrary group list) pass
    through as span-1 partials — correctness never depends on the run
    shape, only fan-in does.
    """

    def __init__(self, span: int, sink: Callable[[int, int, Stats], None]):
        if span < 1 or span & (span - 1):
            raise ValueError(f"combine span must be a power of two, got {span}")
        self.span = int(span)
        self.sink = sink
        self._g0: Optional[int] = None  # run start (aligned)
        self._count = 0
        self._tree = PairwiseStack()

    def emit(self, g: int, stats: Stats) -> None:
        if self._g0 is not None and g != self._g0 + self._count:
            self.flush()  # run broke: the worker jumped to its next run
        if self._g0 is None:
            if self.span == 1 or g % self.span:
                self.sink(g, 1, stats)  # unaligned start: no combining
                return
            self._g0 = g
        self._tree.push(stats)
        self._count += 1
        if self._count == self.span:
            self.flush()

    def flush(self) -> None:
        """Publish whatever is buffered.  The local stack entries after
        ``count`` pushes mirror count's binary digits, and each is an
        aligned dyadic block (the run starts at a multiple of ``span``),
        so they emit directly as span partials."""
        if self._g0 is None:
            return
        g = self._g0
        for entry, weight in zip(self._tree.stack, self._tree.counts):
            self.sink(g, weight, entry)
            g += weight
        self._g0 = None
        self._count = 0
        self._tree = PairwiseStack()


def reduce_group_partials(partials: Mapping[int, Stats],
                          init_fn: Callable[[], Stats], n_chunks: int,
                          group_chunks: int = MERGE_GROUP_CHUNKS) -> Stats:
    """Deterministic fixed-order tree-reduce of per-group partials:
    ``partials`` maps group index → stats and must cover every group.
    Reproduces the single-process segmented accumulation bitwise
    regardless of which worker computed which group or in what order
    they completed.  (The cluster coordinator streams the same tree
    from disk instead — see ``ClusterCoordinator`` — so only O(log G)
    partials are ever resident there; this eager form remains for
    in-memory partial sets.)"""
    acc = SegmentedAccumulator(init_fn, n_chunks, group_chunks)
    for g in range(acc.n_groups):
        if g not in partials:
            raise ValueError(f"merge group {g} missing from partial set")
        acc.push_group(g, partials[g])
    return acc.result()
