"""Fused project+accumulate Pallas kernel for the range-finder pass.

The dominant data pass of Algorithm 1 (lines 7-8) updates, per row
chunk, ``ΔYa = Aᵀ(B Qb)`` and ``ΔYb = Bᵀ(A Qa)``.  Issued as separate
matmuls that is four ``pallas_call``s per chunk, with each view read
from HBM twice and the projected activations P making an HBM
round-trip.  This kernel fuses one view's update — the projection tile
``P = B Qb`` stays in a VMEM scratch accumulator and ``ΔYa = AᵀP`` is
emitted directly — the same fusion :mod:`repro.kernels.projgram`
applies to the final pass.  A full ``power_pass_chunk`` is then two
``pallas_call``s, each reading A and B exactly once.

Column-bucketed grid (da_t, n_t, db_t), output buckets outermost and
the contraction (db) innermost:

- the ΔY output columns (the da rows of ΔY) are split into buckets of
  ``bda`` with ``bda·k̃p ≤ VMEM_BLOCK_ELEMS`` (the shared per-buffer
  budget from :mod:`repro.kernels.matmul`);
- per bucket, per row tile, ``P = Σ_db B_tile Qb_tile`` accumulates in
  VMEM scratch; on the last db step ``ΔY_bucket += A_bucketᵀ P``;
- each bucket's (bda, k̃p) block has an index map constant in (n_t,
  db_t), so it stays VMEM-resident across all row steps of its bucket
  and is written back to HBM exactly once.

When ``dap·k̃p`` fits a single block the bucket covers all of ΔY and
the schedule is identical to the old 2-axis grid — small shapes lose
nothing.  Arbitrarily large ``da`` (Europarl's d = 2^19) now runs
fused, and Halko et al. 2011 guarantee blockwise accumulation is
exact.

TWO SCHEDULES, ONE COST MODEL (be honest about it).  The bucketed
*recompute* schedule above re-reads B and Q and re-accumulates the
projection ``P = B Qb`` once per bucket, so a chunk costs
``n_buckets·proj + acc`` FLOPs versus the unfused pair's
``proj + acc`` (which instead pays the P HBM round-trip).  That wins
when ``n_buckets`` is small and/or the projection is cheap relative to
accumulation (db ≪ da); at Europarl's da = db with ~2k buckets the
recompute dominates.  The *staged* schedule
(:func:`power_project_accumulate` with ``schedule="staged"``) removes
the recompute: phase 1 (``proj_stage`` kernel, grid (n_t, db_t))
computes each row tile's ``P = B Qb`` exactly once, accumulating f32
directly in the (bn, k̃p) output block (index map constant in the
inner contraction axis, so the block stays VMEM-resident and hits HBM
once); phase 2 (``powerpass_sweep`` kernel, grid (da_t, n_t)) sweeps
the ΔY buckets reloading the staged P tiles instead of recomputing
them.  Cost: ``proj + acc`` FLOPs — bucket-count-independent — plus
one ``n×k̃`` f32 HBM round-trip and ``n_buckets`` re-reads of P.  The
two schedules issue bitwise-identical f32 dot sequences (P is staged
in full f32 precision), so the choice is pure performance: the
crossover rule (:func:`choose_powerpass_schedule`, built on
:func:`repro.kernels.matmul.pick_schedule`) compares the modelled
``max(flops/roofline, bytes)`` of each schedule per shape, and an
autotuned ``op="powerpass-staged"`` cache entry (measured by
``benchmarks/sweep_blocks.py``) overrides the model.  The unfused
matmul-pair fallback remains only for genuinely degenerate shapes —
``k̃p > VMEM_BLOCK_ELEMS/128`` (= 8192), where even a 128-row block of
ΔY or P blows the budget and fusion is pointless (k̃ ~ d).

Block caps resolve from the autotune cache (``op="powerpass"``, keyed
by the padded (n, db, k̃) problem plus the bucketed dap) — see
:func:`repro.kernels.autotune.autotune_powerpass` and
``benchmarks/sweep_blocks.py``.  The staged schedule resolves blocks
through the *same* lookup, so both schedules tile identically and
parity is structural.

Ω-RESIDENCY ACCOUNTING (the ``omega="seeded"`` variant): with a
materialized sketch the power pass holds Ω = ``d·k̃`` elements resident
in HBM for the whole fit (Europarl: 2^19 × 2060 ≈ 4.3 GB f32, or
2.2 GB bf16) and every chunk's kernel launch streams ``bdb·k̃p`` Q
tiles from HBM — ``d·k̃·bytes`` of Ω reads per chunk per bucket, on
top of the A/B reads.  :func:`power_project_accumulate_seeded` instead
regenerates each Q tile inside the kernel from a 64-bit seed
(:mod:`repro.kernels.rand`): Ω's HBM residency drops from ``d·k̃·bytes``
to 8 bytes and its read traffic to zero, at the cost of ~40 uint32
ALU ops per generated element (Threefry-2x32 + Box–Muller) — VPU work
that overlaps the MXU dot on real hardware.  Per power-pass chunk the
HBM bytes are then ``n·(da+db)·bytes`` (the data reads) instead of
``n·(da+db)·bytes + n_buckets·d·k̃·bytes`` with materialized Ω tiles,
and cluster rounds ship the 8-byte seed instead of the 4 GB array.
Under the staged schedule the same applies per *phase*: the seeded
stage kernel generates each Ω tile exactly once (phase 1 is the only
consumer — the sweep touches no Ω at all), which is the seeded analogue
of removing the materialized-Ω bucket re-reads.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import autotune, rand
from .matmul import (NN, TN, _pad2, _pick_block, _round_up, mxu_dot,
                     pallas_matmul, pick_schedule, vmem_row_cap)
from .plan import BlockDef, KernelPlan, ScalarDef, ScratchDef, launch_args


def _powerpass_kernel(a_ref, b_ref, q_ref, y_ref, p_acc, *, n_k_steps: int):
    """y_bucket += a_bucketᵀ(b q); grid (da_t, n_t, db_t), db innermost."""
    n_step = pl.program_id(1)
    k_step = pl.program_id(2)

    @pl.when(jnp.logical_and(n_step == 0, k_step == 0))
    def _init_y():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(k_step == 0)
    def _init_p():
        p_acc[...] = jnp.zeros_like(p_acc)

    p_acc[...] += mxu_dot(b_ref[...], q_ref[...], NN)

    @pl.when(k_step == n_k_steps - 1)
    def _accumulate():
        # aᵀ p without materializing aᵀ
        y_ref[...] += mxu_dot(a_ref[...], p_acc[...], TN).astype(y_ref.dtype)


def resolve_blocks(
    np_: int, dap: int, dbp: int, ktp: int,
    block_n: int, block_db: int, block_da: int,
) -> tuple[int, int, int] | None:
    """Effective (bn, bdb, bda) for the bucketed grid, or ``None`` when
    the shape is degenerate (k̃p > 8192: no 128-row block fits VMEM).

    Every block obeys the shared budget: bda·k̃p (ΔY bucket), bn·k̃p
    (P scratch), bn·bda (A tile) and bdb·k̃p (Q tile) all stay within
    ``VMEM_BLOCK_ELEMS``.  A bucket covering all of dap is preferred
    when it fits, reproducing the unbucketed single-block schedule.
    """
    row_cap = vmem_row_cap(ktp)
    if row_cap < 128:
        return None
    cap_da = min(block_da, row_cap)
    bda = dap if dap <= cap_da else _pick_block(dap, cap_da)
    bdb = _pick_block(dbp, min(block_db, row_cap))
    bn = _pick_block(np_, min(block_n, row_cap, vmem_row_cap(bda), vmem_row_cap(bdb)))
    return bn, bdb, bda


def plan_powerpass(n: int, da: int, db: int, kt: int, dtype, *,
                   block_n: int | None = None, block_db: int | None = None,
                   block_da: int | None = None) -> KernelPlan | None:
    """Launch plan for the fused project+accumulate kernel, or ``None``
    for the degenerate unfused-fallback shapes (k̃p > 8192).  Resolves
    blocks exactly as the wrapper does (autotune cache, then the shared
    VMEM budget) — the static checker consumes the same plan."""
    dap = _round_up(da, 128)
    ktp = _round_up(kt, 128)
    np_, dbp = _round_up(n, 128), _round_up(db, 128)
    if block_n is None or block_db is None or block_da is None:
        tuned = autotune.lookup("powerpass", np_, dbp, ktp, dtype, extra=dap)
        block_n = tuned[0] if block_n is None else block_n
        block_db = tuned[1] if block_db is None else block_db
        block_da = tuned[2] if block_da is None else block_da
    blocks = resolve_blocks(np_, dap, dbp, ktp, block_n, block_db, block_da)
    if blocks is None:
        return None
    bn, bdb, bda = blocks
    in_dt = str(jnp.dtype(dtype))
    return KernelPlan(
        name="powerpass",
        grid=(dap // bda, np_ // bn, dbp // bdb),
        in_specs=(
            BlockDef((bn, bda), lambda j, i, k: (i, j), (np_, dap), in_dt),
            BlockDef((bn, bdb), lambda j, i, k: (i, k), (np_, dbp), in_dt),
            BlockDef((bdb, ktp), lambda j, i, k: (k, 0), (dbp, ktp), in_dt),
        ),
        out_specs=(
            BlockDef((bda, ktp), lambda j, i, k: (j, 0), (dap, ktp),
                     "float32"),
        ),
        scratch=(ScratchDef((bn, ktp), "float32"),),
        out_shape=((da, kt),),
        accum_outputs=(0,),
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_db", "block_da", "schedule",
                     "interpret"),
)
def power_project_accumulate(
    a: jax.Array,
    b: jax.Array,
    q: jax.Array,
    *,
    block_n: int | None = None,
    block_db: int | None = None,
    block_da: int | None = None,
    schedule: str | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Return ΔY = aᵀ (b @ q) with a and b each read from HBM once.

    a: (n, da), b: (n, db), q: (db, k̃) → (da, k̃) in f32.

    ``block_da`` caps the output-column bucket (rows of ΔY resident in
    VMEM at once); ``None`` caps resolve from the autotune cache
    (``op="powerpass"``) and then from the shared VMEM budget.

    ``schedule`` picks ``"recompute"`` (P re-accumulated per bucket) or
    ``"staged"`` (P staged through HBM once, buckets reload it); the
    default ``None`` resolves per shape via
    :func:`choose_powerpass_schedule`.  Both schedules are bitwise
    equal — P is carried in full f32 precision either way.
    """
    n, da = a.shape
    n2, db = b.shape
    db2, kt = q.shape
    assert n == n2, f"row mismatch {n} vs {n2}"
    assert db == db2, f"contraction mismatch {db} vs {db2}"

    plan = plan_powerpass(n, da, db, kt, a.dtype, block_n=block_n,
                          block_db=block_db, block_da=block_da)
    if plan is None:
        # k̃p > 8192: even a 128-row block blows VMEM — unfused pair
        p = pallas_matmul(b, q, out_dtype=jnp.float32, interpret=interpret)
        return pallas_matmul(a, p, transpose_lhs=True, out_dtype=jnp.float32,
                             interpret=interpret)
    if schedule is None:
        schedule = choose_powerpass_schedule(
            n, da, db, kt, a.dtype, block_n=block_n, block_db=block_db,
            block_da=block_da)
    if schedule == "staged":
        plans = plan_powerpass_staged(n, da, db, kt, a.dtype,
                                      block_n=block_n, block_db=block_db,
                                      block_da=block_da)
        if plans is not None:
            stage, sweep = plans
            ap = _pad2(a, *sweep.in_specs[0].padded)
            bp = _pad2(b, *stage.in_specs[0].padded)
            qp = _pad2(q, *stage.in_specs[1].padded)
            out = _staged_call(ap, bp, qp, stage, sweep, interpret)
            return out[:da, :kt]
    ap = _pad2(a, *plan.in_specs[0].padded)
    bp = _pad2(b, *plan.in_specs[1].padded)
    qp = _pad2(q, *plan.in_specs[2].padded)

    out = pl.pallas_call(
        functools.partial(_powerpass_kernel, n_k_steps=plan.grid[2]),
        **launch_args(plan),
        interpret=interpret,
    )(ap, bp, qp)
    return out[:da, :kt]


def _powerpass_seeded_kernel(seed_ref, a_ref, b_ref, y_ref, p_acc, *,
                             n_k_steps: int, bdb: int, ktp: int,
                             db: int, kt: int, q_dtype):
    """y_bucket += a_bucketᵀ(b Ω_tile(seed)); Ω never touches HBM.

    Identical schedule to :func:`_powerpass_kernel`; the (bdb, k̃p) Q
    tile is regenerated from the SMEM seed at global row offset
    ``k_step·bdb`` instead of being streamed from HBM.  The tile is
    generated in f32, masked to zero outside the logical (db, k̃)
    bounds, and cast once to the data dtype — bitwise identical to a
    zero-padded materialized ``rand.dense_omega`` tile.
    """
    n_step = pl.program_id(1)
    k_step = pl.program_id(2)

    @pl.when(jnp.logical_and(n_step == 0, k_step == 0))
    def _init_y():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(k_step == 0)
    def _init_p():
        p_acc[...] = jnp.zeros_like(p_acc)

    q_tile = rand.normal_tile(
        seed_ref[0], seed_ref[1],
        (k_step * bdb).astype(rand.U32), rand.U32(0),
        (bdb, ktp), row_limit=db, col_limit=kt,
    ).astype(q_dtype)
    p_acc[...] += mxu_dot(b_ref[...], q_tile, NN)

    @pl.when(k_step == n_k_steps - 1)
    def _accumulate():
        y_ref[...] += mxu_dot(a_ref[...], p_acc[...], TN).astype(y_ref.dtype)


def plan_powerpass_seeded(n: int, da: int, db: int, kt: int, dtype, *,
                          block_n: int | None = None,
                          block_db: int | None = None,
                          block_da: int | None = None) -> KernelPlan | None:
    """Launch plan for the seeded fused kernel: the materialized plan's
    geometry with the Q operand replaced by a (2,)-uint32 SMEM seed
    scalar — Ω has no HBM block, which is the point."""
    base = plan_powerpass(n, da, db, kt, dtype, block_n=block_n,
                          block_db=block_db, block_da=block_da)
    if base is None:
        return None
    return dataclasses.replace(
        base,
        name="powerpass_seeded",
        in_specs=base.in_specs[:2],
        scalars=(ScalarDef((2,), "uint32"),),
    )


@functools.partial(
    jax.jit,
    static_argnames=("kt", "q_dtype", "block_n", "block_db", "block_da",
                     "schedule", "interpret"),
)
def power_project_accumulate_seeded(
    a: jax.Array,
    b: jax.Array,
    seed: jax.Array,
    *,
    kt: int,
    q_dtype=None,
    block_n: int | None = None,
    block_db: int | None = None,
    block_da: int | None = None,
    schedule: str | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Return ΔY = aᵀ (b @ Ω(seed)) with Ω generated inside the kernel.

    a: (n, da), b: (n, db), seed: (2,) uint32 → (da, k̃) in f32.
    Bitwise identical to ``power_project_accumulate(a, b, Q)`` where
    ``Q = rand.dense_omega(seed, db, kt, q_dtype)`` — the materialized
    oracle — because the in-kernel tiles are the same counter-PRNG
    values cast the same way.  Only the degenerate unfused fallback
    (k̃p > 8192) materializes Ω transiently.

    ``schedule`` as in :func:`power_project_accumulate`; under
    ``"staged"`` each Ω tile is generated exactly once, in phase 1.
    """
    n, da = a.shape
    n2, db = b.shape
    assert n == n2, f"row mismatch {n} vs {n2}"
    q_dtype = a.dtype if q_dtype is None else jnp.dtype(q_dtype)

    plan = plan_powerpass_seeded(n, da, db, kt, a.dtype, block_n=block_n,
                                 block_db=block_db, block_da=block_da)
    if plan is None:
        # k̃p > 8192: unfused pair; Ω materialized transiently (documented)
        q = rand.dense_omega(seed, db, kt, q_dtype)
        p = pallas_matmul(b, q, out_dtype=jnp.float32, interpret=interpret)
        return pallas_matmul(a, p, transpose_lhs=True, out_dtype=jnp.float32,
                             interpret=interpret)
    if schedule is None:
        schedule = choose_powerpass_schedule(
            n, da, db, kt, a.dtype, block_n=block_n, block_db=block_db,
            block_da=block_da)
    if schedule == "staged":
        plans = plan_powerpass_staged(n, da, db, kt, a.dtype,
                                      block_n=block_n, block_db=block_db,
                                      block_da=block_da, seeded=True)
        if plans is not None:
            stage, sweep = plans
            ap = _pad2(a, *sweep.in_specs[0].padded)
            bp = _pad2(b, *stage.in_specs[0].padded)
            bd = stage.in_specs[0].shape[1]
            ktp = stage.out_specs[0].shape[1]
            out = _staged_call(
                ap, bp, jnp.asarray(seed, jnp.uint32), stage, sweep,
                interpret,
                seeded_kwargs=dict(bd=bd, ktp=ktp, d=db, kt=kt,
                                   q_dtype=q_dtype))
            return out[:da, :kt]
    ap = _pad2(a, *plan.in_specs[0].padded)
    bp = _pad2(b, *plan.in_specs[1].padded)
    bdb = plan.in_specs[1].shape[1]
    ktp = plan.out_specs[0].shape[1]

    out = pl.pallas_call(
        functools.partial(_powerpass_seeded_kernel, n_k_steps=plan.grid[2],
                          bdb=bdb, ktp=ktp, db=db, kt=kt, q_dtype=q_dtype),
        **launch_args(plan),
        interpret=interpret,
    )(jnp.asarray(seed, jnp.uint32), ap, bp)
    return out[:da, :kt]


# --------------------------------------------------------------------------
# staged (P-reuse) schedule: stage P through HBM once, sweep buckets
# --------------------------------------------------------------------------


def _proj_stage_kernel(x_ref, q_ref, p_ref):
    """Phase 1: P = Σ_k x_tile q_tile, f32, accumulated in the output
    block itself; grid (n_t, k_t) with the contraction innermost.  The
    (bn, k̃p) block's index map is constant in k, so it stays
    VMEM-resident across the contraction and is written to HBM exactly
    once — the one ``n×k̃`` round-trip the staged schedule pays."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        p_ref[...] = jnp.zeros_like(p_ref)

    p_ref[...] += mxu_dot(x_ref[...], q_ref[...], NN)


def _proj_stage_seeded_kernel(seed_ref, x_ref, p_ref, *,
                              bd: int, ktp: int, d: int, kt: int, q_dtype):
    """Seeded phase 1: the (bd, k̃p) Ω tile is regenerated from the SMEM
    seed at global row offset ``k_step·bd`` — each tile is generated
    exactly once per chunk, since only phase 1 touches Ω at all."""
    k_step = pl.program_id(1)

    @pl.when(k_step == 0)
    def _init():
        p_ref[...] = jnp.zeros_like(p_ref)

    q_tile = rand.normal_tile(
        seed_ref[0], seed_ref[1],
        (k_step * bd).astype(rand.U32), rand.U32(0),
        (bd, ktp), row_limit=d, col_limit=kt,
    ).astype(q_dtype)
    p_ref[...] += mxu_dot(x_ref[...], q_tile, NN)


def _powerpass_sweep_kernel(a_ref, p_ref, y_ref):
    """Phase 2: y_bucket += a_bucketᵀ p; grid (da_t, n_t), rows
    innermost.  Reloads the staged (bn, k̃p) P tiles once per bucket
    instead of recomputing them — same contraction order and f32
    accumulation as the recompute schedule's last-k step, so the two
    schedules are bitwise equal."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    # aᵀ p without materializing aᵀ
    y_ref[...] += mxu_dot(a_ref[...], p_ref[...], TN)


def plan_proj_stage(n: int, d: int, kt: int, dtype, *,
                    bn: int | None = None,
                    bd: int | None = None) -> KernelPlan | None:
    """Launch plan for the phase-1 stage kernel (P = X Q, f32).

    ``bn``/``bd`` are *resolved* blocks when given (the staged composite
    passes the recompute plan's blocks verbatim so both schedules tile
    identically); ``None`` resolves standalone from the shared VMEM
    budget — the entry point the registry and the sharded
    collective-fused path use.
    """
    np_, dp, ktp = _round_up(n, 128), _round_up(d, 128), _round_up(kt, 128)
    row_cap = vmem_row_cap(ktp)
    if row_cap < 128:
        return None
    if bd is None:
        bd = _pick_block(dp, min(512, row_cap))
    if bn is None:
        bn = _pick_block(np_, min(256, row_cap, vmem_row_cap(bd)))
    in_dt = str(jnp.dtype(dtype))
    return KernelPlan(
        name="proj_stage",
        grid=(np_ // bn, dp // bd),
        in_specs=(
            BlockDef((bn, bd), lambda i, k: (i, k), (np_, dp), in_dt),
            BlockDef((bd, ktp), lambda i, k: (k, 0), (dp, ktp), in_dt),
        ),
        out_specs=(
            BlockDef((bn, ktp), lambda i, k: (i, 0), (np_, ktp), "float32"),
        ),
        scratch=(),
        out_shape=((n, kt),),
        accum_outputs=(0,),
    )


def plan_proj_stage_seeded(n: int, d: int, kt: int, dtype, *,
                           bn: int | None = None,
                           bd: int | None = None) -> KernelPlan | None:
    """Seeded phase-1 plan: the stage plan's geometry with the Q
    operand replaced by a (2,)-uint32 SMEM seed scalar."""
    base = plan_proj_stage(n, d, kt, dtype, bn=bn, bd=bd)
    if base is None:
        return None
    return dataclasses.replace(
        base,
        name="proj_stage_seeded",
        in_specs=base.in_specs[:1],
        scalars=(ScalarDef((2,), "uint32"),),
    )


def plan_powerpass_sweep(n: int, da: int, kt: int, dtype, *,
                         bn: int | None = None,
                         bda: int | None = None,
                         p_dtype="float32") -> KernelPlan | None:
    """Launch plan for the phase-2 sweep kernel (ΔY = AᵀP, bucketed).

    ``dtype`` is A's dtype; ``p_dtype`` is the staged P's (f32 inside
    the composite, the compute dtype on the sharded collective-fused
    path where P crosses a psum).  Blocks as in :func:`plan_proj_stage`.
    """
    np_, dap, ktp = _round_up(n, 128), _round_up(da, 128), _round_up(kt, 128)
    row_cap = vmem_row_cap(ktp)
    if row_cap < 128:
        return None
    if bda is None:
        bda = dap if dap <= row_cap else _pick_block(dap, row_cap)
    if bn is None:
        bn = _pick_block(np_, min(256, row_cap, vmem_row_cap(bda)))
    in_dt = str(jnp.dtype(dtype))
    return KernelPlan(
        name="powerpass_sweep",
        grid=(dap // bda, np_ // bn),
        in_specs=(
            BlockDef((bn, bda), lambda j, i: (i, j), (np_, dap), in_dt),
            BlockDef((bn, ktp), lambda j, i: (i, 0), (np_, ktp),
                     str(jnp.dtype(p_dtype))),
        ),
        out_specs=(
            BlockDef((bda, ktp), lambda j, i: (j, 0), (dap, ktp), "float32"),
        ),
        scratch=(),
        out_shape=((da, kt),),
        accum_outputs=(0,),
    )


def plan_powerpass_staged(
    n: int, da: int, db: int, kt: int, dtype, *,
    block_n: int | None = None, block_db: int | None = None,
    block_da: int | None = None, seeded: bool = False,
) -> tuple[KernelPlan, KernelPlan] | None:
    """(stage, sweep) plan pair for the staged schedule, or ``None`` on
    the degenerate shapes.  Blocks are extracted from the *recompute*
    plan for the same shape (same autotune lookup, same VMEM budget),
    so staged and recompute tile identically — the structural basis of
    their bitwise parity."""
    base = plan_powerpass(n, da, db, kt, dtype, block_n=block_n,
                          block_db=block_db, block_da=block_da)
    if base is None:
        return None
    bn, bda = base.in_specs[0].shape
    bdb = base.in_specs[1].shape[1]
    if seeded:
        stage = plan_proj_stage_seeded(n, db, kt, dtype, bn=bn, bd=bdb)
    else:
        stage = plan_proj_stage(n, db, kt, dtype, bn=bn, bd=bdb)
    sweep = plan_powerpass_sweep(n, da, kt, dtype, bn=bn, bda=bda)
    if stage is None or sweep is None:
        return None
    return stage, sweep


def choose_powerpass_schedule(
    n: int, da: int, db: int, kt: int, dtype, *,
    block_n: int | None = None, block_db: int | None = None,
    block_da: int | None = None,
) -> str:
    """``"staged"`` or ``"recompute"`` for one powerpass shape.

    Order of authority: an autotuned schedule entry
    (``op="powerpass-staged"``, written by
    :func:`repro.kernels.autotune.autotune_powerpass_staged`), then the
    analytic roofline crossover (:func:`repro.kernels.matmul.pick_schedule`)
    over the KernelPlan-derived cost model — the same model the obs
    roofline counters charge, so the report's numbers explain the
    choice.  Single-bucket shapes always recompute: staged would add
    the P round-trip and remove nothing.
    """
    np_, dap = _round_up(n, 128), _round_up(da, 128)
    dbp, ktp = _round_up(db, 128), _round_up(kt, 128)
    tuned = autotune.lookup_schedule("powerpass-staged",
                                     (np_, dbp, ktp, dap), dtype)
    if tuned is not None:
        return tuned
    base = plan_powerpass(n, da, db, kt, dtype, block_n=block_n,
                          block_db=block_db, block_da=block_da)
    if base is None or base.grid[0] == 1:
        return "recompute"
    plans = plan_powerpass_staged(n, da, db, kt, dtype, block_n=block_n,
                                  block_db=block_db, block_da=block_da)
    if plans is None:
        return "recompute"
    from repro.obs.cost import plan_cost  # deferred: obs imports kernels.plan

    rec = plan_cost(base)
    stage, sweep = (plan_cost(p) for p in plans)
    return pick_schedule({
        "recompute": (rec["flops"], rec["bytes"]),
        "staged": (stage["flops"] + sweep["flops"],
                   stage["bytes"] + sweep["bytes"]),
    })


def _staged_call(ap, bp, qp_or_seed, stage: KernelPlan, sweep: KernelPlan,
                 interpret: bool, *, seeded_kwargs=None) -> jax.Array:
    """Launch the (stage, sweep) pallas_call pair; returns padded ΔY.
    The staged P stays padded (np_, k̃p) f32 between the phases — no
    host-side slicing, one HBM round-trip."""
    if seeded_kwargs is None:
        body = _proj_stage_kernel
        operands = (bp, qp_or_seed)
    else:
        body = functools.partial(_proj_stage_seeded_kernel, **seeded_kwargs)
        operands = (qp_or_seed, bp)  # seed scalar leads the blocked operands
    p = pl.pallas_call(
        body,
        **launch_args(stage),
        interpret=interpret,
    )(*operands)
    return pl.pallas_call(
        _powerpass_sweep_kernel,
        **launch_args(sweep),
        interpret=interpret,
    )(ap, p)


@functools.partial(jax.jit, static_argnames=("interpret",))
def proj_stage(x: jax.Array, q: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """Standalone phase-1 stage: P = x @ q in f32, staged blockwise.

    x: (n, d), q: (d, k̃) → (n, k̃) f32.  Used by the sharded
    collective-fused path (partial P on the local feature shard, psum
    at the phase boundary) and as the registry entry point for the
    ``proj_stage`` contract checks; the staged composite inlines the
    same kernel with the recompute plan's blocks.
    """
    n, d = x.shape
    d2, kt = q.shape
    assert d == d2, f"contraction mismatch {d} vs {d2}"
    plan = plan_proj_stage(n, d, kt, x.dtype)
    if plan is None:
        return pallas_matmul(x, q, out_dtype=jnp.float32, interpret=interpret)
    xp = _pad2(x, *plan.in_specs[0].padded)
    qp = _pad2(q, *plan.in_specs[1].padded)
    p = pl.pallas_call(
        _proj_stage_kernel,
        **launch_args(plan),
        interpret=interpret,
    )(xp, qp)
    return p[:n, :kt]


@functools.partial(jax.jit, static_argnames=("kt", "q_dtype", "interpret"))
def proj_stage_seeded(x: jax.Array, seed: jax.Array, *, kt: int,
                      q_dtype=None, interpret: bool = False) -> jax.Array:
    """Standalone seeded phase-1 stage: P = x @ Ω(seed) in f32, each Ω
    tile generated in-kernel exactly once.  Bitwise identical to
    ``proj_stage(x, rand.dense_omega(seed, d, kt, q_dtype))``."""
    n, d = x.shape
    q_dtype = x.dtype if q_dtype is None else jnp.dtype(q_dtype)
    plan = plan_proj_stage_seeded(n, d, kt, x.dtype)
    if plan is None:
        q = rand.dense_omega(seed, d, kt, q_dtype)
        return pallas_matmul(x, q, out_dtype=jnp.float32, interpret=interpret)
    xp = _pad2(x, *plan.in_specs[0].padded)
    bd = plan.in_specs[0].shape[1]
    ktp = plan.out_specs[0].shape[1]
    p = pl.pallas_call(
        functools.partial(_proj_stage_seeded_kernel, bd=bd, ktp=ktp,
                          d=d, kt=kt, q_dtype=q_dtype),
        **launch_args(plan),
        interpret=interpret,
    )(jnp.asarray(seed, jnp.uint32), xp)
    return p[:n, :kt]


@functools.partial(jax.jit, static_argnames=("interpret",))
def powerpass_sweep(a: jax.Array, p: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """Standalone phase-2 sweep: ΔY = aᵀ p, reloading staged P tiles
    per ΔY bucket.  a: (n, da), p: (n, k̃) → (da, k̃) f32.  ``p`` may be
    f32 (local staged composite) or the compute dtype (the sharded path,
    where P crosses the ``col_axis`` psum between the phases)."""
    n, da = a.shape
    n2, kt = p.shape
    assert n == n2, f"row mismatch {n} vs {n2}"
    plan = plan_powerpass_sweep(n, da, kt, a.dtype, p_dtype=str(p.dtype))
    if plan is None:
        return pallas_matmul(a, p, transpose_lhs=True, out_dtype=jnp.float32,
                             interpret=interpret)
    ap = _pad2(a, *plan.in_specs[0].padded)
    pp = _pad2(p, *plan.in_specs[1].padded)
    out = pl.pallas_call(
        _powerpass_sweep_kernel,
        **launch_args(plan),
        interpret=interpret,
    )(ap, pp)
    return out[:da, :kt]
