"""Fused project+gram Pallas kernel: one X read → (P = XQ, C = PᵀP).

Final-pass hot spot (Algorithm 1 lines 15-17): the projected covariance
``C = Qᵀ Xᵀ X Q`` is computed as the Gram of ``P = X Q``.  Fusing both
matmuls into one kernel means X is read from HBM exactly once per pass
and P never makes an HBM round-trip before the Gram — the remaining P
write-out is only needed for the cross term F (done as a TN matmul on
the emitted Pa, Pb).

Column-bucketed grid (kt_t, n_t, d_t), C-column buckets outermost and
the contraction (d) innermost:

- the k̃ output columns of C are split into buckets of ``bc`` with
  ``k̃p·bc ≤ VMEM_BLOCK_ELEMS`` (the shared per-buffer budget from
  :mod:`repro.kernels.matmul`);
- per bucket, per row tile, the FULL P tile (bn, k̃p) accumulates in
  VMEM scratch over the d steps; on the last d step the tile is
  written out and ``C[:, bucket] += Pᵀ P[:, bucket]`` lands in the
  (k̃p, bc) block, whose index map is constant in (n_t, d_t) — each
  bucket's block stays VMEM-resident across row steps and hits HBM
  once;
- the P output tile is rewritten (identically) once per bucket so its
  buffer never carries stale data across bucket revisits.

When ``k̃p² ≤ VMEM_BLOCK_ELEMS`` (k̃p ≤ 1024) a single bucket covers C
and the schedule matches the old 2-axis grid exactly.  Larger sketches
(the paper's Europarl run has k̃ = 2060) now stay fused.

TWO SCHEDULES, ONE COST MODEL.  The bucketed *recompute* schedule
above re-reads X and re-accumulates ``P = XQ`` once per C-column
bucket — ``n_buckets·proj + gram`` FLOPs versus the unfused pair's
single projection plus P round-trip.  The bucket count here is only
``k̃p/bc`` (17 for Europarl, not thousands), but for d ≫ k̃ the
projection dominates.  The *staged* schedule (``schedule="staged"``,
requires ``p_dtype=float32``) reuses the powerpass phase-1 kernel
(:func:`repro.kernels.powerpass.proj_stage`): P is projected exactly
once into its f32 output buffer — which the final pass has to emit
anyway for the cross term F — and phase 2 (the ``gram_sweep`` kernel,
grid (kt_t, n_t)) computes ``C[:, bucket] += Pᵀ P[:, bucket]`` reloading
the staged P tiles.  Cost: ``proj + gram`` FLOPs plus ``n_buckets``
re-reads of P, with no extra round-trip at all (the P write-out was
already part of the contract).  Both schedules issue bitwise-identical
f32 dot sequences; :func:`choose_projgram_schedule` picks per shape via
the shared roofline crossover
(:func:`repro.kernels.matmul.pick_schedule`), overridden by autotuned
``op="projgram-staged"`` cache entries.  The unfused matmul-pair
fallback remains only for degenerate ``k̃p > 8192`` where even a
128-column C block (or a 128-row P/Q tile) blows the budget.

Block caps resolve from the autotune cache (``op="projgram"``) — see
:func:`repro.kernels.autotune.autotune_projgram` and
``benchmarks/sweep_blocks.py``.  The staged schedule resolves blocks
through the same lookup, so both schedules tile identically.
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
from .powerpass import (_proj_stage_kernel, _proj_stage_seeded_kernel,
                        plan_proj_stage, plan_proj_stage_seeded)


def _projgram_kernel(x_ref, q_ref, p_ref, c_ref, acc_ref,
                     *, n_d_steps: int, block_c: int):
    """grid (kt_t, n_t, d_t), d innermost.  acc_ref: (bn, k̃p) P tile."""
    c_step = pl.program_id(0)
    n_step = pl.program_id(1)
    d_step = pl.program_id(2)

    @pl.when(jnp.logical_and(n_step == 0, d_step == 0))
    def _init_c():
        c_ref[...] = jnp.zeros_like(c_ref)

    @pl.when(d_step == 0)
    def _init_p():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(x_ref[...], q_ref[...], NN)

    @pl.when(d_step == n_d_steps - 1)
    def _flush():
        p = acc_ref[...]
        p_ref[...] = p.astype(p_ref.dtype)
        pj = acc_ref[:, pl.ds(c_step * block_c, block_c)]
        # Pᵀ P[:, bucket] on the MXU
        c_ref[...] += mxu_dot(p, pj, TN).astype(c_ref.dtype)


def resolve_blocks(
    np_: int, dp: int, ktp: int,
    block_n: int, block_d: int, block_c: int,
) -> tuple[int, int, int] | None:
    """Effective (bn, bd, bc) for the bucketed grid, or ``None`` when
    the shape is degenerate (k̃p > 8192).  bn·k̃p (P tile/scratch),
    bd·k̃p (Q tile) and k̃p·bc (C bucket) all stay within the shared
    ``VMEM_BLOCK_ELEMS`` budget; a bucket covering all of k̃p is
    preferred when it fits (single-block schedule for k̃p ≤ 1024)."""
    row_cap = vmem_row_cap(ktp)
    if row_cap < 128:
        return None
    cap_c = min(block_c, row_cap)
    bc = ktp if ktp <= cap_c else _pick_block(ktp, cap_c)
    bn = _pick_block(np_, min(block_n, row_cap))
    bd = _pick_block(dp, min(block_d, row_cap))
    return bn, bd, bc


def plan_projgram(n: int, d: int, kt: int, dtype, *,
                  block_n: int | None = None, block_d: int | None = None,
                  block_c: int | None = None,
                  p_dtype=jnp.float32) -> KernelPlan | None:
    """Launch plan for the fused project+gram kernel, or ``None`` for
    the degenerate unfused-fallback shapes (k̃p > 8192).  Block caps
    resolve exactly as in the wrapper (autotune cache, then the shared
    VMEM budget) — the static checker consumes the same plan."""
    np_, dp, ktp = _round_up(n, 128), _round_up(d, 128), _round_up(kt, 128)
    if block_n is None or block_d is None or block_c is None:
        tuned = autotune.lookup("projgram", np_, dp, ktp, dtype)
        block_n = tuned[0] if block_n is None else block_n
        block_d = tuned[1] if block_d is None else block_d
        block_c = tuned[2] if block_c is None else block_c
    blocks = resolve_blocks(np_, dp, ktp, block_n, block_d, block_c)
    if blocks is None:
        return None
    bn, bd, bc = blocks
    in_dt = str(jnp.dtype(dtype))
    return KernelPlan(
        name="projgram",
        grid=(ktp // bc, np_ // bn, dp // bd),
        in_specs=(
            BlockDef((bn, bd), lambda j, i, k: (i, k), (np_, dp), in_dt),
            BlockDef((bd, ktp), lambda j, i, k: (k, 0), (dp, ktp), in_dt),
        ),
        out_specs=(
            BlockDef((bn, ktp), lambda j, i, k: (i, 0), (np_, ktp),
                     str(jnp.dtype(p_dtype))),
            BlockDef((ktp, bc), lambda j, i, k: (0, j), (ktp, ktp),
                     "float32"),
        ),
        scratch=(ScratchDef((bn, ktp), "float32"),),
        out_shape=((n, kt), (kt, kt)),
        accum_outputs=(1,),
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_d", "block_c", "schedule", "interpret",
                     "p_dtype"),
)
def projgram(
    x: jax.Array,
    q: jax.Array,
    *,
    block_n: int | None = None,
    block_d: int | None = None,
    block_c: int | None = None,
    schedule: str | None = None,
    p_dtype=jnp.float32,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Return (P = x@q, C = PᵀP) with x read once per C-column bucket.

    x: (n, d), q: (d, k̃).  ``block_c`` caps the C-column bucket;
    ``None`` caps resolve from the autotune cache (``op="projgram"``)
    and then from the shared VMEM budget.

    ``schedule`` picks ``"recompute"`` or ``"staged"`` (P projected
    once, Gram buckets reload it; requires ``p_dtype`` f32); ``None``
    resolves per shape via :func:`choose_projgram_schedule`.  Both
    schedules are bitwise equal.
    """
    n, d = x.shape
    d2, kt = q.shape
    assert d == d2
    plan = plan_projgram(n, d, kt, x.dtype, block_n=block_n, block_d=block_d,
                         block_c=block_c, p_dtype=p_dtype)
    if plan is None:
        # k̃p > 8192: no 128-wide block fits the budget — unfused fallback
        p = pallas_matmul(x, q, out_dtype=p_dtype, interpret=interpret)
        c = pallas_matmul(p, p, transpose_lhs=True, interpret=interpret)
        return p, c
    if schedule is None:
        schedule = choose_projgram_schedule(
            n, d, kt, x.dtype, block_n=block_n, block_d=block_d,
            block_c=block_c, p_dtype=p_dtype)
    if schedule == "staged":
        plans = plan_projgram_staged(n, d, kt, x.dtype, block_n=block_n,
                                     block_d=block_d, block_c=block_c,
                                     p_dtype=p_dtype)
        if plans is not None:
            stage, gram = plans
            xp = _pad2(x, *stage.in_specs[0].padded)
            qp = _pad2(q, *stage.in_specs[1].padded)
            p, c = _staged_gram_call(xp, qp, stage, gram, interpret)
            return p[:n, :kt], c[:kt, :kt]
    xp = _pad2(x, *plan.in_specs[0].padded)
    qp = _pad2(q, *plan.in_specs[1].padded)

    p, c = pl.pallas_call(
        functools.partial(_projgram_kernel, n_d_steps=plan.grid[2],
                          block_c=plan.out_specs[1].shape[1]),
        **launch_args(plan),
        interpret=interpret,
    )(xp, qp)
    return p[:n, :kt], c[:kt, :kt]


def _projgram_seeded_kernel(seed_ref, x_ref, p_ref, c_ref, acc_ref, *,
                            n_d_steps: int, block_c: int, bd: int, ktp: int,
                            d: int, kt: int, q_dtype):
    """Seeded-Ω variant of :func:`_projgram_kernel`: the (bd, k̃p) Q
    tile is regenerated from the SMEM seed at global row offset
    ``d_step·bd`` (f32 → zero-mask outside (d, k̃) → one cast), bitwise
    identical to streaming a zero-padded ``rand.dense_omega`` tile."""
    c_step = pl.program_id(0)
    n_step = pl.program_id(1)
    d_step = pl.program_id(2)

    @pl.when(jnp.logical_and(n_step == 0, d_step == 0))
    def _init_c():
        c_ref[...] = jnp.zeros_like(c_ref)

    @pl.when(d_step == 0)
    def _init_p():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_tile = rand.normal_tile(
        seed_ref[0], seed_ref[1],
        (d_step * bd).astype(rand.U32), rand.U32(0),
        (bd, ktp), row_limit=d, col_limit=kt,
    ).astype(q_dtype)
    acc_ref[...] += mxu_dot(x_ref[...], q_tile, NN)

    @pl.when(d_step == n_d_steps - 1)
    def _flush():
        p = acc_ref[...]
        p_ref[...] = p.astype(p_ref.dtype)
        pj = acc_ref[:, pl.ds(c_step * block_c, block_c)]
        c_ref[...] += mxu_dot(p, pj, TN).astype(c_ref.dtype)


def plan_projgram_seeded(n: int, d: int, kt: int, dtype, *,
                         block_n: int | None = None,
                         block_d: int | None = None,
                         block_c: int | None = None,
                         p_dtype=jnp.float32) -> KernelPlan | None:
    """Launch plan for the seeded project+gram kernel: the materialized
    plan's geometry with the Q operand replaced by a (2,)-uint32 SMEM
    seed scalar."""
    base = plan_projgram(n, d, kt, dtype, block_n=block_n, block_d=block_d,
                         block_c=block_c, p_dtype=p_dtype)
    if base is None:
        return None
    return dataclasses.replace(
        base,
        name="projgram_seeded",
        in_specs=base.in_specs[:1],
        scalars=(ScalarDef((2,), "uint32"),),
    )


@functools.partial(
    jax.jit,
    static_argnames=("kt", "q_dtype", "block_n", "block_d", "block_c",
                     "schedule", "interpret", "p_dtype"),
)
def projgram_seeded(
    x: jax.Array,
    seed: jax.Array,
    *,
    kt: int,
    q_dtype=None,
    block_n: int | None = None,
    block_d: int | None = None,
    block_c: int | None = None,
    schedule: str | None = None,
    p_dtype=jnp.float32,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Return (P = x @ Ω(seed), C = PᵀP) with Ω generated in-kernel.

    x: (n, d), seed: (2,) uint32.  Bitwise identical to
    ``projgram(x, rand.dense_omega(seed, d, kt, q_dtype))``; only the
    degenerate unfused fallback (k̃p > 8192) materializes Ω transiently.
    ``schedule`` as in :func:`projgram`; under ``"staged"`` each Ω tile
    is generated exactly once, in phase 1.
    """
    n, d = x.shape
    q_dtype = x.dtype if q_dtype is None else jnp.dtype(q_dtype)
    plan = plan_projgram_seeded(n, d, kt, x.dtype, block_n=block_n,
                                block_d=block_d, block_c=block_c,
                                p_dtype=p_dtype)
    if plan is None:
        q = rand.dense_omega(seed, d, kt, q_dtype)
        p = pallas_matmul(x, q, out_dtype=p_dtype, interpret=interpret)
        c = pallas_matmul(p, p, transpose_lhs=True, interpret=interpret)
        return p, c
    if schedule is None:
        schedule = choose_projgram_schedule(
            n, d, kt, x.dtype, block_n=block_n, block_d=block_d,
            block_c=block_c, p_dtype=p_dtype)
    if schedule == "staged":
        plans = plan_projgram_staged(n, d, kt, x.dtype, block_n=block_n,
                                     block_d=block_d, block_c=block_c,
                                     p_dtype=p_dtype, seeded=True)
        if plans is not None:
            stage, gram = plans
            xp = _pad2(x, *stage.in_specs[0].padded)
            bd = stage.in_specs[0].shape[1]
            ktp = stage.out_specs[0].shape[1]
            p, c = _staged_gram_call(
                xp, jnp.asarray(seed, jnp.uint32), stage, gram, interpret,
                seeded_kwargs=dict(bd=bd, ktp=ktp, d=d, kt=kt,
                                   q_dtype=q_dtype))
            return p[:n, :kt], c[:kt, :kt]
    xp = _pad2(x, *plan.in_specs[0].padded)
    bd = plan.in_specs[0].shape[1]
    ktp = plan.out_specs[0].shape[1]

    p, c = pl.pallas_call(
        functools.partial(_projgram_seeded_kernel, n_d_steps=plan.grid[2],
                          block_c=plan.out_specs[1].shape[1],
                          bd=bd, ktp=ktp, d=d, kt=kt, q_dtype=q_dtype),
        **launch_args(plan),
        interpret=interpret,
    )(jnp.asarray(seed, jnp.uint32), xp)
    return p[:n, :kt], c[:kt, :kt]


# --------------------------------------------------------------------------
# staged (P-reuse) schedule: project once, sweep the Gram buckets
# --------------------------------------------------------------------------


def _gram_sweep_kernel(p_ref, c_ref, *, block_c: int):
    """Phase 2: C[:, bucket] += Pᵀ P[:, bucket]; grid (kt_t, n_t), rows
    innermost.  Reloads the staged (bn, k̃p) P tiles once per C-column
    bucket — the same f32 dot the recompute schedule issues on its last
    d step, so the two schedules are bitwise equal."""
    c_step = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    p = p_ref[...]
    pj = p_ref[:, pl.ds(c_step * block_c, block_c)]
    c_ref[...] += mxu_dot(p, pj, TN)  # Pᵀ P[:, bucket] on the MXU


def plan_gram_sweep(n: int, kt: int, *,
                    bn: int | None = None,
                    bc: int | None = None) -> KernelPlan | None:
    """Launch plan for the phase-2 Gram sweep (C = PᵀP, bucketed).

    ``bn``/``bc`` are resolved blocks when given (the staged composite
    passes the recompute plan's blocks verbatim); ``None`` resolves
    standalone from the shared VMEM budget.
    """
    np_, ktp = _round_up(n, 128), _round_up(kt, 128)
    row_cap = vmem_row_cap(ktp)
    if row_cap < 128:
        return None
    if bc is None:
        bc = ktp if ktp <= row_cap else _pick_block(ktp, row_cap)
    if bn is None:
        bn = _pick_block(np_, min(256, row_cap))
    return KernelPlan(
        name="gram_sweep",
        grid=(ktp // bc, np_ // bn),
        in_specs=(
            BlockDef((bn, ktp), lambda j, i: (i, 0), (np_, ktp), "float32"),
        ),
        out_specs=(
            BlockDef((ktp, bc), lambda j, i: (0, j), (ktp, ktp), "float32"),
        ),
        scratch=(),
        out_shape=((kt, kt),),
        accum_outputs=(0,),
    )


def plan_projgram_staged(
    n: int, d: int, kt: int, dtype, *,
    block_n: int | None = None, block_d: int | None = None,
    block_c: int | None = None, p_dtype=jnp.float32, seeded: bool = False,
) -> tuple[KernelPlan, KernelPlan] | None:
    """(stage, gram_sweep) plan pair for the staged schedule, or
    ``None`` on degenerate shapes or when ``p_dtype`` is not f32 (the
    staged P *is* the emitted P buffer, and parity requires it exact).
    Blocks are extracted from the recompute plan for the same shape, so
    both schedules tile identically."""
    if jnp.dtype(p_dtype) != jnp.float32:
        return None
    base = plan_projgram(n, d, kt, dtype, block_n=block_n, block_d=block_d,
                         block_c=block_c, p_dtype=p_dtype)
    if base is None:
        return None
    bn, bd = base.in_specs[0].shape
    bc = base.out_specs[1].shape[1]
    if seeded:
        stage = plan_proj_stage_seeded(n, d, kt, dtype, bn=bn, bd=bd)
    else:
        stage = plan_proj_stage(n, d, kt, dtype, bn=bn, bd=bd)
    gram = plan_gram_sweep(n, kt, bn=bn, bc=bc)
    if stage is None or gram is None:
        return None
    return stage, gram


def choose_projgram_schedule(
    n: int, d: int, kt: int, dtype, *,
    block_n: int | None = None, block_d: int | None = None,
    block_c: int | None = None, p_dtype=jnp.float32,
) -> str:
    """``"staged"`` or ``"recompute"`` for one projgram shape — same
    order of authority as
    :func:`repro.kernels.powerpass.choose_powerpass_schedule`: autotuned
    ``op="projgram-staged"`` entry, then the analytic roofline crossover
    over the plan-derived cost model.  Non-f32 ``p_dtype`` always
    recomputes (the staged schedule's P buffer must stay exact)."""
    if jnp.dtype(p_dtype) != jnp.float32:
        return "recompute"
    np_, dp, ktp = _round_up(n, 128), _round_up(d, 128), _round_up(kt, 128)
    tuned = autotune.lookup_schedule("projgram-staged", (np_, dp, ktp), dtype)
    if tuned is not None:
        return tuned
    base = plan_projgram(n, d, kt, dtype, block_n=block_n, block_d=block_d,
                         block_c=block_c, p_dtype=p_dtype)
    if base is None or base.grid[0] == 1:
        return "recompute"
    plans = plan_projgram_staged(n, d, kt, dtype, block_n=block_n,
                                 block_d=block_d, block_c=block_c,
                                 p_dtype=p_dtype)
    if plans is None:
        return "recompute"
    from repro.obs.cost import plan_cost  # deferred: obs imports kernels.plan

    rec = plan_cost(base)
    stage, gram = (plan_cost(p) for p in plans)
    return pick_schedule({
        "recompute": (rec["flops"], rec["bytes"]),
        "staged": (stage["flops"] + gram["flops"],
                   stage["bytes"] + gram["bytes"]),
    })


def _staged_gram_call(xp, q_or_seed, stage: KernelPlan, gram: KernelPlan,
                      interpret: bool, *, seeded_kwargs=None):
    """Launch the (stage, gram_sweep) pallas_call pair; returns the
    padded (P, C).  P is the staged f32 buffer itself — the final pass
    emits it anyway for the cross term F, so staging is free here."""
    if seeded_kwargs is None:
        body = _proj_stage_kernel
        operands = (xp, q_or_seed)
    else:
        body = functools.partial(_proj_stage_seeded_kernel, **seeded_kwargs)
        operands = (q_or_seed, xp)  # seed scalar leads the blocked operands
    p = pl.pallas_call(
        body,
        **launch_args(stage),
        interpret=interpret,
    )(*operands)
    c = pl.pallas_call(
        functools.partial(_gram_sweep_kernel,
                          block_c=gram.out_specs[0].shape[1]),
        **launch_args(gram),
        interpret=interpret,
    )(p)
    return p, c


@functools.partial(jax.jit, static_argnames=("interpret",))
def gram_sweep(p: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Standalone phase-2 Gram sweep: C = pᵀ p, reloading P tiles per
    C-column bucket.  p: (n, k̃) f32 (or the compute dtype on the
    sharded collective-fused path) → (k̃, k̃) f32.  Registry entry point
    for the ``gram_sweep`` contract checks."""
    n, kt = p.shape
    plan = plan_gram_sweep(n, kt)
    if plan is None:
        return pallas_matmul(p, p, transpose_lhs=True, interpret=interpret)
    pp = _pad2(p, *plan.in_specs[0].padded)
    if plan.in_specs[0].dtype != str(p.dtype):
        plan = dataclasses.replace(
            plan,
            in_specs=(dataclasses.replace(plan.in_specs[0],
                                          dtype=str(p.dtype)),),
        )
    c = pl.pallas_call(
        functools.partial(_gram_sweep_kernel,
                          block_c=plan.out_specs[0].shape[1]),
        **launch_args(plan),
        interpret=interpret,
    )(pp)
    return c[:kt, :kt]
