"""Pallas TPU matmul kernels for the CCA data pass.

The data pass of Algorithm 1 is three matmul shapes (see DESIGN.md §3):

  NN: P = X @ Q            (rows × features) @ (features × k̃)
  TN: Y = Xᵀ @ P           contraction over the streamed row dimension
  (gram) C = Pᵀ @ P        TN with X == P — reuses the TN kernel

Both kernels use an f32 VMEM scratch accumulator with the contraction
dimension innermost in the grid, MXU-aligned blocks (multiples of 128 on
every matmul dim), and cast to the output dtype only on the final
contraction step.  Validated against ref.py in interpret mode; on real
TPUs the same code lowers to Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import autotune
from .plan import BlockDef, KernelPlan, ScratchDef, launch_args


# --------------------------------------------------------------------------
# kernel bodies
# --------------------------------------------------------------------------

#: dot_general dimension numbers: x @ q, and xᵀ @ p without materializing
#: the transpose (contract the leading, streamed dimension)
NN = (((1,), (0,)), ((), ()))
TN = (((0,), (0,)), ((), ()))


def mxu_dot(x: jax.Array, y: jax.Array, dims) -> jax.Array:
    """The MXU product of a kernel body, accumulated in f32.

    Two f32 operands contract at full f32 precision (Mosaic's fp32
    contract precision, several bf16 passes); the default would be one
    bf16 pass.  Narrower operands take the default single pass: their
    products are exact in f32, and Mosaic refuses fp32 precision on
    them."""
    f32 = jnp.float32
    precision = (jax.lax.Precision.HIGHEST if x.dtype == y.dtype == f32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(x, y, dims, precision=precision,
                               preferred_element_type=f32)


def _mm_nn_kernel(x_ref, q_ref, o_ref, acc_ref, *, n_k_steps: int):
    """o[i,j] = Σ_k x[i,k] q[k,j]; grid (i, j, k) with k innermost."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(x_ref[...], q_ref[...], NN)

    @pl.when(k_step == n_k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_tn_kernel(x_ref, p_ref, o_ref, acc_ref, *, n_k_steps: int):
    """o[d,j] = Σ_n x[n,d] p[n,j]  (contract over leading/stream dim)."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(x_ref[...], p_ref[...], TN)

    @pl.when(k_step == n_k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# --------------------------------------------------------------------------
# host-side wrappers (padding + BlockSpec assembly)
# --------------------------------------------------------------------------

#: Per-buffer VMEM budget, in elements (f32 ⇒ ~4 MB per block).  Single
#: source of truth for every fused kernel's block sizing: the bucketed
#: powerpass/projgram wrappers size their output-column buckets so each
#: VMEM-resident block stays within this budget, and fall back to the
#: unfused matmul pair only when even a 128-row block cannot fit.
VMEM_BLOCK_ELEMS = 1 << 20

#: Modelled accelerator balance point (peak MXU FLOP/s ÷ HBM bytes/s)
#: used by the staged-vs-recompute schedule crossover.  The default is
#: the benchmark target's ratio (~197 TFLOP/s ÷ 819 GB/s ≈ 240 — the
#: same constants ``benchmarks/kernel_bench.py`` rooflines against); an
#: autotuned schedule entry (``op="powerpass-staged"`` /
#: ``"projgram-staged"``) always overrides the analytic rule, so this
#: constant only decides unswept shapes.
ROOFLINE_FLOPS_PER_BYTE = 240.0


def pick_schedule(costs: dict, *,
                  roofline: float = ROOFLINE_FLOPS_PER_BYTE) -> str:
    """Shared-budget crossover rule between kernel schedules.

    ``costs`` maps a schedule name to its modelled ``(flops, bytes)``
    for one launch (or launch pair).  A schedule's modelled wall time in
    HBM-byte units is ``max(flops / roofline, bytes)`` — compute-bound
    schedules are charged their FLOPs at the balance point, memory-bound
    ones their traffic — and the cheaper schedule wins.  Ties break
    deterministically by name order, so the choice is reproducible
    across processes.
    """
    def t(c) -> float:
        flops, bytes_ = c
        return max(float(flops) / roofline, float(bytes_))

    return min(sorted(costs), key=lambda k: t(costs[k]))


def vmem_row_cap(cols: int) -> int:
    """Largest multiple-of-128 row count ``r`` with ``r·cols`` inside
    :data:`VMEM_BLOCK_ELEMS`; 0 when even 128 rows do not fit."""
    return (VMEM_BLOCK_ELEMS // max(cols, 1)) // 128 * 128


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pick_block(dim: int, cap: int) -> int:
    """Largest power-of-two multiple of 128 that divides the padded dim
    and is ≤ cap.  Padding is always to a multiple of 128 first."""
    b = 128
    while b * 2 <= cap and dim % (b * 2) == 0:
        b *= 2
    return b


def _pad2(x: jax.Array, r: int, c: int) -> jax.Array:
    pr, pc = r - x.shape[0], c - x.shape[1]
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, ((0, pr), (0, pc)))


def plan_matmul(M: int, K: int, N: int, dtype, *, transpose_lhs: bool = False,
                block_m: int | None = None, block_n: int | None = None,
                block_k: int | None = None,
                out_dtype=jnp.float32) -> KernelPlan:
    """Launch plan for ``pallas_matmul`` on an (M, K) @ (K, N) problem
    — grid, blocks, index maps and scratch, resolved exactly as the
    wrapper resolves them (autotune cache, then the 512³ heuristic).
    Pure and trace-free: the static kernel checker consumes the same
    plan the wrapper launches."""
    Mp, Np, Kp = _round_up(M, 128), _round_up(N, 128), _round_up(K, 128)
    if block_m is None or block_n is None or block_k is None:
        op = "matmul_tn" if transpose_lhs else "matmul_nn"
        tuned = autotune.lookup(op, Mp, Kp, Np, dtype)
        block_m = tuned[0] if block_m is None else block_m
        block_n = tuned[1] if block_n is None else block_n
        block_k = tuned[2] if block_k is None else block_k
    bm, bn, bk = _pick_block(Mp, block_m), _pick_block(Np, block_n), _pick_block(Kp, block_k)
    gm, gn, gk = Mp // bm, Np // bn, Kp // bk
    in_dt = str(jnp.dtype(dtype))
    if transpose_lhs:
        x_spec = BlockDef((bk, bm), lambda i, j, k: (k, i), (Kp, Mp), in_dt)
    else:
        x_spec = BlockDef((bm, bk), lambda i, j, k: (i, k), (Mp, Kp), in_dt)
    return KernelPlan(
        name="matmul_tn" if transpose_lhs else "matmul_nn",
        grid=(gm, gn, gk),
        in_specs=(x_spec,
                  BlockDef((bk, bn), lambda i, j, k: (k, j), (Kp, Np), in_dt)),
        out_specs=(BlockDef((bm, bn), lambda i, j, k: (i, j), (Mp, Np),
                            str(jnp.dtype(out_dtype))),),
        scratch=(ScratchDef((bm, bn), "float32"),),
        out_shape=((M, N),),
        accum_outputs=(0,) if jnp.dtype(out_dtype) == jnp.float32 else (),
    )


@functools.partial(
    jax.jit,
    static_argnames=("transpose_lhs", "block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def pallas_matmul(
    x: jax.Array,
    y: jax.Array,
    *,
    transpose_lhs: bool = False,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """MXU-tiled ``x @ y`` (or ``xᵀ @ y``) with f32 accumulation.

    Shapes: NN — x (M, K), y (K, N) → (M, N);
            TN — x (K, M), y (K, N) → (M, N)  (contraction = dim 0).
    Inputs are zero-padded to multiples of 128; the result is sliced
    back, so any shape is accepted.

    Block caps left as ``None`` resolve from the autotune cache for this
    (backend, op, dtype, padded shape) — see :mod:`repro.kernels.autotune`
    — falling back to the 512³ heuristic for unswept shapes.
    """
    if transpose_lhs:
        K, M = x.shape
        K2, N = y.shape
    else:
        M, K = x.shape
        K2, N = y.shape
    assert K == K2, f"contraction mismatch {K} vs {K2}"

    plan = plan_matmul(M, K, N, x.dtype, transpose_lhs=transpose_lhs,
                       block_m=block_m, block_n=block_n, block_k=block_k,
                       out_dtype=out_dtype)
    body = _mm_tn_kernel if transpose_lhs else _mm_nn_kernel
    kernel = functools.partial(body, n_k_steps=plan.grid[2])
    xp = _pad2(x, *plan.in_specs[0].padded)
    yp = _pad2(y, *plan.in_specs[1].padded)

    out = pl.pallas_call(
        kernel,
        **launch_args(plan, ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, yp)
    return out[:M, :N]
