"""Declarative kernel launch plans — the checkable kernel contract.

Every Pallas wrapper in this package assembles its ``pallas_call`` from
a :class:`KernelPlan` built by a pure, trace-free ``plan_*`` function
(``matmul.plan_matmul``, ``powerpass.plan_powerpass``,
``projgram.plan_projgram``).  The plan is the single source of truth
for the launch geometry: grid, block shapes, index maps, padded
operand/output shapes, scratch allocations and dtypes.  Because the
wrapper and the static checker (:mod:`repro.analysis.kernel_check`)
consume the *same* plan object, the checker verifies exactly what runs
— grid × block × index-map consistency, full output coverage, VMEM
residency against the shared per-buffer budget
(:data:`repro.kernels.matmul.VMEM_BLOCK_ELEMS`) and against the scoped
VMEM limit the launch requests, and the bf16-in/f32-accum dtype rules —
with no device and no duplicated sizing logic that could drift.

VMEM accounting.  Mosaic pipelines every blocked operand and output
through two buffers, keeps scratch resident, and keeps the kernel body's
values (loaded blocks, the transposed operand of a TN contraction,
products, running sums) on a VMEM stack beside them.  It refuses a
kernel whose allocation exceeds the scoped VMEM limit (16 MiB unless the
launch passes ``vmem_limit_bytes``).  :attr:`KernelPlan.vmem_bytes`
counts the buffers, :attr:`KernelPlan.vmem_need_bytes` charges the body
as much again plus :data:`VMEM_STACK_SLACK_BYTES`, and every launch
requests :attr:`KernelPlan.vmem_limit_bytes`, that need rounded up and
never below the default.  Under the default limit the TPU compiler
refused the recompute powerpass at 512 × 2^19, k̃ = 970 (16 MiB of
buffers) and the sharded sweep at 512 × 2^17, k̃ = 256 (16.5 MiB of
buffers, 34.46 MiB in all).

A ``plan_*`` function returns ``None`` when the shape is degenerate
for its fused kernel (the documented unfused-fallback condition); the
wrapper then decomposes into :func:`~repro.kernels.matmul.pallas_matmul`
calls whose own plans remain checkable.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

IndexMap = Callable[..., Tuple[int, ...]]

#: Mosaic's scoped-VMEM limit for a launch that passes no
#: ``vmem_limit_bytes``.
MOSAIC_DEFAULT_VMEM_LIMIT = 16 << 20

#: Slack above twice a plan's buffers.  Compiled for a described TPU v5e,
#: every kernel at five shapes (512- and 256-row chunks, d up to 2^18,
#: k̃ from 32 to 2060) needed at most its buffers twice over plus
#: 1.46 MiB (the powerpass sweep at 512 × 2^17, k̃ = 256).
VMEM_STACK_SLACK_BYTES = 2 << 20

#: The largest scoped limit a launch may request: half of the 128 MiB of
#: VMEM on a TPU v5e core, leaving the rest to XLA's own fusions.
VMEM_LIMIT_CAP = 64 << 20


@dataclasses.dataclass(frozen=True)
class BlockDef:
    """One blocked operand of a ``pallas_call``: the block shape, the
    grid-position → block-coordinate index map, the full padded array
    shape the blocks tile, and the element dtype name."""

    shape: Tuple[int, ...]
    index_map: IndexMap
    padded: Tuple[int, ...]
    dtype: str

    @property
    def elems(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclasses.dataclass(frozen=True)
class ScratchDef:
    """One VMEM scratch allocation (no index map — scratch is
    grid-invariant and always fully resident)."""

    shape: Tuple[int, ...]
    dtype: str

    @property
    def elems(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclasses.dataclass(frozen=True)
class ScalarDef:
    """One SMEM-resident scalar operand (e.g. a PRNG seed): the full
    small array is passed to the kernel un-blocked, ahead of the
    blocked operands.  PRNG-bearing plans MUST route their seed through
    one of these — never through a trace-time constant — so the
    contract checker (rule RCCA108) can verify the plumbing."""

    shape: Tuple[int, ...]
    dtype: str

    @property
    def elems(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """The complete launch geometry of one fused-kernel invocation."""

    name: str
    grid: Tuple[int, ...]
    in_specs: Tuple[BlockDef, ...]
    out_specs: Tuple[BlockDef, ...]
    scratch: Tuple[ScratchDef, ...]
    #: logical (unpadded) output shapes, in out_specs order
    out_shape: Tuple[Tuple[int, ...], ...]
    #: indices into out_specs of f32 accumulator outputs (dtype rule)
    accum_outputs: Tuple[int, ...] = ()
    #: SMEM scalar operands, passed BEFORE the blocked in_specs
    scalars: Tuple[ScalarDef, ...] = ()

    @property
    def n_steps(self) -> int:
        n = 1
        for g in self.grid:
            n *= g
        return n

    @property
    def vmem_bytes(self) -> int:
        """VMEM the launch allocates: every blocked operand and output
        double-buffered by the pipeline, plus the resident scratch."""
        import jax.numpy as jnp

        def nbytes(buf):
            return buf.elems * jnp.dtype(buf.dtype).itemsize

        blocks = sum(map(nbytes, self.in_specs + self.out_specs))
        return 2 * blocks + sum(map(nbytes, self.scratch))

    @property
    def vmem_need_bytes(self) -> int:
        """VMEM the launch is charged: the buffers, as much again for
        the body's values, and the slack."""
        return 2 * self.vmem_bytes + VMEM_STACK_SLACK_BYTES

    @property
    def vmem_limit_bytes(self) -> int:
        """The scoped-VMEM limit the launch requests: the need rounded
        up to a MiB, never below the default."""
        need = -(-self.vmem_need_bytes // (1 << 20)) << 20
        return max(MOSAIC_DEFAULT_VMEM_LIMIT, need)


def launch_args(plan: KernelPlan, semantics=None) -> dict:
    """``pl.pallas_call`` keyword arguments realized from a plan —
    the one bridge from the declarative contract to a live launch, so
    a wrapper cannot diverge from what the checker verified.

    ``semantics`` is the grid's Mosaic ``dimension_semantics``; the
    default marks every axis ``"arbitrary"`` (sequential), which the
    accumulating kernels need."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .compat import smem_spec, tpu_compiler_params, vmem

    out_specs = [pl.BlockSpec(b.shape, b.index_map) for b in plan.out_specs]
    out_shape = [jax.ShapeDtypeStruct(b.padded, jnp.dtype(b.dtype))
                 for b in plan.out_specs]
    single = len(out_specs) == 1
    in_specs = [smem_spec() for _ in plan.scalars]
    in_specs += [pl.BlockSpec(b.shape, b.index_map) for b in plan.in_specs]
    return dict(
        grid=plan.grid,
        in_specs=in_specs,
        out_specs=out_specs[0] if single else out_specs,
        out_shape=out_shape[0] if single else out_shape,
        scratch_shapes=[vmem(s.shape, jnp.dtype(s.dtype))
                        for s in plan.scratch],
        compiler_params=tpu_compiler_params(
            dimension_semantics=tuple(semantics or
                                      ("arbitrary",) * len(plan.grid)),
            vmem_limit_bytes=plan.vmem_limit_bytes),
    )
