"""The jax surface the data-pass engine reaches through one module.

Pinned to the installed jax (0.9.0).  The kernel and launch layers
touch the version-sensitive namespaces only through these helpers:

- ``tpu_compiler_params(...)`` — ``pltpu.CompilerParams``.
- ``set_mesh(mesh)`` — ``jax.set_mesh``: makes ``mesh`` ambient.
- ``cost_analysis(compiled)`` — ``compiled.cost_analysis()`` as a
  (possibly empty) dict.
- ``sub_jaxprs(value)`` / ``count_pallas_calls(jaxpr)`` — the jaxpr walk
  over ``jax.extend.core`` containers (the fused-vs-fallback regression
  metric used by tests and benchmarks).
- ``vmem(shape, dtype)`` — a VMEM scratch allocation (``pltpu.VMEM``).
- ``smem_spec()`` — a ``pl.BlockSpec`` placing a small scalar operand
  (e.g. the Ω PRNG seed) in SMEM, the scalar-operand path for the
  seeded kernels.
- ``shard_map(f, mesh=..., in_specs=..., out_specs=..., check_rep=...)``
  — ``jax.shard_map`` with ``check_rep`` passed as ``check_vma``.

``repro.analysis`` lint rule RCCA002 enforces the discipline: no
``pltpu.`` / ``jax.experimental.shard_map`` use outside this module.

The helpers resolve their jax attribute at call time (not import time),
so a test monkeypatching one is picked up without reloading this module.
"""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu


def tpu_compiler_params(*, dimension_semantics=None, **kwargs):
    """Mosaic compiler params (``dimension_semantics``,
    ``vmem_limit_bytes``, ...) for ``pl.pallas_call(compiler_params=...)``."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                **kwargs)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` — always a (possibly empty) dict."""
    return compiled.cost_analysis() or {}


def sub_jaxprs(value):
    """Yield the jaxprs held by one eqn parameter value: a closed or
    open jaxpr, or a list/tuple of them (``cond`` branches)."""
    from jax.extend import core

    if isinstance(value, core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, core.Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from sub_jaxprs(v)


def count_pallas_calls(closed_jaxpr) -> int:
    """Number of ``pallas_call`` eqns anywhere in a closed jaxpr — the
    fusion-regression metric the kernel tests and BENCH reports assert
    on (2 fused calls per power-pass chunk; a fallback to the unfused
    matmul pair doubles it).  It counts kernel launches, not HBM
    traffic — bucketed grids re-read inputs within one call."""

    def walk(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for val in eqn.params.values():
                n += sum(walk(sub) for sub in sub_jaxprs(val))
        return n

    return walk(closed_jaxpr.jaxpr)


def vmem(shape, dtype):
    """A VMEM scratch-buffer allocation for ``pl.pallas_call``
    (``scratch_shapes=[vmem((bm, bn), jnp.float32)]``) — the one place
    the kernels touch the ``pltpu`` namespace for memory spaces."""
    return pltpu.VMEM(tuple(shape), dtype)


def smem_spec():
    """A ``pl.BlockSpec`` that places a small scalar operand (a PRNG
    seed, a size, ...) in SMEM: no block shape, the full array is
    handed to the kernel and read elementwise (``seed_ref[0]``).

    This is the scalar-operand path for PRNG-bearing kernels — the
    seed rides as data (visible to jit, binding metadata and the
    contract checker), never as a Python-level constant baked into the
    trace.
    """
    from jax.experimental import pallas as pl

    return pl.BlockSpec(memory_space=pltpu.SMEM)


def shard_map(f, *, mesh, in_specs, out_specs, check_rep=False):
    """``jax.shard_map``; ``check_rep`` is passed as ``check_vma``.
    Usable directly or as ``functools.partial(shard_map, mesh=...)``
    decoration, exactly like the upstream function."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


def set_mesh(mesh):
    """Context manager making ``mesh`` the ambient device mesh."""
    return jax.set_mesh(mesh)
