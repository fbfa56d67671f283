"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.linalg import full_f32


@full_f32
def matmul_ref(x: jax.Array, y: jax.Array, transpose_lhs: bool = False) -> jax.Array:
    x32, y32 = x.astype(jnp.float32), y.astype(jnp.float32)
    return (x32.T if transpose_lhs else x32) @ y32


@full_f32
def projgram_ref(x: jax.Array, q: jax.Array) -> tuple[jax.Array, jax.Array]:
    p = x.astype(jnp.float32) @ q.astype(jnp.float32)
    return p, p.T @ p


@full_f32
def power_pass_ref(a, b, Qa, Qb):
    """One chunk of the range-finder pass: (ΔYa, ΔYb)."""
    f32 = jnp.float32
    pb = b.astype(f32) @ Qb.astype(f32)
    pa = a.astype(f32) @ Qa.astype(f32)
    return a.astype(f32).T @ pb, b.astype(f32).T @ pa


@full_f32
def final_pass_ref(a, b, Qa, Qb):
    """One chunk of the final pass: (ΔCa, ΔCb, ΔF)."""
    f32 = jnp.float32
    pa = a.astype(f32) @ Qa.astype(f32)
    pb = b.astype(f32) @ Qb.astype(f32)
    return pa.T @ pa, pb.T @ pb, pa.T @ pb
