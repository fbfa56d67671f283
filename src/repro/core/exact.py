"""Exact (dense) regularized CCA — test oracle.

Solves the paper's eq. (1)-(2) directly via whitening + SVD:

    maximize Tr(Xaᵀ AᵀB Xb)
    s.t. Xaᵀ (AᵀA + λa I) Xa = n I,   Xbᵀ (BᵀB + λb I) Xb = n I

Cost O(n·d² + d³); only usable at test scale.  The framework's
RandomizedCCA is validated against this oracle.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .linalg import full_f32, inv_sqrt_psd, sym, topk_svd


class CCASolution(NamedTuple):
    Xa: jax.Array  # (da, k)
    Xb: jax.Array  # (db, k)
    rho: jax.Array  # (k,) canonical correlations (singular values of whitened cross-cov)


def center(M: jax.Array) -> jax.Array:
    return M - jnp.mean(M, axis=0, keepdims=True)


@full_f32
def exact_cca(
    A: jax.Array,
    B: jax.Array,
    k: int,
    lam_a: float = 0.0,
    lam_b: float = 0.0,
    *,
    do_center: bool = False,
) -> CCASolution:
    n = A.shape[0]
    if do_center:
        A = center(A)
        B = center(B)
    da, db = A.shape[1], B.shape[1]
    Ca = sym(A.T @ A) + lam_a * jnp.eye(da, dtype=A.dtype)
    Cb = sym(B.T @ B) + lam_b * jnp.eye(db, dtype=B.dtype)
    Cab = A.T @ B
    Wa = inv_sqrt_psd(Ca)
    Wb = inv_sqrt_psd(Cb)
    T = Wa @ Cab @ Wb
    U, S, V = topk_svd(T, k)
    Xa = jnp.sqrt(n) * (Wa @ U)
    Xb = jnp.sqrt(n) * (Wb @ V)
    # With constraints Xᵀ(C+λI)X = nI the singular values of the whitened
    # cross-covariance ARE the canonical correlations: (1/n)Tr(XaᵀCabXb) = ΣSᵢ.
    return CCASolution(Xa=Xa, Xb=Xb, rho=S)


@full_f32
def cca_objective(A: jax.Array, B: jax.Array, Xa: jax.Array, Xb: jax.Array) -> jax.Array:
    """(1/n) Tr(Xaᵀ AᵀB Xb) — the quantity in paper Fig. 2a / Table 2b."""
    n = A.shape[0]
    PA = A @ Xa
    PB = B @ Xb
    return jnp.trace(PA.T @ PB) / n


@full_f32
def feasibility_errors(
    A: jax.Array,
    B: jax.Array,
    Xa: jax.Array,
    Xb: jax.Array,
    lam_a: float = 0.0,
    lam_b: float = 0.0,
) -> dict[str, jax.Array]:
    """Constraint residuals: paper reports solutions feasible to machine
    precision — (regularized) identity covariance & diagonal cross-cov."""
    return streamed_feasibility_errors([(A, B)], Xa, Xb, lam_a, lam_b)


@full_f32
def streamed_feasibility_errors(chunks, Xa, Xb, lam_a=0.0,
                                lam_b=0.0) -> dict[str, jax.Array]:
    """:func:`feasibility_errors` over an iterable of paired row chunks
    ``(a, b)``: (AXa)ᵀ(AXa) = Σ (aXa)ᵀ(aXa), so only k × k Grams are
    accumulated and A, B are never materialized."""
    n = 0
    Ga = Gb = C = 0.0
    for a, b in chunks:
        pa, pb = a @ Xa, b @ Xb
        Ga = Ga + pa.T @ pa
        Gb = Gb + pb.T @ pb
        C = C + pa.T @ pb
        n += a.shape[0]
    Ia = Ga + lam_a * (Xa.T @ Xa)
    Ib = Gb + lam_b * (Xb.T @ Xb)
    C = C / n
    eye = jnp.eye(Xa.shape[1], dtype=Xa.dtype)
    offdiag = C - jnp.diag(jnp.diagonal(C))
    return {
        "cov_a": jnp.max(jnp.abs(Ia / n - eye)),
        "cov_b": jnp.max(jnp.abs(Ib / n - eye)),
        "crosscov_offdiag": jnp.max(jnp.abs(offdiag)),
    }
