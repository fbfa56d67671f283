"""Matmul-friendly linear algebra helpers used across the CCA core.

Everything here is deliberately expressed as dense matmuls + small
(k̃ × k̃) host-scale factorizations so it maps onto the TPU MXU: no
Householder QR, no pivoting.  ``k̃ = k + p`` is a few hundred to a few
thousand, so all square factorizations below are "small" in the paper's
sense (§3: feasible on one commodity machine for k+p ≲ 10000).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular


def full_f32(fn):
    """Run ``fn`` with its matmuls at full f32 precision.

    On a TPU, XLA's default f32 matmul is one bf16 pass (~4e-3 relative
    error), which the fit's whitening amplifies far past its tolerances.
    The scope covers every matmul ``fn`` traces or dispatches; the
    Pallas kernels set their own precision (``kernels.matmul.mxu_dot``).
    A CPU computes f32 matmuls in f32 either way, so there it changes
    nothing."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


def sym(M: jax.Array) -> jax.Array:
    """Symmetrize (guards eigh/cholesky against matmul round-off skew)."""
    return 0.5 * (M + M.T)


def chol_psd(M: jax.Array, jitter: float = 0.0) -> jax.Array:
    """Cholesky of a (nearly) PSD matrix with optional diagonal jitter."""
    d = M.shape[-1]
    if jitter:
        M = M + jitter * jnp.eye(d, dtype=M.dtype)
    return jnp.linalg.cholesky(sym(M))


def tri_solve_right(Y: jax.Array, L: jax.Array, *, trans: bool = False) -> jax.Array:
    """Compute ``Y @ inv(L)`` (or ``Y @ inv(L).T``) via triangular solve.

    L is lower triangular.  Used for CholeskyQR and the paper's line 21
    ``F ← La^{-T} F Lb^{-1}`` without forming explicit inverses.
    """
    # Y L^{-1} = (L^{-T} Y^T)^T ; solve L^T Z = Y^T  (upper system)
    if not trans:
        return solve_triangular(L.T, Y.T, lower=False).T
    # Y L^{-T} = (L^{-1} Y^T)^T ; solve L Z = Y^T (lower system)
    return solve_triangular(L, Y.T, lower=True).T


def cholesky_qr(Y: jax.Array, jitter: float = 0.0) -> tuple[jax.Array, jax.Array]:
    """One round of CholeskyQR: Q = Y L^{-T} with L = chol(YᵀY).

    Returns (Q, R) with R = Lᵀ upper-triangular so that Q R = Y.
    All-matmul: the only non-matmul op is a k̃×k̃ Cholesky.
    """
    G = sym(Y.T @ Y)
    L = chol_psd(G, jitter)
    Q = tri_solve_right(Y, L, trans=False)
    return Q, L.T


def cholesky_qr2(Y: jax.Array, jitter: float = 0.0) -> jax.Array:
    """CholeskyQR2: two rounds ⇒ orthogonality error O(ε) instead of
    O(ε·κ²).  This is the TPU-native replacement for Matlab ``orth`` in
    Algorithm 1 lines 10-11 (see DESIGN.md §3)."""
    Q, _ = cholesky_qr(Y, jitter)
    Q, _ = cholesky_qr(Q, 0.0)
    return Q


def eigh_whiten(Y: jax.Array, G: jax.Array, rel_eps: float = 1e-12) -> jax.Array:
    """First-round orthonormalization robust to arbitrary κ(Y):
    Q = Y · V · w^{-1/2} from the eigendecomposition of the Gram.
    Power iteration squares the condition number every pass, which
    overwhelms plain CholeskyQR in f32 — eigh does not care."""
    w, V = jnp.linalg.eigh(sym(G).astype(jnp.float32))
    w = jnp.maximum(w, rel_eps * jnp.max(w))
    return (Y.astype(jnp.float32) @ V) * (1.0 / jnp.sqrt(w))


def orth(Y: jax.Array) -> jax.Array:
    """Paper's ``orth``: orthonormal basis for range(Y).

    eigh-whitened first round (rank/κ robust) + one CholeskyQR cleanup
    round (restores orthogonality to O(ε)).  Both factorizations are
    k̃×k̃ — "small" in the paper's sense — so this stays matmul-dominated.
    """
    dt = Y.dtype
    Q = eigh_whiten(Y, Y.T @ Y)
    Q, _ = cholesky_qr(Q, 0.0)
    return Q.astype(dt)


def inv_sqrt_psd(M: jax.Array, eps: float = 0.0) -> jax.Array:
    """Symmetric inverse square root via eigh (small matrices only)."""
    w, V = jnp.linalg.eigh(sym(M))
    w = jnp.maximum(w, 0.0) + eps
    return (V * (1.0 / jnp.sqrt(w))) @ V.T


def topk_svd(F: jax.Array, k: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k SVD of a small dense matrix (paper line 22)."""
    U, S, Vt = jnp.linalg.svd(F, full_matrices=False)
    return U[:, :k], S[:k], Vt[:k, :].T
