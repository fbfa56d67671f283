"""Matmul-friendly linear algebra helpers used across the CCA core.

Everything here is deliberately expressed as dense matmuls + small
(k̃ × k̃) host-scale factorizations so it maps onto the TPU MXU: no
Householder QR, no pivoting.  ``k̃ = k + p`` is a few hundred to a few
thousand, so all square factorizations below are "small" in the paper's
sense (§3: feasible on one commodity machine for k+p ≲ 10000).
"""

from __future__ import annotations

import functools
from typing import Optional

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


def gram(M: jax.Array, axis_name: Optional[str] = None) -> jax.Array:
    """``MᵀM`` in f32, summed over the feature shards of ``axis_name``
    (a mesh axis that splits M's rows; ``None`` for a whole M)."""
    M = M.astype(jnp.float32)
    G = M.T @ M
    if axis_name is not None:
        G = jax.lax.psum(G, axis_name)
    return sym(G)


def pow2_scale(Y: jax.Array, axis_name: Optional[str] = None) -> jax.Array:
    """Y times the power of two that puts max |Y| in [1, 2), over every
    shard of ``axis_name``.  The product is exact in f32, and the Gram
    of the result stays far inside f32's range (its entries are at most
    4 × rows), whatever Y's own scale."""
    amax = jnp.max(jnp.abs(Y)).astype(jnp.float32)
    if axis_name is not None:
        amax = jax.lax.pmax(amax, axis_name)
    _, e = jnp.frexp(amax)  # amax = m·2^e, 0.5 ≤ m < 1
    # 2^(1-e) built from its exponent bits, clipped to f32's normal range
    bits = (jnp.clip(1 - e, -126, 127) + 127).astype(jnp.int32) << 23
    return Y * jax.lax.bitcast_convert_type(bits, jnp.float32).astype(Y.dtype)


def cholesky_qr(Y: jax.Array, axis_name: Optional[str] = None) -> jax.Array:
    """One round of CholeskyQR: Q = Y L⁻ᵀ with L Lᵀ = YᵀY.

    All-matmul: the only non-matmul ops are a k̃×k̃ Cholesky and the
    inverse of its factor, applied to Y by one matmul (a triangular
    solve against the tall Y holds ~3 more copies of Y on a TPU).  Its
    orthogonality error is O(ε·κ(Y)²), so it only cleans up a basis
    that is already nearly orthonormal.
    """
    L = jnp.linalg.cholesky(gram(Y, axis_name))
    L_inv = solve_triangular(L, jnp.eye(L.shape[0], dtype=L.dtype), lower=True)
    return Y @ L_inv.T


def eigh_whiten(Y: jax.Array, G: jax.Array, rel_eps: float = 1e-12) -> jax.Array:
    """First-round orthonormalization robust to arbitrary κ(Y):
    Q = Y · V · w^{-1/2} from the eigendecomposition of the Gram.
    Power iteration squares the condition number every pass, which
    overwhelms plain CholeskyQR in f32 — eigh does not care."""
    w, V = jnp.linalg.eigh(sym(G).astype(jnp.float32))
    w = jnp.maximum(w, rel_eps * jnp.max(w))
    return (Y.astype(jnp.float32) @ V) * (1.0 / jnp.sqrt(w))


def orth(Y: jax.Array, axis_name: Optional[str] = None) -> jax.Array:
    """Paper's ``orth``: orthonormal basis for range(Y).

    Y is first scaled by a power of two (exact), so its Gram cannot
    overflow f32 however many rows were summed into Y.  Then an
    eigh-whitened first round (rank/κ robust) and two CholeskyQR rounds:
    one round after the eigh leaves ‖QᵀQ − I‖ at 3.5e-6 for κ(Y) 8.7e4
    and 5e-4 for κ(Y) 1e6, two leave ~5e-7 (970 columns).  The
    factorizations are k̃×k̃ — "small" in the paper's sense — so this
    stays matmul-dominated.  ``axis_name`` names the mesh axis that
    splits Y's rows (features), whose Grams and scale are reduced over
    it.
    """
    dt = Y.dtype
    Y = pow2_scale(Y.astype(jnp.float32), axis_name)
    Q = eigh_whiten(Y, gram(Y, axis_name))
    return cholesky_qr(cholesky_qr(Q, axis_name), axis_name).astype(dt)


def inv_sqrt_psd(M: jax.Array, eps: float = 0.0) -> jax.Array:
    """Symmetric inverse square root via eigh (small matrices only)."""
    w, V = jnp.linalg.eigh(sym(M))
    w = jnp.maximum(w, 0.0) + eps
    return (V * (1.0 / jnp.sqrt(w))) @ V.T


def topk_svd(F: jax.Array, k: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k SVD of a small dense matrix (paper line 22)."""
    U, S, Vt = jnp.linalg.svd(F, full_matrices=False)
    return U[:, :k], S[:k], Vt[:k, :].T
