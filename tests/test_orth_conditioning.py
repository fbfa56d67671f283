"""``orth`` at the condition numbers a fit's sketch really has.

Power iteration squares κ: the first-pass sketch of a hashed bilingual
corpus at k̃ = 970 has κ(Y) ≈ 8.7e4, and its Gram's κ is past f32's
reach.  Each case builds Y = U diag(s) Vᵀ with 970 singular values
log-spaced over κ and asks of ``orth`` (and of ``dist_orth`` on one
feature shard, the same code) what Algorithm 1's line 11 asks: an
orthonormal basis whose range holds Y, as a Householder QR gives.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.linalg import full_f32, orth
from repro.core.rcca_dist import dist_orth

ROWS, COLS = 4096, 970
KAPPAS = (1e2, 1e4, 8.7e4, 1e6)
ORTHS = {"orth": orth, "dist_orth": functools.partial(dist_orth, col_axis=None)}

# ‖QᵀQ − I‖_max: an eigh-whitened round then CholeskyQR2 reads 3e-7–5e-7
# on every κ here; one CholeskyQR round after the eigh reads 3.5e-6 at
# κ 8.7e4 and 5.4e-4 at κ 1e6 (16384 rows), and a round that solves with
# L⁻¹ where L⁻ᵀ is needed reads 1.6e-5 at κ 1e2 and 2.2 at κ 1e4.
ORTHONORMAL_TOL = 1e-5
# ‖Y − Q QᵀY‖_F / ‖Y‖_F: a Householder QR reads 3.3e-7 on every κ here
# (the f32 rounding of Y); orth reads the same up to κ 8.7e4 and 1.1e-6
# at κ 1e6, where its rounds apply L⁻ᵀ by a matmul whose rounding grows
# with κ(L).
RANGE_TOL = 2e-6


@pytest.fixture(scope="module")
def bases():
    rng = np.random.default_rng(0)
    U, _ = np.linalg.qr(rng.standard_normal((ROWS, COLS)))
    V, _ = np.linalg.qr(rng.standard_normal((COLS, COLS)))
    return U, V


def _conditioned(bases, kappa):
    U, V = bases
    s = np.logspace(0, -np.log10(kappa), COLS)
    return jnp.asarray(((U * s) @ V.T).astype(np.float32))


def _orthonormality(Q):
    Q = np.asarray(Q, np.float64)
    return np.abs(Q.T @ Q - np.eye(Q.shape[1])).max()


def _range_residual(Q, Y):
    """‖Y − Q QᵀY‖_F / ‖Y‖_F in float64: how much of Y lies outside
    range(Q) (Q orthonormal)."""
    Q, Y = np.asarray(Q, np.float64), np.asarray(Y, np.float64)
    return np.linalg.norm(Y - Q @ (Q.T @ Y)) / np.linalg.norm(Y)


@pytest.mark.parametrize("kappa", KAPPAS)
@pytest.mark.parametrize("name", sorted(ORTHS))
def test_orth_holds_at_sketch_conditioning(bases, name, kappa):
    Y = _conditioned(bases, kappa)
    Q = jax.jit(full_f32(ORTHS[name]))(Y)
    assert Q.shape == Y.shape and Q.dtype == Y.dtype
    assert _orthonormality(Q) <= ORTHONORMAL_TOL
    # range(Q) = range(Y): Y lies in range(Q) about as closely as in the
    # range of a Householder QR of the same Y
    Qh = jnp.linalg.qr(Y)[0]
    assert _range_residual(Qh, Y) <= RANGE_TOL
    assert _range_residual(Q, Y) <= RANGE_TOL


@pytest.mark.parametrize("name", sorted(ORTHS))
def test_orth_gram_does_not_overflow(name):
    """Entries near 1e19 square past f32's range in YᵀY; orth scales Y
    by a power of two first, so its range is the same as at unit scale."""
    rng = np.random.default_rng(1)
    Y = rng.standard_normal((ROWS, 64)).astype(np.float32)
    big = jnp.asarray(Y * np.float32(1e19))
    f = jax.jit(full_f32(ORTHS[name]))
    Q = f(big)
    assert bool(jnp.all(jnp.isfinite(Q)))
    assert _orthonormality(Q) <= ORTHONORMAL_TOL
    assert _range_residual(Q, big) <= RANGE_TOL
    Q, Q1 = np.asarray(Q, np.float64), np.asarray(f(jnp.asarray(Y)), np.float64)
    np.testing.assert_allclose(Q @ Q.T, Q1 @ Q1.T, atol=1e-5)
