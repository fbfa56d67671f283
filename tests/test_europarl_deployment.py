"""The paper's Europarl deployment, cut to a CPU, against a plain reference.

Paired token bags are hashed on the host by ``HashingFeaturizer`` for
every chunk of every pass and fed to ``randomized_cca_iterator`` (the
out-of-core entry over ``PassEngine.run_stream``): d 4096 slots per
view, k 6, p 90, q 1, ν 0.01, 16 chunks of 64 rows in 2 merge groups.
The reference is Algorithm 1 written out here at f32 ``highest``, with
a Householder QR for ``orth``; the program's own in-memory
``randomized_cca`` shares its ``orth`` and so cannot judge it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.scipy.linalg import solve_triangular

from repro.core.rcca import RCCAConfig, randomized_cca_iterator
from repro.data import HashingFeaturizer, synth_paired_docs

D, K, P, CHUNK, N_CHUNKS, NU = 4096, 6, 90, 64, 16, 0.01
N = CHUNK * N_CHUNKS
HI = jax.lax.Precision.HIGHEST

# ρ, the cross-covariance residual and the feasibility residual read
# 4e-7–2.6e-6 (CPU, seeds 1–3): f32 sums over 1024 rows in another order
# and other k̃ × k̃ factorizations.  1e-5 leaves ~4× room above that.
RHO_TOL = CROSS_TOL = FEAS_TOL = 1e-5
# ‖QᵀQ − I‖_max of the fit's bases reads ~1.2e-6; an orth that solves
# with L⁻¹ where L⁻ᵀ is needed reads 0.8–1.4 here (κ(Y) ≈ 4e3).
ORTHONORMAL_TOL = 1e-5


def _dot(x, y):
    return jnp.matmul(x, y, precision=HI)


def plain_rcca(A, B, key):
    """Algorithm 1 with q = 1: Ω from ``key`` as the program draws it,
    Householder ``orth``, λ = ν Tr(AᵀA) / d.  Returns ρ, Xa, Xb, λa, λb
    and the power pass's bases (Qa, Qb) and sketches (Ya, Yb)."""
    kt = K + P
    ka, kb = jax.random.split(key)
    Qa = jax.random.normal(ka, (D, kt), jnp.float32)
    Qb = jax.random.normal(kb, (D, kt), jnp.float32)
    Ya, Yb = _dot(A.T, _dot(B, Qb)), _dot(B.T, _dot(A, Qa))
    Qa, Qb = jnp.linalg.qr(Ya)[0], jnp.linalg.qr(Yb)[0]
    Pa, Pb = _dot(A, Qa), _dot(B, Qb)
    lam_a, lam_b = NU * jnp.sum(A * A) / D, NU * jnp.sum(B * B) / D
    sym = lambda M: 0.5 * (M + M.T)
    La = jnp.linalg.cholesky(sym(_dot(Pa.T, Pa) + lam_a * _dot(Qa.T, Qa)))
    Lb = jnp.linalg.cholesky(sym(_dot(Pb.T, Pb) + lam_b * _dot(Qb.T, Qb)))
    Fw = solve_triangular(La, _dot(Pa.T, Pb), lower=True)
    Fw = solve_triangular(Lb, Fw.T, lower=True).T
    U, S, Vt = jnp.linalg.svd(Fw, full_matrices=False)
    Xa = np.sqrt(N) * _dot(Qa, solve_triangular(La.T, U[:, :K], lower=False))
    Xb = np.sqrt(N) * _dot(Qb, solve_triangular(Lb.T, Vt[:K].T, lower=False))
    return {"rho": np.asarray(S[:K]), "Xa": np.asarray(Xa), "Xb": np.asarray(Xb),
            "lam": (float(lam_a), float(lam_b)), "Y": (Ya, Yb)}


@pytest.fixture(scope="module", params=[1, 2**31 + 3])
def fit_and_ref(request):
    seed = request.param
    docs_a, docs_b = synth_paired_docs(N, vocab=50_000, doc_len=30, seed=seed % 2**32)
    fa, fb = HashingFeaturizer(D, seed=1), HashingFeaturizer(D, seed=2)

    def source():
        for i in range(N_CHUNKS):
            rows = slice(i * CHUNK, (i + 1) * CHUNK)
            yield fa.featurize_batch(docs_a[rows]), fb.featurize_batch(docs_b[rows])

    key = jax.random.PRNGKey(seed % 2**31)
    res = randomized_cca_iterator(source, D, D, RCCAConfig(k=K, p=P, q=1, nu=NU), key,
                                  n_chunks=N_CHUNKS)
    A = jnp.asarray(fa.featurize_batch(docs_a))
    B = jnp.asarray(fb.featurize_batch(docs_b))
    return res, plain_rcca(A, B, key), (np.asarray(A, np.float64), np.asarray(B, np.float64))


def test_hashed_fit_matches_plain_reference(fit_and_ref):
    res, ref, (A, B) = fit_and_ref
    rho = np.asarray(res.rho)
    assert np.all(np.isfinite(rho))
    assert np.abs(rho - ref["rho"]).max() <= RHO_TOL
    # the fit's projections solve the reference's CCA: Xaᵀ AᵀB Xb / n is
    # diag(ρ_ref), and (Xᵀ AᵀA X + λ XᵀX) / n is I in each view
    Xa, Xb = np.asarray(res.Xa, np.float64), np.asarray(res.Xb, np.float64)
    Pa, Pb = A @ Xa, B @ Xb
    assert np.abs(Pa.T @ Pb / N - np.diag(ref["rho"])).max() <= CROSS_TOL
    for Pv, X, lam in ((Pa, Xa, ref["lam"][0]), (Pb, Xb, ref["lam"][1])):
        assert np.abs((Pv.T @ Pv + lam * X.T @ X) / N - np.eye(K)).max() <= FEAS_TOL


def test_hashed_fit_bases_are_orthonormal_and_span_the_sketch(fit_and_ref):
    """The final pass's Qa, Qb (the fit's ``orth`` of the power pass's
    sketches) are orthonormal and hold the reference's sketches."""
    res, ref, _ = fit_and_ref
    for Q, Y in zip((res.Qa, res.Qb), ref["Y"]):
        Q, Y = np.asarray(Q, np.float64), np.asarray(Y, np.float64)
        assert np.abs(Q.T @ Q - np.eye(K + P)).max() <= ORTHONORMAL_TOL
        assert np.linalg.norm(Y - Q @ (Q.T @ Y)) / np.linalg.norm(Y) <= 1e-5
