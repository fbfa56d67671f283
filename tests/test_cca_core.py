"""CCA core correctness: Algorithm 1 vs the exact oracle, streaming
equivalence, centering, Horst baseline and warm-start (paper claims)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    HorstConfig,
    cca_objective,
    exact_cca,
    feasibility_errors,
    horst_cca,
    randomized_cca,
    randomized_cca_iterator,
    randomized_cca_streaming,
    streamed_feasibility_errors,
)
from repro.core.rcca import RCCAConfig
from repro.data import planted_views


@pytest.fixture(scope="module")
def views():
    A, B = planted_views(0, n=3000, da=48, db=40, rank=6, noise=0.4)
    return jnp.asarray(A), jnp.asarray(B)


LAM = 1e-3
K = 5


def test_exact_oracle_feasible(views):
    A, B = views
    sol = exact_cca(A, B, K, LAM, LAM)
    errs = feasibility_errors(A, B, sol.Xa, sol.Xb, LAM, LAM)
    for name, v in errs.items():
        assert float(v) < 1e-4, (name, float(v))
    # canonical correlations are in (0, 1] and sorted
    rho = np.asarray(sol.rho)
    assert np.all(rho[:-1] >= rho[1:] - 1e-6)
    assert np.all(rho > 0) and np.all(rho <= 1 + 1e-5)


def test_rcca_matches_exact(views):
    A, B = views
    ex = exact_cca(A, B, K, LAM, LAM)
    cfg = RCCAConfig(k=K, p=24, q=2, lam_a=LAM, lam_b=LAM)
    r = randomized_cca(A, B, cfg, jax.random.PRNGKey(1))
    # objective within 1% of exact optimum
    assert float(jnp.sum(r.rho)) > 0.99 * float(jnp.sum(ex.rho))
    # feasible to (near) machine precision — paper §4
    errs = feasibility_errors(A, B, r.Xa, r.Xb, LAM, LAM)
    for name, v in errs.items():
        assert float(v) < 1e-4, (name, float(v))


def test_streamed_feasibility_matches_materialized(views):
    """The chunked residuals (k × k Grams folded over row chunks) agree
    with the one-shot form: the same sums in another order."""
    A, B = views
    sol = exact_cca(A, B, K, LAM, LAM)
    chunks = [(A[i:i + 700], B[i:i + 700]) for i in range(0, A.shape[0], 700)]
    got = streamed_feasibility_errors(chunks, sol.Xa, sol.Xb, LAM, LAM)
    want = feasibility_errors(A, B, sol.Xa, sol.Xb, LAM, LAM)
    for name in want:
        assert abs(float(got[name]) - float(want[name])) < 1e-5, name


def test_rcca_objective_matches_rho(views):
    """(1/n)Tr(XaᵀAᵀBXb) must equal Σρ (definition consistency)."""
    A, B = views
    cfg = RCCAConfig(k=K, p=24, q=2, lam_a=LAM, lam_b=LAM)
    r = randomized_cca(A, B, cfg, jax.random.PRNGKey(1))
    obj = float(cca_objective(A, B, r.Xa, r.Xb))
    assert abs(obj - float(jnp.sum(r.rho))) < 1e-2


def test_streaming_equals_inmemory(views):
    A, B = views
    cfg = RCCAConfig(k=K, p=16, q=1, lam_a=LAM, lam_b=LAM)
    r_mem = randomized_cca(A, B, cfg, jax.random.PRNGKey(1))
    Ac = A.reshape(10, 300, A.shape[1])
    Bc = B.reshape(10, 300, B.shape[1])
    r_str = randomized_cca_streaming(Ac, Bc, cfg, jax.random.PRNGKey(1))
    np.testing.assert_allclose(np.asarray(r_mem.rho), np.asarray(r_str.rho), atol=1e-4)


def test_streaming_kernel_path(views):
    A, B = views
    cfg = RCCAConfig(k=K, p=16, q=1, lam_a=LAM, lam_b=LAM)
    Ac = A.reshape(10, 300, A.shape[1])
    Bc = B.reshape(10, 300, B.shape[1])
    r0 = randomized_cca_streaming(Ac, Bc, cfg, jax.random.PRNGKey(1))
    r1 = randomized_cca_streaming(Ac, Bc, cfg, jax.random.PRNGKey(1), use_kernels=True)
    np.testing.assert_allclose(np.asarray(r0.rho), np.asarray(r1.rho), atol=1e-4)


def test_iterator_resume_equivalence(views):
    """Fault tolerance: a run killed mid-pass and resumed must agree."""
    A, B = views
    da, db = A.shape[1], B.shape[1]
    cfg = RCCAConfig(k=K, p=12, q=1, lam_a=LAM, lam_b=LAM)
    chunks = [(np.asarray(A[i::4]), np.asarray(B[i::4])) for i in range(4)]

    full = randomized_cca_iterator(lambda: iter(chunks), da, db, cfg, jax.random.PRNGKey(2))

    # capture state mid final pass (pass_idx=1 after q=1 power pass)
    snap = {}

    def capture(pass_idx, chunk_idx, acc, Qa, Qb):
        if pass_idx == 1 and chunk_idx == 1:
            snap["state"] = {
                "pass_idx": 1, "chunk_idx": 2, "acc": acc.state(),
                "Qa": Qa, "Qb": Qb,
            }

    randomized_cca_iterator(lambda: iter(chunks), da, db, cfg,
                            jax.random.PRNGKey(2), on_pass_end=capture)
    resumed = randomized_cca_iterator(
        lambda: iter(chunks), da, db, cfg, jax.random.PRNGKey(2),
        resume_state=snap["state"],
    )
    np.testing.assert_allclose(np.asarray(full.rho), np.asarray(resumed.rho), atol=1e-5)


def test_centering_matches_exact(views):
    A, B = views
    A2, B2 = A + 5.0, B - 3.0
    ex = exact_cca(A2, B2, K, LAM, LAM, do_center=True)
    cfg = RCCAConfig(k=K, p=24, q=2, lam_a=LAM, lam_b=LAM, center=True)
    r = randomized_cca(A2, B2, cfg, jax.random.PRNGKey(1))
    assert float(jnp.sum(r.rho)) > 0.99 * float(jnp.sum(ex.rho))


def test_scale_free_regularization(views):
    """ν-parameterization: λ = ν·Tr(XᵀX)/d (paper §4)."""
    A, B = views
    cfg = RCCAConfig(k=K, p=16, q=1, nu=0.01)
    r = randomized_cca(A, B, cfg, jax.random.PRNGKey(1))
    expect_a = 0.01 * float(jnp.sum(A**2)) / A.shape[1]
    assert abs(float(r.diagnostics["lam_a"]) - expect_a) / expect_a < 1e-4


def test_horst_matches_exact(views):
    A, B = views
    ex = exact_cca(A, B, K, LAM, LAM)
    # convergence rate is set by the ρ_k/ρ_{k+1} eigengap — the planted
    # corpus has a small one, so give the power method room
    h = horst_cca(A, B, HorstConfig(k=K, iters=120, lam_a=LAM, lam_b=LAM),
                  key=jax.random.PRNGKey(3))
    np.testing.assert_allclose(np.asarray(h.rho), np.asarray(ex.rho), atol=1e-3)
    # objective history is (eventually) monotone non-decreasing
    hist = np.asarray(h.objective_history)
    assert hist[-1] >= hist[5] - 1e-4


def test_horst_cg_solver(views):
    """Approximate LS solves (paper fn.5) still converge."""
    A, B = views
    ex = exact_cca(A, B, K, LAM, LAM)
    h = horst_cca(A, B, HorstConfig(k=K, iters=60, lam_a=LAM, lam_b=LAM,
                                    solver="cg", cg_iters=8),
                  key=jax.random.PRNGKey(3))
    assert float(np.sum(np.asarray(h.rho))) > 0.98 * float(jnp.sum(ex.rho))


def test_horst_rcca_warmstart_faster(views):
    """Paper claim: RandomizedCCA is an excellent Horst initializer
    (120 → 34 passes).  With warm start, hitting 99.9% of optimum takes
    strictly fewer iterations than from a random start."""
    A, B = views
    ex = exact_cca(A, B, K, LAM, LAM)
    target = 0.999 * float(jnp.sum(ex.rho))

    cold = horst_cca(A, B, HorstConfig(k=K, iters=40, lam_a=LAM, lam_b=LAM),
                     key=jax.random.PRNGKey(4))
    r = randomized_cca(A, B, RCCAConfig(k=K, p=16, q=1, lam_a=LAM, lam_b=LAM),
                       jax.random.PRNGKey(5))
    warm = horst_cca(A, B, HorstConfig(k=K, iters=40, lam_a=LAM, lam_b=LAM),
                     init_Xb=r.Xb)

    def first_hit(hist):
        idx = np.nonzero(np.asarray(hist) >= target)[0]
        return int(idx[0]) if len(idx) else 10_000

    assert first_hit(warm.objective_history) < first_hit(cold.objective_history)


def test_streaming_horst_and_warmstart_passes(views):
    """Out-of-core Horst (CG solves via shared data passes) converges,
    and the rcca warm start cuts the data-pass count ~5× — the paper's
    Table 2b claim (120 → 34 passes) in pass-count currency."""
    from repro.core.horst import horst_cca_streaming

    A, B = views
    ex = exact_cca(A, B, K, LAM, LAM)
    chunks = lambda: ((A[i::4], B[i::4]) for i in range(4))

    cold = horst_cca_streaming(chunks, A.shape[1], B.shape[1],
                               HorstConfig(k=K, iters=25, cg_iters=4),
                               key=jax.random.PRNGKey(3), lam_a=LAM, lam_b=LAM)
    cold_passes = float(cold.objective_history[0])
    assert float(jnp.sum(cold.rho)) > 0.985 * float(jnp.sum(ex.rho))

    r = randomized_cca(A, B, RCCAConfig(k=K, p=16, q=1, lam_a=LAM, lam_b=LAM),
                       jax.random.PRNGKey(5))
    warm = horst_cca_streaming(chunks, A.shape[1], B.shape[1],
                               HorstConfig(k=K, iters=5, cg_iters=4),
                               init_Xb=r.Xb, init_Xa=r.Xa, lam_a=LAM, lam_b=LAM)
    warm_passes = float(warm.objective_history[0]) + (1 + 1)  # + rcca's q+1
    assert float(jnp.sum(warm.rho)) > 0.985 * float(jnp.sum(ex.rho))
    assert warm_passes < cold_passes / 3  # ≥3× fewer data passes


def test_rcca_warmstart_cuts_horst_sweeps(views):
    """Paper Table 2b (Horst+rcca): warm-starting the Horst iteration
    from the RandomizedCCA output reaches the same correlation in
    measurably fewer sweeps than a random init (seeded, tolerance on a
    fixed target).  Uses the streaming Horst — sweeps are data passes."""
    from repro.core.horst import horst_cca_streaming

    A, B = views
    da, db = A.shape[1], B.shape[1]

    def src():
        for lo in range(0, A.shape[0], 750):
            yield np.asarray(A[lo:lo + 750]), np.asarray(B[lo:lo + 750])

    ex = exact_cca(A, B, K, LAM, LAM)
    # calibrated so the verdict has margin on both sides: at 0.997·opt
    # the cold start sits at 0.9911 after one sweep (clearly below) and
    # the warm start at 0.9983 (clearly above)
    target = 0.997 * float(jnp.sum(ex.rho))
    rc = randomized_cca(A, B, RCCAConfig(k=K, p=16, q=1, lam_a=LAM, lam_b=LAM),
                        jax.random.PRNGKey(7))

    def min_sweeps(**init):
        for iters in (1, 2, 3, 4, 6, 8):
            h = horst_cca_streaming(
                src, da, db, HorstConfig(k=K, iters=iters, cg_iters=2),
                key=jax.random.PRNGKey(11), lam_a=LAM, lam_b=LAM, **init)
            if float(jnp.sum(h.rho)) >= target:
                return iters
        return 99

    warm = min_sweeps(init_Xb=rc.Xb, init_Xa=rc.Xa)
    cold = min_sweeps()
    assert warm < cold, (warm, cold)
    assert warm <= max(1, cold // 2), (warm, cold)  # ≥2× fewer sweeps
