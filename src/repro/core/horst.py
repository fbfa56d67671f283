"""Horst iteration for CCA — the paper's baseline (§2, Table 2b).

Gauss-Seidel variant of the Horst/orthogonal power method for the
multivariate eigenvalue problem (Chu & Watterson 1993; Zhang & Chu
2011): alternate regularized least-squares solves with block
normalization in the covariance metric.  One Horst iteration costs two
data passes (one per view); the paper budget is 120 passes.

Also implements ``Horst+rcca`` — initializing from a RandomizedCCA
solution — which the paper shows cuts 120 passes to ~34.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .linalg import full_f32, inv_sqrt_psd, sym


@dataclasses.dataclass(frozen=True)
class HorstConfig:
    k: int
    iters: int = 60  # each iteration = 2 data passes
    lam_a: float = 0.0
    lam_b: float = 0.0
    nu: Optional[float] = None
    solver: str = "chol"  # "chol" (exact, d³) | "cg" (approximate LS, paper fn.5)
    cg_iters: int = 10


class HorstResult(NamedTuple):
    Xa: jax.Array
    Xb: jax.Array
    rho: jax.Array
    objective_history: jax.Array  # (iters,) train objective per iteration


def _metric_normalize(W: jax.Array, M_mul, n: float) -> jax.Array:
    """X ← √n · W (Wᵀ M W)^{-1/2} so that Xᵀ M X = n I."""
    G = sym(W.T @ M_mul(W))
    return jnp.sqrt(n) * (W @ inv_sqrt_psd(G, eps=1e-12))


def _cg_solve(M_mul, RHS: jax.Array, iters: int) -> jax.Array:
    """Block conjugate gradient for M X = RHS (approximate LS, paper's
    footnote 5: solves need only be approximate for convergence)."""

    def body(carry, _):
        X, R, P, rs = carry
        MP = M_mul(P)
        alpha = rs / jnp.maximum(jnp.sum(P * MP, axis=0), 1e-30)
        X = X + P * alpha
        R = R - MP * alpha
        rs_new = jnp.sum(R * R, axis=0)
        beta = rs_new / jnp.maximum(rs, 1e-30)
        P = R + P * beta
        return (X, R, P, rs_new), None

    X0 = jnp.zeros_like(RHS)
    R0 = RHS
    (X, _, _, _), _ = jax.lax.scan(
        body, (X0, R0, R0, jnp.sum(R0 * R0, axis=0)), None, length=iters
    )
    return X


@full_f32
def horst_cca(
    A: jax.Array,
    B: jax.Array,
    cfg: HorstConfig,
    key: Optional[jax.Array] = None,
    init_Xb: Optional[jax.Array] = None,
) -> HorstResult:
    """Dense Horst iteration.  ``init_Xb`` warm-starts (Horst+rcca).

    At test scale we precompute the Gram matrices once; on a cluster the
    same recurrence runs as data passes (each matmul against A/B is a
    streamed shard_map pass exactly like rcca's — see rcca_dist).
    """
    n, da = A.shape
    db = B.shape[1]
    if cfg.nu is not None:
        lam_a = cfg.nu * jnp.sum(A.astype(jnp.float32) ** 2) / da
        lam_b = cfg.nu * jnp.sum(B.astype(jnp.float32) ** 2) / db
    else:
        lam_a, lam_b = cfg.lam_a, cfg.lam_b

    Caa = sym(A.T @ A)
    Cbb = sym(B.T @ B)
    Cab = A.T @ B

    Ma = lambda X: Caa @ X + lam_a * X
    Mb = lambda X: Cbb @ X + lam_b * X

    if cfg.solver == "chol":
        La = jnp.linalg.cholesky(Caa + lam_a * jnp.eye(da, dtype=A.dtype))
        Lb = jnp.linalg.cholesky(Cbb + lam_b * jnp.eye(db, dtype=B.dtype))
        solve_a = lambda R: jax.scipy.linalg.cho_solve((La, True), R)
        solve_b = lambda R: jax.scipy.linalg.cho_solve((Lb, True), R)
    else:
        solve_a = lambda R: _cg_solve(Ma, R, cfg.cg_iters)
        solve_b = lambda R: _cg_solve(Mb, R, cfg.cg_iters)

    if init_Xb is None:
        assert key is not None, "need a PRNG key for random init"
        Xb = jax.random.normal(key, (db, cfg.k), A.dtype)  # paper fn.5: Gaussian init
    else:
        Xb = init_Xb
    Xb = _metric_normalize(Xb, Mb, n)

    def step(Xb, _):
        Wa = solve_a(Cab @ Xb)  # LS solve: argmin ‖A Xa − B Xb‖² + λ‖Xa‖²
        Xa = _metric_normalize(Wa, Ma, n)
        Wb = solve_b(Cab.T @ Xa)  # Gauss-Seidel: uses fresh Xa
        Xb = _metric_normalize(Wb, Mb, n)
        obj = jnp.trace(Xa.T @ Cab @ Xb) / n
        return Xb, (Xa, obj)

    Xb, (Xas, objs) = jax.lax.scan(step, Xb, None, length=cfg.iters)
    Xa = Xas[-1]

    # rotate into canonical (diagonal cross-cov) coordinates
    T = Xa.T @ Cab @ Xb / n
    U, S, Vt = jnp.linalg.svd(T)
    Xa = Xa @ U
    Xb = Xb @ Vt.T
    return HorstResult(Xa=Xa, Xb=Xb, rho=S, objective_history=objs)


# ---------------------------------------------------------------------------
# streaming / out-of-core Horst (the paper's actual large-scale regime)
# ---------------------------------------------------------------------------


class StreamingGrams:
    """Gram-vector products as streamed data passes, with an explicit
    pass counter — the currency of the paper's Table 2b.  Never
    materializes AᵀA (O(d·k) state only)."""

    def __init__(self, source_factory):
        self.source_factory = source_factory
        self.passes = 0
        self.n = None

    def gram_a(self, V):
        """One pass → AᵀA·V (the view-A CG matvec)."""
        self.passes += 1
        G = None
        for a, _ in self.source_factory():
            u = a.T @ (a @ V)
            G = u if G is None else G + u
        return G

    def gram_b(self, V):
        """One pass → BᵀB·V."""
        self.passes += 1
        G = None
        for _, b in self.source_factory():
            u = b.T @ (b @ V)
            G = u if G is None else G + u
        return G

    def norm_cross_a(self, Wa):
        """One pass → (AᵀA·Wa, BᵀA·Wa): everything the A-side metric
        normalization AND the follow-up B-side cross product need —
        both are linear in Wa, so one pass serves both."""
        self.passes += 1
        U = V = None
        n = 0
        for a, b in self.source_factory():
            p = a @ Wa
            u, v = a.T @ p, b.T @ p
            U = u if U is None else U + u
            V = v if V is None else V + v
            n += a.shape[0]
        self.n = n
        return U, V

    def norm_cross_b(self, Wb):
        """One pass → (BᵀB·Wb, AᵀB·Wb)."""
        self.passes += 1
        U = V = None
        n = 0
        for a, b in self.source_factory():
            p = b @ Wb
            u, v = b.T @ p, a.T @ p
            U = u if U is None else U + u
            V = v if V is None else V + v
            n += a.shape[0]
        self.n = n
        return U, V


@full_f32
def horst_cca_streaming(
    source_factory,
    da: int,
    db: int,
    cfg: HorstConfig,
    key: Optional[jax.Array] = None,
    init_Xb: Optional[jax.Array] = None,
    init_Xa: Optional[jax.Array] = None,
    lam_a: float = 0.0,
    lam_b: float = 0.0,
) -> HorstResult:
    """Horst iteration with every matrix product a streamed data pass
    (paper §2: the multiplication step runs directly in the X coordinate
    system; AᵀA is never materialized).  The regularized LS solves use a
    few CG iterations whose matvecs are data passes — the paper's
    footnote-5 regime (approximate solves still converge).

    The update order is Gauss-Seidel, matching :func:`horst_cca`: the
    B-side solve uses the FRESH Xa.  (A simultaneous/Jacobi update of
    both views is not monotone for the Horst iteration and stalls in a
    limit cycle well below the optimum.)  Passes are shared where the
    dependency structure allows: each view's metric normalization and
    the other view's next cross product are both linear in the solved W,
    so one combined pass (norm_cross_*) serves both.  CG solves warm-
    start from the previous iteration's W.

    Pass cost per Horst iteration: 2·(cg_iters + warm-start residual)
    CG matvecs + 2 combined normalize+cross passes.  The total is in
    ``objective_history[0]`` via the StreamingGrams counter; use
    ``init_Xb`` from RandomizedCCA for the Horst+rcca warm start and
    compare pass counts with Alg. 1's q+1 (Table 2b).
    """
    k = cfg.k
    if init_Xb is None:
        assert key is not None
        Xb = jax.random.normal(jax.random.split(key)[1], (db, k), jnp.float32)
    else:
        Xb = jnp.asarray(init_Xb, jnp.float32)
    grams = StreamingGrams(source_factory)

    def cg_view(gram_fn, lam, R, W0):
        """CG on (G + λ)W = R; W0=None starts from zero (saves the
        warm-start residual pass)."""
        if W0 is None:
            W, r = jnp.zeros_like(R), R
        else:
            W = W0
            r = R - (gram_fn(W0) + lam * W0)
        p, rs = r, jnp.sum(r * r, 0)
        for _ in range(cfg.cg_iters):
            Gp = gram_fn(p) + lam * p
            alpha = rs / jnp.maximum(jnp.sum(p * Gp, 0), 1e-30)
            W = W + p * alpha
            r = r - Gp * alpha
            rs2 = jnp.sum(r * r, 0)
            p = r + p * (rs2 / jnp.maximum(rs, 1e-30))
            rs = rs2
        return W

    # bootstrap: normalize the initial Xb in the B metric and produce the
    # first A-side RHS Ra = AᵀB·Xb — one combined pass
    Ub, Va = grams.norm_cross_b(Xb)
    n = grams.n
    Tb = inv_sqrt_psd(sym(Xb.T @ Ub) + lam_b * sym(Xb.T @ Xb), eps=1e-12)
    Xb = jnp.sqrt(n) * (Xb @ Tb)
    Ra = jnp.sqrt(n) * (Va @ Tb)

    Wa = jnp.asarray(init_Xa, jnp.float32) if init_Xa is not None else None
    Wb = None
    # iters=0 (warm-start evaluation only): the loop never assigns Xa
    Xa = Wa if Wa is not None else jax.random.normal(
        key if key is not None else jax.random.PRNGKey(0), (da, k), jnp.float32)
    for _ in range(cfg.iters):
        # view A: LS solve, then one pass for (normalization, B-side RHS)
        Wa = cg_view(grams.gram_a, lam_a, Ra, Wa)
        Ua, Vb = grams.norm_cross_a(Wa)
        Ta = inv_sqrt_psd(sym(Wa.T @ Ua) + lam_a * sym(Wa.T @ Wa), eps=1e-12)
        Xa = jnp.sqrt(n) * (Wa @ Ta)
        Rb = jnp.sqrt(n) * (Vb @ Ta)  # = BᵀA·Xa — Gauss-Seidel: fresh Xa
        # view B likewise; its combined pass yields the next Ra
        Wb = cg_view(grams.gram_b, lam_b, Rb, Wb)
        Ub, Va = grams.norm_cross_b(Wb)
        Tb = inv_sqrt_psd(sym(Wb.T @ Ub) + lam_b * sym(Wb.T @ Wb), eps=1e-12)
        Xb = jnp.sqrt(n) * (Wb @ Tb)
        Ra = jnp.sqrt(n) * (Va @ Tb)

    # canonical rotation + objective: Ra is already AᵀB·Xb for the final Xb
    F = Xa.T @ Ra / n
    U, S, Vt = jnp.linalg.svd(F)
    return HorstResult(Xa=Xa @ U, Xb=Xb @ Vt.T, rho=S,
                       objective_history=jnp.asarray([grams.passes], jnp.float32))
