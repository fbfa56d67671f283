"""The plain reference: Algorithm 1 of Mineiro & Karampatziakis (2014)
and the projection it serves, written from the paper and from the
featurizer's definition, importing nothing of the program.

Everything runs in float32 over blocks of rows, so that it fits on one
chip beside the corpus.  ``precision="highest"`` is the configuration's
own precision (f32 matmuls at full precision).  ``precision="high"`` is
the control: every matmul in three bf16 passes (hi*hi + hi*lo + lo*hi,
f32 accumulation), written out so that it computes the same on any
backend.
"""

from __future__ import annotations

import numpy as np


# -- feature hashing (Weinberger et al., 2009), as the featurizer defines it


def _mix(x: np.ndarray, seed: int) -> np.ndarray:
    """splitmix64 finalizer of ``x + seed * golden``."""
    with np.errstate(over="ignore"):
        x = x.astype(np.uint64) + np.uint64((seed * 0x9E3779B97F4A7C15) % 2**64)
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    return x


def hash_slots(tokens: np.ndarray, n_slots: int, seed: int) -> np.ndarray:
    return (_mix(tokens, seed) % np.uint64(n_slots)).astype(np.int32)


def hash_signs(tokens: np.ndarray, seed: int) -> np.ndarray:
    return np.where(_mix(tokens, seed + 1) & np.uint64(1), 1.0, -1.0).astype(np.float32)


def hashed_rows(tokens: np.ndarray, d: int, seed: int):
    """Dense ``(rows, d)`` f32 device array of a padded token matrix
    (0 = pad): each token adds its sign at its slot."""
    import jax.numpy as jnp

    valid = tokens > 0
    rows = np.broadcast_to(np.arange(tokens.shape[0])[:, None], tokens.shape)
    slots = hash_slots(np.where(valid, tokens, 1), d, seed)
    signs = np.where(valid, hash_signs(np.where(valid, tokens, 1), seed), 0.0)
    return _scatter(jnp.asarray(rows.astype(np.int32)), jnp.asarray(slots),
                    jnp.asarray(signs.astype(np.float32)), tokens.shape[0], d)


_SCATTER = {}


def _scatter(rows, slots, signs, n, d):
    import jax
    import jax.numpy as jnp

    if (n, d) not in _SCATTER:
        _SCATTER[n, d] = jax.jit(
            lambda r, s, v: jnp.zeros((n, d), jnp.float32).at[r, s].add(v))
    return _SCATTER[n, d](rows, slots, signs)


def hashed_projection(tokens: np.ndarray, X: np.ndarray, seed: int) -> np.ndarray:
    """``hash(doc) @ X`` for one padded doc, in float64 on the host."""
    valid = tokens > 0
    t = tokens[valid]
    slots = hash_slots(t, X.shape[0], seed)
    signs = hash_signs(t, seed).astype(np.float64)
    return signs @ X[slots].astype(np.float64)


# -- Algorithm 1


def _dot(precision: str):
    import jax
    import jax.numpy as jnp

    if precision == "highest":
        return lambda x, y: jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        def dot3(x, y):
            xh = x.astype(jnp.bfloat16)
            xl = (x - xh.astype(jnp.float32)).astype(jnp.bfloat16)
            yh = y.astype(jnp.bfloat16)
            yl = (y - yh.astype(jnp.float32)).astype(jnp.bfloat16)
            mm = lambda u, v: jnp.matmul(u, v, preferred_element_type=jnp.float32)
            return mm(xh, yh) + (mm(xh, yl) + mm(xl, yh))
        return dot3
    raise ValueError(f"unknown precision {precision!r}")


def sketch_probe(Y, G):
    """``Y @ G`` at full f32 precision: a few columns that stand for Y."""
    import jax
    import jax.numpy as jnp

    return jnp.matmul(Y, G, precision=jax.lax.Precision.HIGHEST)


def rel_err(x: np.ndarray, ref: np.ndarray) -> float:
    """``||x - ref||_F / ||ref||_F`` in float64."""
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(x - ref) / max(np.linalg.norm(ref), 1e-300))


def rcca(blocks, n_blocks: int, da: int, db: int, *, k: int, p: int, q: int,
         nu: float, center: bool, key, precision: str = "highest",
         probe=None) -> dict:
    """Algorithm 1 over ``blocks(i) -> (A_i, B_i)``, ``i < n_blocks``.

    Omega is drawn as the algorithm states: a standard Gaussian
    ``(d, k + p)`` per view from one split of ``key``.  Orth is a
    reduced QR.  lambda = nu * Tr(A^T A) / d on the data as given.
    Returns rho, Xa, Xb and lambda as host arrays.  With ``probe =
    (Ga, Gb)`` and q >= 1 it also returns ``y_probe``: the first power
    pass's sketch of the data as given, ``(A^T B Omega_b) Ga`` and
    ``(B^T A Omega_a) Gb``.
    """
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    dot = _dot(precision)
    jdot = jax.jit(dot)
    T = lambda x: x.T
    kt = k + p
    with jax.default_matmul_precision(precision):
        ka, kb = jax.random.split(key)
        Qa = jax.random.normal(ka, (da, kt), f32)
        Qb = jax.random.normal(kb, (db, kt), f32)

        n = 0
        sa, sb = jnp.zeros(da, f32), jnp.zeros(db, f32)
        tr_a = tr_b = jnp.zeros((), f32)
        for i in range(n_blocks):
            A, B = blocks(i)
            n += A.shape[0]
            sa, sb = sa + A.sum(0), sb + B.sum(0)
            tr_a, tr_b = tr_a + jnp.sum(A * A), tr_b + jnp.sum(B * B)
        mu_a = sa / n if center else jnp.zeros(da, f32)
        mu_b = sb / n if center else jnp.zeros(db, f32)

        def centered(i):
            A, B = blocks(i)
            return A - mu_a, B - mu_b

        def power_pass(blocks_i):
            Ya, Yb = jnp.zeros((da, kt), f32), jnp.zeros((db, kt), f32)
            for i in range(n_blocks):
                A, B = blocks_i(i)
                Ya = Ya + jdot(T(A), jdot(B, Qb))
                Yb = Yb + jdot(T(B), jdot(A, Qa))
            return Ya, Yb

        y_probe = None
        for it in range(q):
            Ya, Yb = power_pass(centered)
            if probe is not None and it == 0:
                raw = (Ya, Yb) if not center else power_pass(blocks)
                y_probe = tuple(np.asarray(sketch_probe(y, g)) for y, g in zip(raw, probe))
                del raw
            Qa, _ = jnp.linalg.qr(Ya)
            Qb, _ = jnp.linalg.qr(Yb)

        Ca, Cb, F = (jnp.zeros((kt, kt), f32) for _ in range(3))
        for i in range(n_blocks):
            A, B = centered(i)
            Pa, Pb = jdot(A, Qa), jdot(B, Qb)
            Ca, Cb, F = Ca + jdot(T(Pa), Pa), Cb + jdot(T(Pb), Pb), F + jdot(T(Pa), Pb)

        lam_a, lam_b = nu * tr_a / da, nu * tr_b / db
        sym = lambda M: 0.5 * (M + M.T)
        La = jnp.linalg.cholesky(sym(Ca + lam_a * jdot(T(Qa), Qa)))
        Lb = jnp.linalg.cholesky(sym(Cb + lam_b * jdot(T(Qb), Qb)))
        tri = jax.scipy.linalg.solve_triangular
        Fw = tri(La, F, lower=True)                  # La^-1 F
        Fw = tri(Lb, Fw.T, lower=True).T             # ... Lb^-T
        U, S, Vt = jnp.linalg.svd(Fw, full_matrices=False)
        sqn = jnp.sqrt(jnp.asarray(n, f32))
        Xa = sqn * jdot(Qa, tri(La.T, U[:, :k], lower=False))
        Xb = sqn * jdot(Qb, tri(Lb.T, Vt[:k].T, lower=False))
    out = {"rho": np.asarray(S[:k]), "Xa": np.asarray(Xa), "Xb": np.asarray(Xb),
           "lam": (float(lam_a), float(lam_b)), "n": n,
           "mu": (np.asarray(mu_a), np.asarray(mu_b))}
    if y_probe is not None:
        out["y_probe"] = y_probe
    return out


def solution_residuals(blocks, n_blocks: int, Xa, Xb, lam: tuple, mu: tuple,
                       rho_ref) -> dict:
    """How far ``(Xa, Xb)`` is from a CCA solution of the data with the
    reference's correlations, at full f32 precision:

    * ``cross``: max |Xa^T Abar^T Bbar Xb / n - diag(rho_ref)|
    * ``feas``: max over views of |(X^T Abar^T Abar X + lam X^T X) / n - I|
    """
    import jax
    import jax.numpy as jnp

    dot = jax.jit(_dot("highest"))
    Xa, Xb = jnp.asarray(Xa), jnp.asarray(Xb)
    k = Xa.shape[1]
    Gaa, Gbb, Gab = (jnp.zeros((k, k), jnp.float32) for _ in range(3))
    n = 0
    with jax.default_matmul_precision("highest"):
        for i in range(n_blocks):
            A, B = blocks(i)
            n += A.shape[0]
            Pa, Pb = dot(A - mu[0], Xa), dot(B - mu[1], Xb)
            Gaa, Gbb, Gab = Gaa + dot(Pa.T, Pa), Gbb + dot(Pb.T, Pb), Gab + dot(Pa.T, Pb)
        eye = jnp.eye(k, dtype=jnp.float32)
        fa = (Gaa + lam[0] * dot(Xa.T, Xa)) / n - eye
        fb = (Gbb + lam[1] * dot(Xb.T, Xb)) / n - eye
        cross = Gab / n - jnp.diag(jnp.asarray(rho_ref, jnp.float32))
    return {"cross": float(jnp.max(jnp.abs(cross))),
            "feas": float(max(jnp.max(jnp.abs(fa)), jnp.max(jnp.abs(fb))))}
