"""RandomizedCCA — Algorithm 1 of Mineiro & Karampatziakis (2014).

Three entry points, sharing one "finish" (paper lines 19-25):

- :func:`randomized_cca` — paper-faithful in-memory version (the ref).
- :func:`randomized_cca_streaming` / :func:`randomized_cca_iterator` —
  out-of-core semantics: each data pass is a fold over row chunks with
  explicit, checkpointable accumulator state.  Both are shells over
  the ONE pass engine in :mod:`repro.exec`, which also runs the same
  passes device-parallel (``Sharded``), multi-process (``Cluster``)
  and both at once (``Hybrid``).
- the feature-sharded resident-mode version lives in
  :mod:`repro.core.rcca_dist` (shard_map over a (pod, data, model)
  mesh, psums inside the pass).

Every execution topology accumulates in the same CANONICAL ORDER —
chunks left-fold into fixed-size merge groups, group sums reduce
through a fixed pairwise tree (see :mod:`repro.exec.accumulate`) — so
their results agree bitwise: the cluster coordinator's merge of
per-worker partials (:func:`merge_power_stats` /
:func:`merge_final_stats` are exact combiners — every accumulator
field is a plain sum over rows) is bit-identical to a single-process
pass for any worker count and any devices-per-worker layout.

Mean-centering is the paper's §3 rank-one update: column sums are
accumulated alongside each pass (O(da+db) extra state, no extra pass)
and products are corrected as  Āᵀ B̄ = AᵀB − n μa μbᵀ.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.data.hashing import HashedRows

from .linalg import full_f32, orth, sym, topk_svd
from jax.scipy.linalg import solve_triangular


# --------------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------------

#: Production default of the data-pass engine.  "kernels" = Pallas
#: (Mosaic on TPU, interpret mode elsewhere); "jnp" = the pure-jnp
#: oracle path the kernels are validated against.
DEFAULT_ENGINE = "kernels"


def resolve_engine(engine: str, use_kernels: Optional[bool] = None) -> str:
    """Normalize the engine knob; ``use_kernels`` is the legacy boolean
    spelling and wins when passed explicitly."""
    if use_kernels is not None:
        engine = "kernels" if use_kernels else "jnp"
    if engine not in ("kernels", "jnp"):
        raise ValueError(f"unknown engine {engine!r}; expected 'kernels' or 'jnp'")
    return engine


@dataclasses.dataclass(frozen=True)
class RCCAConfig:
    """Hyper-parameters of Algorithm 1.

    k:       target embedding dimension.
    p:       oversampling (paper uses 910-2000 for k=60).
    q:       number of power-iteration data passes (0 = pure sketch).
    lam_a/b: explicit ridge regularizers; if ``nu`` is set they are
             derived scale-free as λ = ν·Tr(XᵀX)/d (paper §4).
    center:  mean-shift both views via the rank-one update.
    """

    k: int
    p: int = 100
    q: int = 1
    lam_a: float = 0.0
    lam_b: float = 0.0
    nu: Optional[float] = None
    center: bool = False
    dtype: jnp.dtype = jnp.float32

    @property
    def sketch(self) -> int:  # k̃ = k + p
        return self.k + self.p


def algo_meta(cfg: RCCAConfig) -> dict:
    """The hyper-parameter identity that binds persisted pass state —
    PassRunner cursors and cluster rounds/partials both embed and
    validate exactly this dict, so they can never drift apart."""
    return {"k": cfg.k, "p": cfg.p, "q": cfg.q, "center": cfg.center,
            "nu": cfg.nu, "lam_a": cfg.lam_a, "lam_b": cfg.lam_b,
            "dtype": str(jnp.dtype(cfg.dtype))}


class RCCAResult(NamedTuple):
    Xa: jax.Array
    Xb: jax.Array
    rho: jax.Array  # top-k canonical correlations (Σ of paper line 22)
    Qa: jax.Array  # final range bases — useful to warm-start / analyze
    Qb: jax.Array
    diagnostics: dict


# --------------------------------------------------------------------------
# pass statistics (checkpointable)
# --------------------------------------------------------------------------


class PowerStats(NamedTuple):
    """Accumulators of one range-finder pass (paper lines 6-9)."""

    Ya: jax.Array  # AᵀB Qb   (da, k̃)
    Yb: jax.Array  # BᵀA Qa   (db, k̃)
    sa: jax.Array  # Aᵀ1      (da,)
    sb: jax.Array  # Bᵀ1      (db,)
    n: jax.Array  # row count ()
    tr_a: jax.Array  # ‖A‖_F²  () — for scale-free λ
    tr_b: jax.Array  # ‖B‖_F²  ()


class FinalStats(NamedTuple):
    """Accumulators of the final pass (paper lines 14-18)."""

    Ca: jax.Array  # Qaᵀ AᵀA Qa  (k̃, k̃)
    Cb: jax.Array  # Qbᵀ BᵀB Qb  (k̃, k̃)
    F: jax.Array  # Qaᵀ AᵀB Qb  (k̃, k̃)
    sa: jax.Array
    sb: jax.Array
    n: jax.Array
    tr_a: jax.Array
    tr_b: jax.Array


def init_power_stats(da: int, db: int, sketch: int, dtype) -> PowerStats:
    z = jnp.zeros
    return PowerStats(
        Ya=z((da, sketch), dtype),
        Yb=z((db, sketch), dtype),
        sa=z((da,), dtype),
        sb=z((db,), dtype),
        n=z((), dtype),
        tr_a=z((), dtype),
        tr_b=z((), dtype),
    )


def init_final_stats(sketch: int, da: int, db: int, dtype) -> FinalStats:
    z = jnp.zeros
    return FinalStats(
        Ca=z((sketch, sketch), dtype),
        Cb=z((sketch, sketch), dtype),
        F=z((sketch, sketch), dtype),
        sa=z((da,), dtype),
        sb=z((db,), dtype),
        n=z((), dtype),
        tr_a=z((), dtype),
        tr_b=z((), dtype),
    )


@full_f32
def update_power_stats(
    s: PowerStats, a: jax.Array, b: jax.Array, Qa: jax.Array, Qb: jax.Array
) -> PowerStats:
    """Fold one row chunk into the range-finder accumulators.

    The two rank-k̃ products are the data-pass hot spot; the Pallas
    kernel (repro.kernels.ccapass) implements exactly this update with
    fused VMEM tiling — this jnp form is its oracle.
    """
    f32 = jnp.float32
    pb = b @ Qb  # (c, k̃)
    pa = a @ Qa
    return PowerStats(
        Ya=s.Ya + (a.T @ pb).astype(s.Ya.dtype),
        Yb=s.Yb + (b.T @ pa).astype(s.Yb.dtype),
        sa=s.sa + jnp.sum(a, axis=0, dtype=f32).astype(s.sa.dtype),
        sb=s.sb + jnp.sum(b, axis=0, dtype=f32).astype(s.sb.dtype),
        n=s.n + a.shape[0],
        tr_a=s.tr_a + jnp.sum(a.astype(f32) ** 2),
        tr_b=s.tr_b + jnp.sum(b.astype(f32) ** 2),
    )


@full_f32
def update_power_stats_kernel(
    s: PowerStats, a: jax.Array, b: jax.Array, Qa: jax.Array, Qb: jax.Array
) -> PowerStats:
    """Pallas-kernel-backed version of :func:`update_power_stats`
    (fused MXU matmuls; interpret-mode on CPU).  The fused kernels
    bucket their output columns over a third grid axis, so this path
    holds at any feature width — Europarl's da = db = 2^19 included —
    rather than silently degrading to the unfused matmul pair."""
    from repro.kernels import ops as kops

    f32 = jnp.float32
    dYa, dYb = kops.power_pass_chunk(a, b, Qa, Qb)
    return s._replace(
        Ya=s.Ya + dYa.astype(s.Ya.dtype),
        Yb=s.Yb + dYb.astype(s.Yb.dtype),
        sa=s.sa + jnp.sum(a, axis=0, dtype=f32).astype(s.sa.dtype),
        sb=s.sb + jnp.sum(b, axis=0, dtype=f32).astype(s.sb.dtype),
        n=s.n + a.shape[0],
        tr_a=s.tr_a + jnp.sum(a.astype(f32) ** 2),
        tr_b=s.tr_b + jnp.sum(b.astype(f32) ** 2),
    )


@full_f32
def update_final_stats_kernel(
    s: FinalStats, a: jax.Array, b: jax.Array, Qa: jax.Array, Qb: jax.Array
) -> FinalStats:
    """Pallas-kernel-backed version of :func:`update_final_stats`
    (projgram fusion: each view read from HBM once per chunk)."""
    from repro.kernels import ops as kops

    f32 = jnp.float32
    dCa, dCb, dF = kops.final_pass_chunk(a, b, Qa, Qb)
    return s._replace(
        Ca=s.Ca + dCa.astype(s.Ca.dtype),
        Cb=s.Cb + dCb.astype(s.Cb.dtype),
        F=s.F + dF.astype(s.F.dtype),
        sa=s.sa + jnp.sum(a, axis=0, dtype=f32).astype(s.sa.dtype),
        sb=s.sb + jnp.sum(b, axis=0, dtype=f32).astype(s.sb.dtype),
        n=s.n + a.shape[0],
        tr_a=s.tr_a + jnp.sum(a.astype(f32) ** 2),
        tr_b=s.tr_b + jnp.sum(b.astype(f32) ** 2),
    )


@full_f32
def update_final_stats(
    s: FinalStats, a: jax.Array, b: jax.Array, Qa: jax.Array, Qb: jax.Array
) -> FinalStats:
    pa = a @ Qa  # (c, k̃)
    pb = b @ Qb
    f32 = jnp.float32
    return FinalStats(
        Ca=s.Ca + (pa.T @ pa).astype(s.Ca.dtype),
        Cb=s.Cb + (pb.T @ pb).astype(s.Cb.dtype),
        F=s.F + (pa.T @ pb).astype(s.F.dtype),
        sa=s.sa + jnp.sum(a, axis=0, dtype=f32).astype(s.sa.dtype),
        sb=s.sb + jnp.sum(b, axis=0, dtype=f32).astype(s.sb.dtype),
        n=s.n + a.shape[0],
        tr_a=s.tr_a + jnp.sum(a.astype(f32) ** 2),
        tr_b=s.tr_b + jnp.sum(b.astype(f32) ** 2),
    )


# --------------------------------------------------------------------------
# mergeable sufficient statistics (repro.cluster's map/combine contract)
#
# The canonical accumulation machinery (merge groups, pairwise tree,
# segmented accumulator) lives in repro.exec.accumulate — the one
# implementation every execution topology shares.  It is re-exported
# here because these names are part of this module's long-standing API.
# --------------------------------------------------------------------------

from repro.exec.accumulate import (  # noqa: E402, F401  (re-exports)
    MERGE_GROUP_CHUNKS,
    PairwiseStack,
    SegmentedAccumulator,
    merge_stats,
    reduce_group_partials,
)


def merge_power_stats(x: PowerStats, y: PowerStats) -> PowerStats:
    """Combine two range-finder accumulators over disjoint row sets.

    Every field is a plain sum over rows, so the merge is the exact
    map/reduce combiner of Algorithm 1: stats(S₁ ∪ S₂) = stats(S₁) ⊕
    stats(S₂) with ⊕ = elementwise +.  (Exact as algebra; the fp ADD
    still rounds — which is why the canonical reduction ORDER of
    ``repro.exec.accumulate`` exists.)
    """
    return merge_stats(x, y)


def merge_final_stats(x: FinalStats, y: FinalStats) -> FinalStats:
    """Combine two final-pass accumulators — same contract as
    :func:`merge_power_stats`."""
    return merge_stats(x, y)


# --------------------------------------------------------------------------
# centering corrections (rank-one updates, paper §3)
# --------------------------------------------------------------------------


def centered_Y(s: PowerStats, Qa, Qb, center: bool):
    if not center:
        return s.Ya, s.Yb
    n = jnp.maximum(s.n, 1.0)
    mu_a = s.sa / n
    mu_b = s.sb / n
    Ya = s.Ya - n * jnp.outer(mu_a, mu_b @ Qb)  # ĀᵀB̄Qb = AᵀBQb − n μa(μbᵀQb)
    Yb = s.Yb - n * jnp.outer(mu_b, mu_a @ Qa)
    return Ya, Yb


def centered_CF(s: FinalStats, Qa, Qb, center: bool):
    if not center:
        return s.Ca, s.Cb, s.F
    n = jnp.maximum(s.n, 1.0)
    qa = Qa.T @ (s.sa / n)  # (k̃,) = Qaᵀ μa
    qb = Qb.T @ (s.sb / n)
    Ca = s.Ca - n * jnp.outer(qa, qa)
    Cb = s.Cb - n * jnp.outer(qb, qb)
    F = s.F - n * jnp.outer(qa, qb)
    return Ca, Cb, F


def resolve_lambdas(cfg: RCCAConfig, tr_a, tr_b, da: int, db: int):
    if cfg.nu is None:
        return jnp.asarray(cfg.lam_a, jnp.float32), jnp.asarray(cfg.lam_b, jnp.float32)
    return cfg.nu * tr_a / da, cfg.nu * tr_b / db


# --------------------------------------------------------------------------
# shared per-pass transitions — every driver (streaming scan, iterator,
# cluster coordinator) runs EXACTLY these, which is what makes their
# outputs comparable bit-for-bit
# --------------------------------------------------------------------------


#: The Ω-provenance knob of the seeded-sketch path:
#: - ``"materialized"``   — classic ``jax.random.normal`` draw, array
#:   threaded everywhere (the default; pre-existing behavior).
#: - ``"seeded"``         — Ω is a pure function of a (2,)-uint32 seed
#:   (:mod:`repro.kernels.rand`); the first data pass generates its
#:   tiles inside the Pallas kernels and never materializes the
#:   ``(d, k̃)`` array, and cluster rounds ship the seed, not the array.
#: - ``"seeded-materialized"`` — the same tile-PRNG Ω, but materialized
#:   up front and run through the standard update path: the bitwise
#:   oracle ``omega="seeded"`` is validated against.
OMEGA_MODES = ("materialized", "seeded", "seeded-materialized")


def resolve_omega(omega: str) -> str:
    """Normalize/validate the Ω-provenance knob."""
    if omega not in OMEGA_MODES:
        raise ValueError(
            f"unknown omega {omega!r}; expected one of {OMEGA_MODES}")
    return omega


def omega_seeds(key: jax.Array):
    """Per-view (2,)-uint32 Ω seeds for the seeded modes — the 64-bit
    payload that replaces the (d, k̃) broadcast, identically derived
    from the PRNG key by every execution mode."""
    from repro.kernels import rand as krand

    return krand.seeds_from_key(key)


def init_Q(key: jax.Array, da: int, db: int, cfg: RCCAConfig,
           omega: str = "materialized"):
    """Line 1-2: the Gaussian sketch bases, identically derived from the
    PRNG key by every execution mode.

    Always generated in f32 with a single cast to ``cfg.dtype`` —
    drawing directly in bf16 would quantize the underlying uniforms
    and lose entropy, and it would diverge from the seeded kernels'
    generate-in-f32-then-cast semantics.  The seeded modes materialize
    the tile-PRNG Ω (the cross-engine oracle of the in-kernel path).
    """
    from repro.kernels import rand as krand

    if resolve_omega(omega) == "materialized":
        ka, kb = jax.random.split(key)
        Qa = jax.random.normal(ka, (da, cfg.sketch), jnp.float32)
        Qb = jax.random.normal(kb, (db, cfg.sketch), jnp.float32)
        return Qa.astype(cfg.dtype), Qb.astype(cfg.dtype)
    seed_a, seed_b = omega_seeds(key)
    return (krand.dense_omega(seed_a, da, cfg.sketch, cfg.dtype),
            krand.dense_omega(seed_b, db, cfg.sketch, cfg.dtype))


@functools.partial(jax.jit, static_argnames=("cfg",))
@full_f32
def power_update_Q(stats: PowerStats, Qa, Qb, cfg: RCCAConfig):
    """Lines 10-11: close one range-finder pass (center + orth), as one
    compiled program per (cfg, shapes).  ``full_f32`` sits inside the
    jit, so every dot of the traced body carries HIGHEST precision."""
    Ya, Yb = centered_Y(stats, Qa, Qb, cfg.center)
    return orth(Ya.astype(cfg.dtype)), orth(Yb.astype(cfg.dtype))


@functools.partial(jax.jit, static_argnames=("cfg", "da", "db"))
@full_f32
def finalize_result(fstats: FinalStats, Qa, Qb, cfg: RCCAConfig,
                    da: int, db: int) -> RCCAResult:
    """Lines 19-25 from merged final-pass statistics, as one compiled
    program per (cfg, da, db, shapes)."""
    Ca, Cb, F = centered_CF(fstats, Qa, Qb, cfg.center)
    lam_a, lam_b = resolve_lambdas(cfg, fstats.tr_a, fstats.tr_b, da, db)
    QtQa = sym((Qa.T @ Qa).astype(jnp.float32))
    QtQb = sym((Qb.T @ Qb).astype(jnp.float32))
    Xa, Xb, S, _, _ = finish(
        Ca, Cb, F, QtQa, QtQb, Qa.astype(jnp.float32), Qb.astype(jnp.float32),
        fstats.n, lam_a, lam_b, cfg.k,
    )
    return RCCAResult(
        Xa=Xa, Xb=Xb, rho=S, Qa=Qa, Qb=Qb,
        diagnostics={"lam_a": lam_a, "lam_b": lam_b, "n": fstats.n},
    )


# --------------------------------------------------------------------------
# finish: paper lines 19-25 (host-scale, (k̃)³)
# --------------------------------------------------------------------------


def finish(
    Ca: jax.Array,
    Cb: jax.Array,
    F: jax.Array,
    QtQa: jax.Array,
    QtQb: jax.Array,
    Qa: jax.Array,
    Qb: jax.Array,
    n: jax.Array,
    lam_a,
    lam_b,
    k: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Lines 19-25: whiten F in the Q bases, SVD, map back to X.

    NOTE on conventions: the paper's ``chol`` is Matlab's (upper R,
    RᵀR = C) so it writes F ← La⁻ᵀ F Lb⁻¹ and Xa = √n Qa La⁻¹ U.  With
    jnp's lower factor (L Lᵀ = C) the equivalent is F ← La⁻¹ F Lb⁻ᵀ and
    Xa = √n Qa La⁻ᵀ U.  (Both give Q̃ᵀ(QᵀMQ)Q̃ = I for Q̃ = Q·W.)
    """
    La = jnp.linalg.cholesky(sym(Ca + lam_a * QtQa))
    Lb = jnp.linalg.cholesky(sym(Cb + lam_b * QtQb))
    Fw = solve_triangular(La, F, lower=True)  # La⁻¹ F
    Fw = solve_triangular(Lb, Fw.T, lower=True).T  # ... Lb⁻ᵀ
    U, S, V = topk_svd(Fw, k)
    sqn = jnp.sqrt(n.astype(Fw.dtype))
    Xa = sqn * (Qa @ solve_triangular(La.T, U, lower=False))  # √n Qa La⁻ᵀ U
    Xb = sqn * (Qb @ solve_triangular(Lb.T, V, lower=False))
    return Xa, Xb, S, La, Lb


# --------------------------------------------------------------------------
# in-memory, paper-faithful
# --------------------------------------------------------------------------


@full_f32
def randomized_cca(
    A: jax.Array, B: jax.Array, cfg: RCCAConfig, key: jax.Array
) -> RCCAResult:
    """Algorithm 1, verbatim, for in-memory A, B (the reference)."""
    n, da = A.shape
    db = B.shape[1]
    kt = cfg.sketch
    ka, kb = jax.random.split(key)
    dt = cfg.dtype
    # f32 generation + single cast — same entropy semantics as init_Q
    Qa = jax.random.normal(ka, (da, kt), jnp.float32).astype(dt)
    Qb = jax.random.normal(kb, (db, kt), jnp.float32).astype(dt)

    if cfg.center:
        A = A - jnp.mean(A, axis=0, keepdims=True)
        B = B - jnp.mean(B, axis=0, keepdims=True)

    for _ in range(cfg.q):  # lines 5-12
        Ya = A.T @ (B @ Qb)
        Yb = B.T @ (A @ Qa)
        Qa = orth(Ya)
        Qb = orth(Yb)

    Pa = A @ Qa  # lines 14-18 (final pass)
    Pb = B @ Qb
    Ca = sym(Pa.T @ Pa)
    Cb = sym(Pb.T @ Pb)
    F = Pa.T @ Pb

    tr_a = jnp.sum(A.astype(jnp.float32) ** 2)
    tr_b = jnp.sum(B.astype(jnp.float32) ** 2)
    lam_a, lam_b = resolve_lambdas(cfg, tr_a, tr_b, da, db)

    QtQa = sym(Qa.T @ Qa)
    QtQb = sym(Qb.T @ Qb)
    Xa, Xb, S, La, Lb = finish(
        Ca, Cb, F, QtQa, QtQb, Qa, Qb, jnp.asarray(n, jnp.float32), lam_a, lam_b, cfg.k
    )
    diag = {"lam_a": lam_a, "lam_b": lam_b, "n": n}
    return RCCAResult(Xa=Xa, Xb=Xb, rho=S, Qa=Qa, Qb=Qb, diagnostics=diag)


# --------------------------------------------------------------------------
# streaming / out-of-core — shells over the repro.exec pass engine
# --------------------------------------------------------------------------


def randomized_cca_streaming(
    A_chunks: jax.Array,  # (nc, c, da) — out-of-core rows, chunked
    B_chunks: jax.Array,  # (nc, c, db)
    cfg: RCCAConfig,
    key: jax.Array,
    *,
    engine: str = DEFAULT_ENGINE,
    use_kernels: Optional[bool] = None,
    merge_group: int = MERGE_GROUP_CHUNKS,
    topology=None,
) -> RCCAResult:
    """Algorithm 1 where every data pass is a fold over row chunks.

    A shell over ``repro.exec.PassEngine`` — the canonical chunk →
    merge-group → pairwise-tree accumulation every execution topology
    shares.  ``engine`` selects the per-chunk update implementation:
    ``"kernels"`` (default) runs the fused Pallas data passes
    (interpret mode off-TPU), ``"jnp"`` the pure-jnp oracle.
    ``use_kernels`` is the legacy boolean spelling of the same knob.
    ``merge_group`` is the canonical merge-group size; a
    ``repro.cluster`` coordinator run with the same value is
    bit-identical to this driver for ANY worker count.  ``topology``
    optionally selects ``repro.exec.Sharded()`` to fold merge groups
    one-per-device over the local mesh (bitwise the same result); the
    default is sequential ``Local`` execution.
    """
    from repro.exec import Local, PassEngine, StackedChunks

    engine = resolve_engine(engine, use_kernels)
    eng = PassEngine(cfg, engine=engine, merge_group=merge_group,
                     topology=Local() if topology is None else topology)
    return eng.run(StackedChunks(A_chunks, B_chunks), key)


_jit_cached = functools.lru_cache(maxsize=None)(jax.jit)


def _dense(x):
    return x.to_dense() if isinstance(x, HashedRows) else x


@functools.lru_cache(maxsize=None)
def dense_chunk_rows(update):
    """``update`` with a :class:`~repro.data.hashing.HashedRows` view of
    the chunk first turned into its dense rows, inside the same traced
    program; a dense view passes through untouched, so for dense chunks
    the program is the unwrapped one.  Keeps ``update``'s name, which
    names the jitted program.  One wrapper per update function."""
    @functools.wraps(update)
    def with_dense_rows(s, a, b, Qa, Qb):
        return update(s, _dense(a), _dense(b), Qa, Qb)
    return with_dense_rows


def jit_update_fn(kind: str, engine: str):
    """The jitted per-chunk update for one pass flavor — the exact
    function cluster workers and the streaming entries share.  One jit
    wrapper per update function, so every fit reuses its dispatch
    cache."""
    return _jit_cached(update_fn(kind, engine))


def update_fn(kind: str, engine: str):
    """The raw (unjitted) per-chunk update for one pass flavor — what
    the device-parallel group fold scans inside shard_map (jitting is
    the caller's concern there).  It takes each chunk view dense or as
    ``HashedRows`` (:func:`dense_chunk_rows`)."""
    kernels = resolve_engine(engine) == "kernels"
    if kind == "power":
        return dense_chunk_rows(update_power_stats_kernel if kernels
                                else update_power_stats)
    if kind == "final":
        return dense_chunk_rows(update_final_stats_kernel if kernels
                                else update_final_stats)
    raise ValueError(f"unknown pass kind {kind!r}")


@functools.lru_cache(maxsize=None)
def seeded_update_fn(kind: str, kt: int, q_dtype):
    """The raw per-chunk update for a seeded-Ω pass (kernels engine):
    Ω tiles are generated inside the fused Pallas kernels, so the Qa/Qb
    operand slots carry the (2,)-uint32 seeds instead of (d, k̃) arrays
    — same arity as :func:`update_fn`'s result, which is what lets the
    fold loop, shard_map specs, cursors and cluster rounds stay
    structurally unchanged.  Bitwise identical to the materialized
    update fed ``rand.dense_omega(seed, d, kt, q_dtype)``.  Takes each
    chunk view dense or as ``HashedRows``, as :func:`update_fn`'s."""
    from repro.kernels import ops as kops

    f32 = jnp.float32
    if kind == "power":
        @full_f32
        def upd(s: PowerStats, a, b, seed_a, seed_b) -> PowerStats:
            dYa, dYb = kops.power_pass_chunk_seeded(a, b, seed_a, seed_b,
                                                    kt=kt, q_dtype=q_dtype)
            return s._replace(
                Ya=s.Ya + dYa.astype(s.Ya.dtype),
                Yb=s.Yb + dYb.astype(s.Yb.dtype),
                sa=s.sa + jnp.sum(a, axis=0, dtype=f32).astype(s.sa.dtype),
                sb=s.sb + jnp.sum(b, axis=0, dtype=f32).astype(s.sb.dtype),
                n=s.n + a.shape[0],
                tr_a=s.tr_a + jnp.sum(a.astype(f32) ** 2),
                tr_b=s.tr_b + jnp.sum(b.astype(f32) ** 2),
            )
        return dense_chunk_rows(upd)
    if kind == "final":
        @full_f32
        def upd(s: FinalStats, a, b, seed_a, seed_b) -> FinalStats:
            dCa, dCb, dF = kops.final_pass_chunk_seeded(a, b, seed_a, seed_b,
                                                        kt=kt, q_dtype=q_dtype)
            return s._replace(
                Ca=s.Ca + dCa.astype(s.Ca.dtype),
                Cb=s.Cb + dCb.astype(s.Cb.dtype),
                F=s.F + dF.astype(s.F.dtype),
                sa=s.sa + jnp.sum(a, axis=0, dtype=f32).astype(s.sa.dtype),
                sb=s.sb + jnp.sum(b, axis=0, dtype=f32).astype(s.sb.dtype),
                n=s.n + a.shape[0],
                tr_a=s.tr_a + jnp.sum(a.astype(f32) ** 2),
                tr_b=s.tr_b + jnp.sum(b.astype(f32) ** 2),
            )
        return dense_chunk_rows(upd)
    raise ValueError(f"unknown pass kind {kind!r}")


@functools.lru_cache(maxsize=None)
def jit_seeded_update_fn(kind: str, kt: int, q_dtype):
    """Jitted :func:`seeded_update_fn` — what streaming drivers and
    cluster workers run for a seeded pass (one wrapper per flavor)."""
    return jax.jit(seeded_update_fn(kind, kt, q_dtype))


@functools.lru_cache(maxsize=None)
def stats_init_fn(kind: str, da: int, db: int, sketch: int):
    """Zero accumulators for one pass flavor (f32 — the accumulator
    precision every execution mode shares): one compiled program per
    (kind, da, db, k̃) that returns the whole stats pytree."""
    if kind == "power":
        def zero_power_stats():
            return init_power_stats(da, db, sketch, jnp.float32)
        return jax.jit(zero_power_stats)
    if kind == "final":
        def zero_final_stats():
            return init_final_stats(sketch, da, db, jnp.float32)
        return jax.jit(zero_final_stats)
    raise ValueError(f"unknown pass kind {kind!r}")


def randomized_cca_iterator(
    source_factory,
    da: int,
    db: int,
    cfg: RCCAConfig,
    key: jax.Array,
    *,
    resume_state: Optional[dict] = None,
    on_pass_end=None,
    engine: str = DEFAULT_ENGINE,
    use_kernels: Optional[bool] = None,
    merge_group: int = MERGE_GROUP_CHUNKS,
    omega: str = "materialized",
    n_chunks: Optional[int] = None,
) -> RCCAResult:
    """True out-of-core driver: ``source_factory()`` yields (a, b) row
    chunks (e.g. from disk / a distributed FS).  Per-chunk updates are
    jitted; pass state is a :class:`SegmentedAccumulator` whose
    ``state()`` pytree the caller can checkpoint between chunks (fault
    tolerance: resume a killed pass mid-stream via ``resume_state`` =
    {"pass_idx", "chunk_idx", "acc", "Qa", "Qb"} with ``acc`` a state
    pytree captured from the ``on_pass_end(pass_idx, chunk_idx, acc,
    Qa, Qb)`` callback's accumulator).  A factory taking a positional
    ``start`` argument is seekable: each pass opens it at its first
    needed chunk, so a resume never re-reads the already-folded prefix
    (``repro.store`` readers/prefetchers use this).  ``engine`` selects
    the per-chunk update implementation and ``merge_group`` the
    canonical merge-group size (see :func:`randomized_cca_streaming`);
    ``n_chunks``, when known, lets a cursor saved at the very last
    chunk of a pass restore correctly (``repro.store.PassRunner``
    passes it).  A shell over ``repro.exec.PassEngine.run_stream`` —
    the engine owns the fold loop, source seeking and resume-state
    restoration.
    """
    from repro.exec import PassEngine

    eng = PassEngine(cfg, engine=resolve_engine(engine, use_kernels),
                     merge_group=merge_group, omega=omega)
    return eng.run_stream(source_factory, da, db, key, n_chunks=n_chunks,
                          resume_state=resume_state, on_pass_end=on_pass_end)
