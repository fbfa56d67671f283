"""Multi-pod distributed RandomizedCCA (shard_map over (pod, data, model)).

Sharding contract (see DESIGN.md §2):

- rows (n)   → mesh axes ``row_axes``  (default ("pod", "data"))
- features   → mesh axis  ``col_axis`` (default "model"); Qa/Qb/Ya/Yb are
  row-sharded over the same axis, so no da/db-sized tensor is ever
  replicated — the paper's binding constraint ("utility of storing Q, Y
  in main memory") becomes a per-device HBM constraint of d·k̃/|model|.

Per microbatch the only collectives are two psums of (mb × k̃) projected
activations over ``col_axis`` (~MBs); with ``engine="kernels"`` they
fold into the staged kernel pipeline at the phase boundary — the
``proj_stage`` kernel emits the local shard's partial P, the psum sums
it globally, and the sweep kernels consume the result (optionally
int8+error-feedback compressed via ``collective="fused-int8ef"``).  The
d-sized accumulators are psummed ONCE per pass over ``row_axes``.
Accumulation is bucketed so the large end-of-pass psum is split into
column buckets that overlap with the next microbatch's compute (XLA
async collectives) — the distributed-optimization trick from DESIGN.md
§5.

``orth`` is ``linalg.orth`` (eigh-whitened, then CholeskyQR2) with k̃×k̃
psum'd Grams (TPU-native; DESIGN §3).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.kernels.compat import shard_map

from .linalg import full_f32, orth, sym
from .rcca import DEFAULT_ENGINE, RCCAConfig, RCCAResult, finish, resolve_engine
from repro.exec.engine import pass_schedule


# --------------------------------------------------------------------------
# collective helpers
# --------------------------------------------------------------------------


def _psum(x, axes):
    if isinstance(axes, str):
        axes = (axes,)
    return jax.lax.psum(x, tuple(axes))


def _trace_psum(tra, trb, row_axes, col_axis):
    """‖A‖²_F and ‖B‖²_F from local shards: every row shard and every
    feature shard holds a part of the sum (λ = ν·tr/d needs the whole;
    a per-feature-shard λ whitens each shard of X differently)."""
    axes = tuple(row_axes) + ((col_axis,) if col_axis is not None else ())
    return _psum(tra, axes), _psum(trb, axes)


def dist_orth(Y: jax.Array, col_axis: Optional[str]):
    """Orthonormalize a row-sharded tall matrix: :func:`linalg.orth`
    with its Grams psum'd and its scale pmax'd over ``col_axis``.  All
    collectives are k̃×k̃ or scalars."""
    return orth(Y, col_axis)


# --------------------------------------------------------------------------
# data passes (run inside shard_map; a/b are LOCAL row×feature shards)
# --------------------------------------------------------------------------


def _microbatches(a: jax.Array, mb: Optional[int]):
    n_loc = a.shape[0]
    if mb is None or mb >= n_loc:
        return 1, n_loc
    assert n_loc % mb == 0, f"local rows {n_loc} not divisible by microbatch {mb}"
    return n_loc // mb, mb


def power_pass_local(a, b, Qa, Qb, *, row_axes, col_axis, microbatch=None,
                     compute_dtype=jnp.bfloat16, int8_reduce=False,
                     reduce_buckets=1, reduce_dtype=None, engine="jnp",
                     collective="fused"):
    """One range-finder pass over the local shard → global (Ya, Yb, stats).

    Returns Ya/Yb sharded like Qa/Qb (features over col_axis, replicated
    over rows) plus centering/λ statistics.

    ``engine="kernels"`` runs the per-microbatch matmuls as Pallas
    kernels on the local shards: fully fused project+accumulate when
    features are unsharded (col_axis None — P stays in VMEM), and the
    collective-fused staged pair when col_axis genuinely shards the
    features: the ``proj_stage`` kernel emits the *partial* P of the
    local feature shard, the (mb × k̃) psum happens at the phase
    boundary, and the ``powerpass_sweep`` kernel accumulates the
    globally-summed P — no unfused matmul pair around a full-width
    psum.  Both fused forms bucket the accumulator output columns, so
    they hold for ANY local feature width da_l·k̃ (the driver collapses
    a size-1 col_axis to None so trivial model axes take the
    single-kernel path).

    ``collective`` picks the sharded phase-boundary reduction:
    ``"fused"`` (exact f32 psum), ``"fused-int8ef"`` (blockwise-int8
    psum with error-feedback residuals carried across microbatches —
    ~4× fewer wire bytes on the cross-pod hop; see
    :func:`repro.distributed.psum_int8_ef`), or ``"unfused"`` (legacy
    project → psum → accumulate_tn matmul pair, kept as the parity
    oracle for the fused path).

    §Perf knobs: ``int8_reduce`` — compress the end-of-pass Y psum with
    blockwise int8 (4× fewer bytes on the row axes; randomized range
    finding tolerates the quantization noise — it's another random
    perturbation of the sketch, see EXPERIMENTS.md §Perf);
    ``reduce_buckets`` — split the Y psum into column buckets issued
    independently so XLA's async collectives overlap them with compute.
    """
    if collective not in ("fused", "fused-int8ef", "unfused"):
        raise ValueError(f"unknown collective mode {collective!r}")
    nb, mb = _microbatches(a, microbatch)
    da_l, kt = Qa.shape
    db_l = Qb.shape[0]
    f32 = jnp.float32
    cd = compute_dtype
    kernels = engine == "kernels"
    if kernels:
        from repro.kernels import ops as kops
    fused_col = kernels and col_axis is not None and collective != "unfused"
    use_ef = fused_col and collective == "fused-int8ef"
    if use_ef:
        from repro.distributed import psum_int8_ef

    a_r = a.reshape(nb, mb, da_l)
    b_r = b.reshape(nb, mb, db_l)
    Qa_c, Qb_c = Qa.astype(cd), Qb.astype(cd)

    def body(carry, ab):
        Ya, Yb, sa, sb, tra, trb, n, ea, eb = carry
        am, bm = ab
        am_c, bm_c = am.astype(cd), bm.astype(cd)
        if kernels and col_axis is None:
            # features unsharded → the fused chunk update applies as-is
            dYa, dYb = kops.power_pass_chunk(am_c, bm_c, Qa_c, Qb_c)
            Ya, Yb = Ya + dYa, Yb + dYb
        elif fused_col:
            # collective-fused staged pair: partial-P stage on the local
            # feature shard, psum at the phase boundary, sweep of the
            # global P — the psum is folded between the two kernel
            # phases instead of bracketing an unfused matmul pair.
            pb = kops.stage_project(bm_c, Qb_c).astype(cd)
            pa = kops.stage_project(am_c, Qa_c).astype(cd)
            if use_ef:
                pb, eb = psum_int8_ef(pb, col_axis, eb)
                pa, ea = psum_int8_ef(pa, col_axis, ea)
            else:
                pb = _psum(pb, col_axis)
                pa = _psum(pa, col_axis)
            Ya = Ya + kops.sweep_accumulate(am_c, pb)
            Yb = Yb + kops.sweep_accumulate(bm_c, pa)
        else:
            # projected activations: the ONLY per-microbatch collectives
            if kernels:
                pb = kops.project(bm_c, Qb_c).astype(cd)
                pa = kops.project(am_c, Qa_c).astype(cd)
            else:
                pb = bm_c @ Qb_c
                pa = am_c @ Qa_c
            if col_axis is not None:
                pb = _psum(pb, col_axis)
                pa = _psum(pa, col_axis)
            if kernels:
                Ya = Ya + kops.accumulate_tn(am_c, pb)
                Yb = Yb + kops.accumulate_tn(bm_c, pa)
            else:
                Ya = Ya + jnp.einsum("md,mk->dk", am_c, pb, preferred_element_type=f32)
                Yb = Yb + jnp.einsum("md,mk->dk", bm_c, pa, preferred_element_type=f32)
        sa = sa + jnp.sum(am, axis=0, dtype=f32)
        sb = sb + jnp.sum(bm, axis=0, dtype=f32)
        tra = tra + jnp.sum(am.astype(f32) ** 2)
        trb = trb + jnp.sum(bm.astype(f32) ** 2)
        return (Ya, Yb, sa, sb, tra, trb, n + mb, ea, eb), None

    z = jnp.zeros
    # error-feedback residuals ride the scan carry (zero-size when the
    # int8 collective is off, so the carry structure stays uniform)
    e_shape = (mb, kt) if use_ef else (0,)
    init = (
        z((da_l, kt), f32), z((db_l, kt), f32),
        z((da_l,), f32), z((db_l,), f32), z((), f32), z((), f32), z((), f32),
        z(e_shape, f32), z(e_shape, f32),
    )
    (Ya, Yb, sa, sb, tra, trb, n, _, _), _ = jax.lax.scan(body, init, (a_r, b_r))

    # one d-sized psum per pass, over the row axes only
    def reduce_Y(Y):
        if reduce_dtype is not None:
            # compressed-payload reduction: the sketch tolerates the
            # low-precision sum (it's one more random perturbation).
            # The optimization barrier stops XLA's convert-reassociation
            # pass from hoisting the cast past the all-reduce (which
            # would silently restore the f32 wire format).
            Y = jax.lax.optimization_barrier(Y.astype(reduce_dtype))
        if int8_reduce:
            # NOTE §Perf: refuted optimization kept for the record — XLA
            # must carry the int8 sum in int32 on the wire, so bytes do
            # NOT drop; see EXPERIMENTS.md §Perf iteration log.
            from repro.distributed import psum_int8_ef

            axes = (row_axes,) if isinstance(row_axes, str) else row_axes
            out = Y
            for ax in axes:
                out, _ = psum_int8_ef(out, ax)
            return out.astype(jnp.float32)
        if reduce_buckets > 1:
            from repro.distributed import bucketed_accumulate

            return bucketed_accumulate(Y, row_axes, reduce_buckets).astype(jnp.float32)
        return _psum(Y, row_axes).astype(jnp.float32)

    Ya, Yb = reduce_Y(Ya), reduce_Y(Yb)
    sa, sb = (_psum(t, row_axes) for t in (sa, sb))
    tra, trb = _trace_psum(tra, trb, row_axes, col_axis)
    return Ya, Yb, sa, sb, tra, trb, _psum(n, row_axes)


def final_pass_local(a, b, Qa, Qb, *, row_axes, col_axis, microbatch=None,
                     compute_dtype=jnp.bfloat16, engine="jnp",
                     collective="fused"):
    """Final pass: projected covariances Ca, Cb, F (paper lines 14-18).

    ``engine="kernels"``: with unsharded features the fused
    project+gram kernel reads each local shard from HBM once per
    C-column bucket per microbatch (C-column bucketing keeps this
    fused for sketches past k̃p = 1024; single bucket ⇒ one read);
    with a genuinely sharded col_axis the collective-fused staged pair
    runs — ``proj_stage`` emits the local shard's partial P, the psum
    folds at the phase boundary, and ``gram_sweep`` /
    ``powerpass_sweep`` build Ca/Cb/F from the global P.  ``collective``
    as in :func:`power_pass_local` (``"fused-int8ef"`` compresses the
    phase-boundary psum with error feedback; ``"unfused"`` is the
    legacy matmul-pair parity oracle)."""
    if collective not in ("fused", "fused-int8ef", "unfused"):
        raise ValueError(f"unknown collective mode {collective!r}")
    nb, mb = _microbatches(a, microbatch)
    da_l, kt = Qa.shape
    db_l = Qb.shape[0]
    f32 = jnp.float32
    cd = compute_dtype
    kernels = engine == "kernels"
    if kernels:
        from repro.kernels import ops as kops
    fused_col = kernels and col_axis is not None and collective != "unfused"
    use_ef = fused_col and collective == "fused-int8ef"
    if use_ef:
        from repro.distributed import psum_int8_ef
    a_r = a.reshape(nb, mb, da_l)
    b_r = b.reshape(nb, mb, db_l)
    Qa_c, Qb_c = Qa.astype(cd), Qb.astype(cd)

    def body(carry, ab):
        Ca, Cb, F, sa, sb, tra, trb, n, ea, eb = carry
        am, bm = ab
        am_c, bm_c = am.astype(cd), bm.astype(cd)
        if kernels and col_axis is None:
            dCa, dCb, dF = kops.final_pass_chunk(am_c, bm_c, Qa_c, Qb_c)
            Ca, Cb, F = Ca + dCa, Cb + dCb, F + dF
        elif fused_col:
            pa = kops.stage_project(am_c, Qa_c).astype(cd)
            pb = kops.stage_project(bm_c, Qb_c).astype(cd)
            if use_ef:
                pa, ea = psum_int8_ef(pa, col_axis, ea)
                pb, eb = psum_int8_ef(pb, col_axis, eb)
            else:
                pa = _psum(pa, col_axis)
                pb = _psum(pb, col_axis)
            Ca = Ca + kops.gram_accumulate(pa)
            Cb = Cb + kops.gram_accumulate(pb)
            # F = PaᵀPb is the sweep contraction with Pa as the operand
            F = F + kops.sweep_accumulate(pa, pb)
        else:
            if kernels:
                pa = kops.project(am_c, Qa_c).astype(cd)
                pb = kops.project(bm_c, Qb_c).astype(cd)
            else:
                pa = am_c @ Qa_c
                pb = bm_c @ Qb_c
            if col_axis is not None:
                pa = _psum(pa, col_axis)
                pb = _psum(pb, col_axis)
            if kernels:
                Ca = Ca + kops.accumulate_tn(pa, pa)
                Cb = Cb + kops.accumulate_tn(pb, pb)
                F = F + kops.accumulate_tn(pa, pb)
            else:
                Ca = Ca + jnp.einsum("mi,mj->ij", pa, pa, preferred_element_type=f32)
                Cb = Cb + jnp.einsum("mi,mj->ij", pb, pb, preferred_element_type=f32)
                F = F + jnp.einsum("mi,mj->ij", pa, pb, preferred_element_type=f32)
        sa = sa + jnp.sum(am, axis=0, dtype=f32)
        sb = sb + jnp.sum(bm, axis=0, dtype=f32)
        tra = tra + jnp.sum(am.astype(f32) ** 2)
        trb = trb + jnp.sum(bm.astype(f32) ** 2)
        return (Ca, Cb, F, sa, sb, tra, trb, n + mb, ea, eb), None

    z = jnp.zeros
    e_shape = (mb, kt) if use_ef else (0,)
    init = (
        z((kt, kt), f32), z((kt, kt), f32), z((kt, kt), f32),
        z((da_l,), f32), z((db_l,), f32), z((), f32), z((), f32), z((), f32),
        z(e_shape, f32), z(e_shape, f32),
    )
    (Ca, Cb, F, sa, sb, tra, trb, n, _, _), _ = jax.lax.scan(body, init, (a_r, b_r))
    # Ca/Cb/F are identical within a model group (pa/pb already psummed
    # over col_axis) — reduce over rows only.
    Ca, Cb, F = (_psum(t, row_axes) for t in (Ca, Cb, F))
    sa, sb = (_psum(t, row_axes) for t in (sa, sb))
    tra, trb = _trace_psum(tra, trb, row_axes, col_axis)
    return Ca, Cb, F, sa, sb, tra, trb, _psum(n, row_axes)


# --------------------------------------------------------------------------
# full distributed solve
# --------------------------------------------------------------------------


@full_f32
def dist_randomized_cca(
    A: jax.Array,
    B: jax.Array,
    cfg: RCCAConfig,
    key: jax.Array,
    mesh: Optional[Mesh] = None,
    *,
    row_axes: Sequence[str] = ("pod", "data"),
    col_axis: Optional[str] = "model",
    microbatch: Optional[int] = None,
    compute_dtype=jnp.float32,
    engine: str = DEFAULT_ENGINE,
    use_kernels: Optional[bool] = None,
    topology=None,
    collective: str = "fused",
) -> RCCAResult:
    """Run Algorithm 1 on row+feature-sharded A (n×da), B (n×db).

    This is the RESIDENT-mode form of the ``repro.exec.Sharded``
    topology: with a non-None ``col_axis`` no da/db-sized tensor is
    ever replicated, at the cost of the bitwise-streaming contract (the
    per-microbatch feature psums reassociate the row sums).  Passing a
    ``repro.exec.Sharded`` value as ``topology`` supplies ``mesh`` and
    ``col_axis`` in one argument.  A/B must be shardable as
    P(row_axes, col_axis).  All q+1 data passes execute as shard_map
    programs on the schedule shared with the streaming engine; the
    finish (lines 19-25) is computed redundantly on every device
    (replicated, no host round-trip).  ``engine`` selects the
    per-microbatch update implementation inside the shard_map bodies
    (see rcca.randomized_cca_streaming); with ``engine="kernels"`` and
    a genuinely sharded ``col_axis``, ``collective`` picks the sharded
    kernel path — ``"fused"`` (default: staged kernels with the
    partial-P psum folded at the phase boundary), ``"fused-int8ef"``
    (same, int8+error-feedback compressed psum for the cross-pod hop),
    or ``"unfused"`` (legacy matmul pair around a full-width psum).
    """
    engine = resolve_engine(engine, use_kernels)
    if topology is not None:
        if topology.mesh is None and mesh is None:
            raise ValueError(
                "resident-mode Sharded topology needs an explicit mesh "
                "(its axis names define the row/feature sharding)")
        mesh = topology.mesh if mesh is None else mesh
        col_axis = topology.col_axis
    if mesh is None:
        raise ValueError("dist_randomized_cca needs a mesh (or a topology)")
    row_axes = tuple(ax for ax in row_axes if ax in mesh.axis_names)
    if col_axis is not None and col_axis not in mesh.axis_names:
        col_axis = None
    if col_axis is not None and mesh.shape[col_axis] == 1:
        # a trivial model axis shards nothing: drop it so the local
        # passes take the fused bucketed kernels (no mid-update psum)
        # instead of the unfused pair around a no-op collective.
        col_axis = None
    n, da = A.shape
    db = B.shape[1]
    kt = cfg.sketch

    data_spec = P(row_axes, col_axis)
    q_spec = P(col_axis, None)
    rep = P()

    ka, kb = jax.random.split(key)
    # Q init: generated under jit with sharded output (distributed randn)
    Qa = jax.jit(
        lambda k: jax.random.normal(k, (da, kt), cfg.dtype),
        out_shardings=NamedSharding(mesh, q_spec),
    )(ka)
    Qb = jax.jit(
        lambda k: jax.random.normal(k, (db, kt), cfg.dtype),
        out_shardings=NamedSharding(mesh, q_spec),
    )(kb)

    A = jax.device_put(A, NamedSharding(mesh, data_spec))
    B = jax.device_put(B, NamedSharding(mesh, data_spec))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(data_spec, data_spec, q_spec, q_spec),
        out_specs=(q_spec, q_spec, rep, rep, rep),
        check_rep=False,
    )
    def power_step(a, b, Qa, Qb):
        Ya, Yb, sa, sb, tra, trb, nn = power_pass_local(
            a, b, Qa, Qb, row_axes=row_axes, col_axis=col_axis,
            microbatch=microbatch, compute_dtype=compute_dtype, engine=engine,
            collective=collective,
        )
        if cfg.center:
            mu_bQ = (sb / nn) @ Qb.astype(jnp.float32)
            mu_aQ = (sa / nn) @ Qa.astype(jnp.float32)
            if col_axis is not None:
                mu_bQ = _psum(mu_bQ, col_axis)
                mu_aQ = _psum(mu_aQ, col_axis)
            Ya = Ya - nn * jnp.outer(sa / nn, mu_bQ)
            Yb = Yb - nn * jnp.outer(sb / nn, mu_aQ)
        Qa_new = dist_orth(Ya.astype(cfg.dtype), col_axis)
        Qb_new = dist_orth(Yb.astype(cfg.dtype), col_axis)
        return Qa_new, Qb_new, tra, trb, nn

    for _pass_idx, kind in pass_schedule(cfg.q):
        if kind != "power":
            break  # the final pass runs below, after final_step is built
        Qa, Qb, _, _, _ = jax.jit(power_step)(A, B, Qa, Qb)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(data_spec, data_spec, q_spec, q_spec),
        out_specs=(q_spec, q_spec, rep, rep, rep),
        check_rep=False,
    )
    def final_step(a, b, Qa, Qb):
        Ca, Cb, F, sa, sb, tra, trb, nn = final_pass_local(
            a, b, Qa, Qb, row_axes=row_axes, col_axis=col_axis,
            microbatch=microbatch, compute_dtype=compute_dtype, engine=engine,
            collective=collective,
        )
        Qa32 = Qa.astype(jnp.float32)
        Qb32 = Qb.astype(jnp.float32)
        if cfg.center:
            qa = Qa32.T @ (sa / nn)
            qb = Qb32.T @ (sb / nn)
            if col_axis is not None:
                qa = _psum(qa, col_axis)
                qb = _psum(qb, col_axis)
            Ca = Ca - nn * jnp.outer(qa, qa)
            Cb = Cb - nn * jnp.outer(qb, qb)
            F = F - nn * jnp.outer(qa, qb)
        QtQa = sym(Qa32.T @ Qa32)
        QtQb = sym(Qb32.T @ Qb32)
        if col_axis is not None:
            QtQa = _psum(QtQa, col_axis)
            QtQb = _psum(QtQb, col_axis)
        if cfg.nu is not None:
            lam_a = cfg.nu * tra / da
            lam_b = cfg.nu * trb / db
        else:
            lam_a = jnp.asarray(cfg.lam_a, jnp.float32)
            lam_b = jnp.asarray(cfg.lam_b, jnp.float32)
        # finish (paper lines 19-25) — replicated small math, local Q matmul
        Xa, Xb, S, _, _ = finish(
            Ca, Cb, F, QtQa, QtQb, Qa32, Qb32, nn, lam_a, lam_b, cfg.k
        )
        return Xa, Xb, S, lam_a, lam_b

    Xa, Xb, S, lam_a, lam_b = jax.jit(final_step)(A, B, Qa, Qb)
    return RCCAResult(
        Xa=Xa, Xb=Xb, rho=S, Qa=Qa, Qb=Qb,
        diagnostics={"lam_a": lam_a, "lam_b": lam_b, "n": n},
    )
