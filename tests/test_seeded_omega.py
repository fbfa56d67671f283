"""Seeded-Ω path: the counter-based tile PRNG and everything built on it.

The bitwise contract under test (see repro/kernels/rand.py): Ω(seed) is
a pure function of ``(seed, row, col)``, so

  * any lane-aligned tile of it equals the matching slice of the
    materialized :func:`dense_omega` bit-for-bit (block-shape
    invariance — what lets the fused kernels generate Ω in-VMEM),
  * the ``*_seeded`` kernels are bitwise identical to their
    materialized twins fed ``dense_omega`` at the same block config,
  * a full fit with ``omega="seeded"`` is bitwise identical to the
    ``omega="seeded-materialized"`` oracle per engine, and a seeded
    fit kill/resumed through a pass cursor (whose pass-0 Qa/Qb slots
    hold the (2,)-uint32 seeds) reproduces it exactly,
  * the seeded pass-0 update never materializes the ``(d, k̃)`` Ω —
    pinned structurally on the jaxpr.

Plus the pass-path correctness fixes that rode along: prefetcher error
propagation (a failed read is never silently dropped), stale-partial
cleanup failures surfacing instead of passing silently, init_Q's
generate-in-f32-then-cast entropy rule, and the RCCA108/RCCA006
static-analysis rules that police the seeded plumbing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import kernel_check, lint
from repro.core.rcca import (
    OMEGA_MODES,
    RCCAConfig,
    init_Q,
    omega_seeds,
    randomized_cca_iterator,
    resolve_omega,
    seeded_update_fn,
    stats_init_fn,
    update_fn,
)
from repro.cluster import partials as pt
from repro.data import PlantedCCAData
from repro.kernels import compat, ops, rand
from repro.kernels.plan import BlockDef, KernelPlan, ScalarDef
from repro.store import PassRunner, ingest_planted
from repro.store.prefetch import ChunkPrefetcher

U32 = jnp.uint32
SEED = jnp.array([0xDEADBEEF, 0x12345678], dtype=U32)


def codes(violations):
    return sorted(v.code for v in violations)


# --------------------------------------------------------------------------
# generator invariance: tiles == dense slices, bit-for-bit
# --------------------------------------------------------------------------


class TestGenerator:
    D, KT = 300, 200  # ragged on purpose: padded to (384, 256)

    def test_row_tiles_match_dense_slices(self):
        dense = np.asarray(rand.dense_omega(SEED, self.D, self.KT))
        for r0 in (0, 128, 256):
            tile = np.asarray(rand.normal_tile(
                SEED[0], SEED[1], U32(r0), U32(0), (128, 256),
                row_limit=self.D, col_limit=self.KT))
            rows = min(128, self.D - r0)
            assert np.array_equal(tile[:rows, :self.KT], dense[r0:r0 + rows])
            # masked padding is exactly 0.0 (matches zero-padded operands)
            assert not tile[rows:, :].any()
            assert not tile[:, self.KT:].any()

    def test_column_tile_matches_dense_slice(self):
        dense = np.asarray(rand.dense_omega(SEED, self.D, self.KT))
        tile = np.asarray(rand.normal_tile(
            SEED[0], SEED[1], U32(128), U32(128), (128, 128),
            row_limit=self.D, col_limit=self.KT))
        assert np.array_equal(tile[:, :self.KT - 128],
                              dense[128:256, 128:self.KT])

    def test_dense_omega_jit_matches_eager(self):
        eager = rand.dense_omega(SEED, self.D, self.KT)
        jitted = jax.jit(lambda s: rand.dense_omega(s, self.D, self.KT))(SEED)
        assert np.array_equal(np.asarray(eager), np.asarray(jitted))

    def test_dense_omega_bf16_is_f32_generation_cast_once(self):
        f32 = rand.dense_omega(SEED, self.D, self.KT, jnp.float32)
        bf16 = rand.dense_omega(SEED, self.D, self.KT, jnp.bfloat16)
        assert bf16.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(f32.astype(jnp.bfloat16)),
                              np.asarray(bf16))

    def test_distinct_seeds_distinct_omegas(self):
        other = jnp.array([1, 2], dtype=U32)
        a = np.asarray(rand.dense_omega(SEED, self.D, self.KT))
        b = np.asarray(rand.dense_omega(other, self.D, self.KT))
        assert not np.array_equal(a, b)


def test_resolve_omega_validates():
    for m in OMEGA_MODES:
        assert resolve_omega(m) == m
    with pytest.raises(ValueError, match="unknown omega"):
        resolve_omega("lazy")


def test_init_q_seeded_is_dense_omega_of_omega_seeds():
    """init_Q's seeded modes and the seed plumbing derive the SAME Ω:
    the materialized oracle and the in-kernel path share one source."""
    key = jax.random.PRNGKey(42)
    cfg = RCCAConfig(k=2, p=2)
    da, db = 24, 16
    seed_a, seed_b = omega_seeds(key)
    Qa, Qb = init_Q(key, da, db, cfg, omega="seeded")
    assert np.array_equal(
        np.asarray(Qa), np.asarray(rand.dense_omega(seed_a, da, cfg.sketch)))
    assert np.array_equal(
        np.asarray(Qb), np.asarray(rand.dense_omega(seed_b, db, cfg.sketch)))


def test_init_q_generates_in_f32_then_casts():
    """Entropy rule: a bf16 sketch is the f32 draw cast once — drawing
    natively in bf16 would quantize the uniforms (and diverge from the
    seeded kernels' generate-in-f32-then-cast semantics)."""
    key = jax.random.PRNGKey(7)
    da, db = 24, 16
    for omega in OMEGA_MODES:
        cfg32 = RCCAConfig(k=2, p=2, dtype=jnp.float32)
        cfg16 = RCCAConfig(k=2, p=2, dtype=jnp.bfloat16)
        Qa32, Qb32 = init_Q(key, da, db, cfg32, omega=omega)
        Qa16, Qb16 = init_Q(key, da, db, cfg16, omega=omega)
        assert Qa16.dtype == jnp.bfloat16 and Qb16.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(Qa32.astype(jnp.bfloat16)),
                              np.asarray(Qa16)), omega
        assert np.array_equal(np.asarray(Qb32.astype(jnp.bfloat16)),
                              np.asarray(Qb16)), omega


# --------------------------------------------------------------------------
# seeded kernels == materialized kernels fed dense_omega (same blocks)
# --------------------------------------------------------------------------


def _chunk(rng, c, d, dtype):
    return jnp.asarray(rng.standard_normal((c, d)), dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_power_pass_chunk_seeded_matches_materialized(dtype):
    q_dtype = jnp.dtype(dtype)
    rng = np.random.default_rng(0)
    c, da, db, kt = 16, 40, 24, 12
    a, b = _chunk(rng, c, da, q_dtype), _chunk(rng, c, db, q_dtype)
    seed_a, seed_b = omega_seeds(jax.random.PRNGKey(1))
    Qa = rand.dense_omega(seed_a, da, kt, q_dtype)
    Qb = rand.dense_omega(seed_b, db, kt, q_dtype)
    dYa_s, dYb_s = ops.power_pass_chunk_seeded(a, b, seed_a, seed_b,
                                               kt=kt, q_dtype=q_dtype)
    dYa_m, dYb_m = ops.power_pass_chunk(a, b, Qa, Qb)
    assert np.array_equal(np.asarray(dYa_s), np.asarray(dYa_m))
    assert np.array_equal(np.asarray(dYb_s), np.asarray(dYb_m))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_final_pass_chunk_seeded_matches_materialized(dtype):
    q_dtype = jnp.dtype(dtype)
    rng = np.random.default_rng(2)
    c, da, db, kt = 16, 40, 24, 12
    a, b = _chunk(rng, c, da, q_dtype), _chunk(rng, c, db, q_dtype)
    seed_a, seed_b = omega_seeds(jax.random.PRNGKey(3))
    Qa = rand.dense_omega(seed_a, da, kt, q_dtype)
    Qb = rand.dense_omega(seed_b, db, kt, q_dtype)
    got = ops.final_pass_chunk_seeded(a, b, seed_a, seed_b,
                                      kt=kt, q_dtype=q_dtype)
    want = ops.final_pass_chunk(a, b, Qa, Qb)
    for g, w, name in zip(got, want, ("Ca", "Cb", "F")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name


# --------------------------------------------------------------------------
# fit-level: omega="seeded" == the seeded-materialized oracle, bitwise
# --------------------------------------------------------------------------

DA, DB = 12, 9
_CHUNKS = [
    (jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32))
    for a, b in (
        (np.random.default_rng(100 + i).standard_normal((8, DA)),
         np.random.default_rng(200 + i).standard_normal((8, DB)))
        for i in range(4)
    )
]


def _source_factory(start=0):
    return iter(_CHUNKS[start:])


def _fit(omega, engine, cfg):
    return randomized_cca_iterator(
        _source_factory, DA, DB, cfg, jax.random.PRNGKey(5),
        engine=engine, merge_group=2, omega=omega, n_chunks=len(_CHUNKS))


def _assert_bit_identical(r1, r2):
    for name in ("Xa", "Xb", "rho", "Qa", "Qb"):
        a1, a2 = np.asarray(getattr(r1, name)), np.asarray(getattr(r2, name))
        assert np.array_equal(a1, a2), f"{name} differs"


@pytest.mark.parametrize("engine", ["kernels", "jnp"])
@pytest.mark.parametrize("cfg", [
    RCCAConfig(k=2, p=2, q=0, nu=0.01),
    RCCAConfig(k=2, p=2, q=1, nu=0.01, center=True),
], ids=["q0-sketch", "q1-centered"])
def test_fit_seeded_matches_oracle_bitwise(engine, cfg):
    """The acceptance criterion: under BOTH engines, the seeded path
    (in-kernel Ω tiles under "kernels"; local stateless materialization
    under "jnp") reproduces the materialized-up-front oracle exactly —
    including the q=0 direct sketch and the centered power boundary,
    the two places the engine must materialize Q from the seed."""
    _assert_bit_identical(_fit("seeded", engine, cfg),
                          _fit("seeded-materialized", engine, cfg))


# --------------------------------------------------------------------------
# no (d, k̃) Ω array exists in the seeded pass — structural jaxpr check
# --------------------------------------------------------------------------


def _shapes(jaxpr, out):
    """All aval shapes in a jaxpr, recursing through sub-jaxprs but NOT
    into pallas kernels — in-VMEM tiles are the point of the design;
    the claim is about what exists at the XLA/HBM level."""
    for v in list(jaxpr.invars) + list(jaxpr.constvars) + list(jaxpr.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            out.append(tuple(aval.shape))
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(tuple(aval.shape))
        if "pallas" in eqn.primitive.name:
            continue
        for p in eqn.params.values():
            for sub in compat.sub_jaxprs(p):
                _shapes(sub, out)
    return out


def test_seeded_final_update_never_materializes_omega():
    """In the final (q=0) update the ONLY (d, k̃)-shaped arrays of the
    materialized path are Ω themselves (stats are (k̃, k̃)) — so the
    seeded jaxpr must contain NO such aval anywhere outside the pallas
    kernels, while the materialized control must (detector is not
    vacuous)."""
    c, da, db, kt = 8, 512, 384, 256
    s = stats_init_fn("final", da, db, kt)()
    a = jnp.zeros((c, da), jnp.float32)
    b = jnp.zeros((c, db), jnp.float32)
    seed_a, seed_b = omega_seeds(jax.random.PRNGKey(0))

    seeded = jax.make_jaxpr(seeded_update_fn("final", kt, jnp.float32))(
        s, a, b, seed_a, seed_b)
    shapes = set(_shapes(seeded.jaxpr, []))
    assert (da, kt) not in shapes and (db, kt) not in shapes

    Qa = jnp.zeros((da, kt), jnp.float32)
    Qb = jnp.zeros((db, kt), jnp.float32)
    control = jax.make_jaxpr(update_fn("final", "kernels"))(s, a, b, Qa, Qb)
    cshapes = set(_shapes(control.jaxpr, []))
    assert (da, kt) in cshapes and (db, kt) in cshapes


def test_seeded_power_update_inputs_carry_seeds_not_omega():
    """The power update legitimately holds (d, k̃) arrays (the Y
    accumulators), so the structural claim is on the input signature:
    exactly ONE (d, k̃) invar per view (the accumulator) plus two
    (2,)-uint32 seeds — the materialized twin has TWO per view."""
    c, da, db, kt = 8, 512, 384, 256
    s = stats_init_fn("power", da, db, kt)()
    a = jnp.zeros((c, da), jnp.float32)
    b = jnp.zeros((c, db), jnp.float32)
    seed_a, seed_b = omega_seeds(jax.random.PRNGKey(0))

    seeded = jax.make_jaxpr(seeded_update_fn("power", kt, jnp.float32))(
        s, a, b, seed_a, seed_b)
    invars = [tuple(v.aval.shape) for v in seeded.jaxpr.invars]
    assert invars.count((da, kt)) == 1 and invars.count((db, kt)) == 1
    assert invars.count((2,)) == 2

    Qa = jnp.zeros((da, kt), jnp.float32)
    Qb = jnp.zeros((db, kt), jnp.float32)
    control = jax.make_jaxpr(update_fn("power", "kernels"))(s, a, b, Qa, Qb)
    cinvars = [tuple(v.aval.shape) for v in control.jaxpr.invars]
    assert cinvars.count((da, kt)) == 2 and cinvars.count((db, kt)) == 2


# --------------------------------------------------------------------------
# store-backed seeded fits: cursors hold seeds, resume is bit-identical
# --------------------------------------------------------------------------


class Kill(Exception):
    """Simulated mid-pass crash."""


@pytest.fixture(scope="module")
def seed_store(tmp_path_factory):
    data = PlantedCCAData(n=600, da=24, db=16, rank=4, noise=0.3,
                          seed=11, chunk=100)  # 6 chunks per pass
    return ingest_planted(str(tmp_path_factory.mktemp("seeded") / "store"),
                          data)


SCFG = RCCAConfig(k=3, p=5, q=1, nu=0.01, center=True)


def test_seeded_kill_resume_bit_identical(seed_store, tmp_path):
    """Kill a seeded kernels-engine fit mid pass 0 — where the cursor's
    Qa/Qb slots hold the (2,)-uint32 seeds, not (d, k̃) bases — and the
    resumed fit must reproduce the uninterrupted one bitwise."""
    key = jax.random.PRNGKey(3)
    base = PassRunner(seed_store, SCFG, engine="kernels", prefetch=0,
                      omega="seeded").fit(key)
    oracle = PassRunner(seed_store, SCFG, engine="kernels", prefetch=0,
                        omega="seeded-materialized").fit(key)
    _assert_bit_identical(base, oracle)

    ck = str(tmp_path / "ck")
    runner = PassRunner(seed_store, SCFG, engine="kernels", prefetch=0,
                        ckpt_dir=ck, ckpt_every=2, omega="seeded")

    def crash(pass_idx, chunk_idx, *_):
        if (pass_idx, chunk_idx) == (0, 3):
            raise Kill

    with pytest.raises(Kill):
        runner.fit(key, resume=False, on_chunk=crash)
    resumed = PassRunner(seed_store, SCFG, engine="kernels", prefetch=0,
                         ckpt_dir=ck, omega="seeded").fit(key, resume=True)
    assert resumed.diagnostics["io"]["resumed"]
    _assert_bit_identical(base, resumed)


def test_cursor_omega_binding(seed_store, tmp_path):
    """Ω provenance is part of the pass state: a cursor written by a
    seeded fit must refuse to resume a materialized one (the pass-0
    payload is a seed, not a basis)."""
    ck = str(tmp_path / "ck")
    runner = PassRunner(seed_store, SCFG, engine="kernels", prefetch=0,
                        ckpt_dir=ck, ckpt_every=2, omega="seeded")

    def crash(pass_idx, chunk_idx, *_):
        if (pass_idx, chunk_idx) == (0, 3):
            raise Kill

    with pytest.raises(Kill):
        runner.fit(jax.random.PRNGKey(3), resume=False, on_chunk=crash)
    with pytest.raises(ValueError, match="omega"):
        PassRunner(seed_store, SCFG, engine="kernels", prefetch=0,
                   ckpt_dir=ck).fit(jax.random.PRNGKey(3), resume=True)


# --------------------------------------------------------------------------
# S1: prefetcher error propagation — a failed read is never swallowed
# --------------------------------------------------------------------------


def test_prefetcher_midstream_error_raises_at_consumer():
    def gen():
        yield (np.ones(3), np.zeros(2))
        raise RuntimeError("disk died")

    pf = ChunkPrefetcher(gen(), depth=2, device_put=False)
    assert np.array_equal(next(pf)[0], np.ones(3))
    with pytest.raises(RuntimeError, match="disk died"):
        next(pf)
    pf.close()  # already delivered in __next__ — close() stays silent


def test_prefetcher_undelivered_error_raises_on_close():
    """The regression: a consumer that shuts the pipeline down before
    reaching the failing chunk must still see the producer's error."""
    def gen():
        raise RuntimeError("boom")
        yield  # pragma: no cover

    pf = ChunkPrefetcher(gen(), depth=2, device_put=False)
    with pytest.raises(RuntimeError, match="boom"):
        pf.close()
    pf.close()  # idempotent: the error is raised exactly once


def test_prefetcher_clean_streams_unaffected():
    chunks = [(np.zeros(1), np.ones(1))] * 6
    pf = ChunkPrefetcher(iter(chunks), depth=2, device_put=False)
    assert len(list(pf)) == 6
    pf.close()
    # early close of a healthy stream: no error, no producer wedge
    pf2 = ChunkPrefetcher(iter(chunks), depth=1, device_put=False)
    next(pf2)
    pf2.close()


# --------------------------------------------------------------------------
# S2: stale-partial cleanup failures surface instead of passing silently
# --------------------------------------------------------------------------


def _meta(fit_id, omega="materialized"):
    return pt.binding_meta(fit_id=fit_id, pass_idx=0, kind="final",
                           engine="jnp", fingerprint="fp", merge_group=2,
                           algo={"k": 1}, omega=omega)


def _publish(cluster_dir, group, meta):
    pt.write_partial(cluster_dir, 0, group, stats_init_fn("final", 4, 3, 2)(),
                     meta, shard=0, n_shards=1)


def test_clear_stale_partial_reports_failure(tmp_path, monkeypatch):
    cd = str(tmp_path)
    _publish(cd, 0, _meta("old"))

    def boom(path, **kw):
        raise OSError("read-only filesystem")

    monkeypatch.setattr(pt.shutil, "rmtree", boom)
    err = pt.clear_stale_partial(cd, 0, 0)
    assert err is not None and "read-only filesystem" in err
    assert pt.partial_meta(cd, 0, 0) is not None  # still on disk
    monkeypatch.undo()
    assert pt.clear_stale_partial(cd, 0, 0) is None  # retry succeeds
    assert pt.partial_meta(cd, 0, 0) is None
    assert pt.clear_stale_partial(cd, 0, 0) is None  # already gone


def test_sweep_stale_partials_returns_failures(tmp_path, monkeypatch):
    cd = str(tmp_path)
    expect = _meta("new")
    _publish(cd, 0, _meta("old"))       # stale, removable
    _publish(cd, 1, _meta("old"))       # stale, removal will fail
    _publish(cd, 2, expect)             # valid — must be left alone
    real_rmtree = pt.shutil.rmtree
    doomed = pt.partial_path(cd, 0, 1)

    def selective(path, **kw):
        if path == doomed:
            raise OSError("EBUSY")
        return real_rmtree(path, **kw)

    monkeypatch.setattr(pt.shutil, "rmtree", selective)
    failures = pt.sweep_stale_partials(cd, 0, n_groups=3, expect=expect)
    assert list(failures) == [1] and "EBUSY" in failures[1]
    assert pt.partial_meta(cd, 0, 0) is None          # stale one removed
    assert pt.partial_meta(cd, 0, 1) is not None      # failed removal stays
    assert pt.binding_matches(pt.partial_meta(cd, 0, 2), expect)  # untouched


def test_omega_is_binding_for_rounds_and_partials():
    """A seeded round's Qa/Qb payload is a seed, not a basis — a worker
    or sweep comparing metadata across Ω provenance must see a
    mismatch."""
    assert "omega" in pt.BINDING_KEYS
    assert not pt.binding_matches(_meta("f", omega="seeded"),
                                  _meta("f", omega="materialized"))
    assert pt.binding_matches(_meta("f", omega="seeded"),
                              _meta("f", omega="seeded"))


# --------------------------------------------------------------------------
# static analysis: RCCA108 (seeded kernel contract) + RCCA006 (RNG home)
# --------------------------------------------------------------------------


def _seeded_plan(name="fixture_seeded",
                 scalars=(ScalarDef((2,), "uint32"),)):
    spec = BlockDef(shape=(128, 128), index_map=lambda i, j: (i, j),
                    padded=(256, 256), dtype="float32")
    return KernelPlan(name=name, grid=(2, 2), in_specs=(spec,),
                      out_specs=(spec,), scratch=(),
                      out_shape=((250, 250),), scalars=tuple(scalars))


def test_rcca108_valid_seeded_plan_is_clean():
    assert kernel_check.check_plan(_seeded_plan()) == []


def test_rcca108_seeded_plan_scalar_count():
    vs = kernel_check.check_plan(_seeded_plan(scalars=()))
    assert codes(vs) == ["RCCA108"]
    vs = kernel_check.check_plan(_seeded_plan(
        scalars=(ScalarDef((2,), "uint32"), ScalarDef((2,), "uint32"))))
    assert "RCCA108" in codes(vs)


def test_rcca108_scalar_must_be_integer_seed():
    vs = kernel_check.check_plan(_seeded_plan(
        scalars=(ScalarDef((2,), "float32"),)))
    assert codes(vs) == ["RCCA108"]
    # the dtype rule guards ALL plans with scalars, seeded-named or not
    vs = kernel_check.check_plan(_seeded_plan(
        name="fixture", scalars=(ScalarDef((2,), "float32"),)))
    assert codes(vs) == ["RCCA108"]


def test_rcca108_scalar_must_not_smuggle_arrays():
    vs = kernel_check.check_plan(_seeded_plan(
        scalars=(ScalarDef((4, 4), "uint32"),)))
    assert codes(vs) == ["RCCA108"]


def test_registry_declares_seeded_kernels():
    from repro.kernels import KERNEL_REGISTRY

    assert "powerpass_seeded" in KERNEL_REGISTRY
    assert "projgram_seeded" in KERNEL_REGISTRY


def test_rcca006_random_draw_outside_rng_home_trips():
    src = "def f(key):\n    return jax.random.normal(key, (4, 4))\n"
    vs = lint.lint_source(src, "repro/exec/engine.py")
    assert codes(vs) == ["RCCA006"]
    assert "rcca" in vs[0].message
    src2 = "def f(key):\n    return jrandom.split(key)\n"
    assert codes(lint.lint_source(src2, "repro/cluster/worker.py")) == \
        ["RCCA006"]


def test_rcca006_rng_home_and_non_pass_path_pass():
    src = "def f(key):\n    return jax.random.normal(key, (4, 4))\n"
    assert lint.lint_source(src, "repro/core/rcca.py") == []     # RNG home
    assert lint.lint_source(src, "repro/launch/bench.py") == []  # not pass-path
    ok = "def f(s):\n    return rand.dense_omega(s, 8, 4)\n"
    assert lint.lint_source(ok, "repro/exec/engine.py") == []
