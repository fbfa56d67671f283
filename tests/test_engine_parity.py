"""Compat-shim behaviour, kernel-vs-jnp engine
parity for the data-pass drivers, the fused power-pass acceptance
criteria, and the block-size autotuner."""

import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.core.rcca import (
    RCCAConfig,
    randomized_cca_iterator,
    randomized_cca_streaming,
    resolve_engine,
)
from repro.core.rcca_dist import dist_randomized_cca
from repro.kernels import autotune, compat, ops, ref
from repro.kernels.powerpass import power_project_accumulate
from repro.data import planted_views


# --------------------------------------------------------------------------
# compat shim
# --------------------------------------------------------------------------


def test_compiler_params_old_spelling():
    """The shim builds the ``pltpu.CompilerParams`` pallas accepts."""
    params = compat.tpu_compiler_params(
        dimension_semantics=("parallel", "arbitrary")
    )
    assert isinstance(params, pltpu.CompilerParams)
    assert tuple(params.dimension_semantics) == ("parallel", "arbitrary")


def test_compiler_params_new_spelling(monkeypatch):
    """When pltpu.CompilerParams exists (jax ≥ 0.5) it must win."""

    class FakeCompilerParams:
        def __init__(self, dimension_semantics=None, **kw):
            self.dimension_semantics = dimension_semantics

    monkeypatch.setattr(pltpu, "CompilerParams", FakeCompilerParams,
                        raising=False)
    params = compat.tpu_compiler_params(dimension_semantics=("arbitrary",))
    assert isinstance(params, FakeCompilerParams)


def test_set_mesh_new_spelling(monkeypatch):
    """With jax.set_mesh present (jax ≥ 0.5) the shim must call it."""
    calls = []

    @contextlib.contextmanager
    def fake_set_mesh(mesh):
        calls.append(mesh)
        yield

    monkeypatch.setattr(jax, "set_mesh", fake_set_mesh, raising=False)
    mesh = jax.make_mesh((1,), ("data",))
    with compat.set_mesh(mesh):
        pass
    assert calls == [mesh]


def test_cost_analysis_normalized():
    class FakeCompiledDict:
        def cost_analysis(self):
            return {"flops": 7.0}

    class FakeCompiledEmpty:
        def cost_analysis(self):
            return None

    assert compat.cost_analysis(FakeCompiledDict())["flops"] == 7.0
    assert compat.cost_analysis(FakeCompiledEmpty()) == {}


def test_resolve_engine():
    assert resolve_engine("kernels") == "kernels"
    assert resolve_engine("jnp") == "jnp"
    # legacy boolean spelling wins when passed explicitly
    assert resolve_engine("kernels", use_kernels=False) == "jnp"
    assert resolve_engine("jnp", use_kernels=True) == "kernels"
    with pytest.raises(ValueError):
        resolve_engine("cuda")


# --------------------------------------------------------------------------
# fused power pass: acceptance criteria
# --------------------------------------------------------------------------


def test_power_pass_chunk_is_fused():
    """≤ 2 pallas_calls per chunk (one fused kernel per view), down from
    the 4 of the unfused project/accumulate pairs."""
    a = jnp.zeros((256, 192))
    b = jnp.zeros((256, 160))
    Qa = jnp.zeros((192, 96))
    Qb = jnp.zeros((160, 96))
    jaxpr = jax.make_jaxpr(
        lambda *xs: ops.power_pass_chunk(*xs, interpret=True)
    )(a, b, Qa, Qb)
    assert compat.count_pallas_calls(jaxpr) <= 2


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_power_project_accumulate_matches_ref(dt):
    kx = jax.random.PRNGKey(0)
    a = jax.random.normal(kx, (384, 300), dt)
    b = jax.random.normal(jax.random.PRNGKey(1), (384, 200), dt)
    q = jax.random.normal(jax.random.PRNGKey(2), (200, 160), dt)
    got = power_project_accumulate(a, b, q, interpret=True)
    want = ref.matmul_ref(a, ref.matmul_ref(b, q), transpose_lhs=True)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel <= (1e-4 if dt == jnp.float32 else 2e-2), rel


def test_power_project_accumulate_large_block_bucketed():
    """dap·k̃p over the per-block VMEM cap now runs the bucketed fused
    grid (it used to fall back to the unfused pair) and stays correct."""
    a = jax.random.normal(jax.random.PRNGKey(0), (128, 1100))  # dap = 1152
    b = jax.random.normal(jax.random.PRNGKey(1), (128, 96))
    q = jax.random.normal(jax.random.PRNGKey(2), (96, 1100))  # ktp = 1152
    got = power_project_accumulate(a, b, q, interpret=True)
    want = ref.matmul_ref(a, ref.matmul_ref(b, q), transpose_lhs=True)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel <= 1e-4, rel


def test_power_project_accumulate_degenerate_fallback():
    """k̃p > 8192 (no 128-row block fits VMEM) still takes the unfused
    matmul pair and stays correct."""
    a = jax.random.normal(jax.random.PRNGKey(0), (128, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (128, 96))
    q = jax.random.normal(jax.random.PRNGKey(2), (96, 8300))  # ktp = 8320
    got = power_project_accumulate(a, b, q, interpret=True)
    want = ref.matmul_ref(a, ref.matmul_ref(b, q), transpose_lhs=True)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel <= 1e-4, rel


# --------------------------------------------------------------------------
# engine parity: streaming / iterator / dist drivers
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def views():
    A, B = planted_views(3, n=1200, da=40, db=32, rank=5, noise=0.4)
    return jnp.asarray(A), jnp.asarray(B)


@pytest.mark.parametrize("dt,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_streaming_engine_parity(views, dt, tol):
    A, B = views
    cfg = RCCAConfig(k=4, p=12, q=1, lam_a=1e-3, lam_b=1e-3, dtype=dt)
    Ac = A.astype(dt).reshape(4, 300, A.shape[1])
    Bc = B.astype(dt).reshape(4, 300, B.shape[1])
    r_k = randomized_cca_streaming(Ac, Bc, cfg, jax.random.PRNGKey(0), engine="kernels")
    r_j = randomized_cca_streaming(Ac, Bc, cfg, jax.random.PRNGKey(0), engine="jnp")
    np.testing.assert_allclose(np.asarray(r_k.rho), np.asarray(r_j.rho), atol=tol)
    np.testing.assert_allclose(np.asarray(jnp.abs(r_k.Xa)), np.asarray(jnp.abs(r_j.Xa)),
                               atol=max(tol, 1e-3) * 30)


def test_streaming_legacy_use_kernels_flag(views):
    A, B = views
    cfg = RCCAConfig(k=4, p=12, q=1, lam_a=1e-3, lam_b=1e-3)
    Ac = A.reshape(4, 300, A.shape[1])
    Bc = B.reshape(4, 300, B.shape[1])
    r_legacy = randomized_cca_streaming(Ac, Bc, cfg, jax.random.PRNGKey(0),
                                        use_kernels=False)
    r_jnp = randomized_cca_streaming(Ac, Bc, cfg, jax.random.PRNGKey(0),
                                     engine="jnp")
    np.testing.assert_array_equal(np.asarray(r_legacy.rho), np.asarray(r_jnp.rho))


def test_iterator_engine_parity(views):
    A, B = views
    da, db = A.shape[1], B.shape[1]
    cfg = RCCAConfig(k=4, p=12, q=1, lam_a=1e-3, lam_b=1e-3)
    chunks = [(np.asarray(A[i::3]), np.asarray(B[i::3])) for i in range(3)]
    r_k = randomized_cca_iterator(lambda: iter(chunks), da, db, cfg,
                                  jax.random.PRNGKey(1), engine="kernels")
    r_j = randomized_cca_iterator(lambda: iter(chunks), da, db, cfg,
                                  jax.random.PRNGKey(1), engine="jnp")
    np.testing.assert_allclose(np.asarray(r_k.rho), np.asarray(r_j.rho), atol=1e-4)


def test_dist_engine_parity_single_device(views):
    """The dist driver's engine knob on a trivial mesh (the multi-device
    kernel path is covered by test_distributed.py)."""
    A, B = views
    mesh = jax.make_mesh((1,), ("data",))
    cfg = RCCAConfig(k=4, p=12, q=1, lam_a=1e-3, lam_b=1e-3)
    kw = dict(row_axes=("data",), col_axis=None, microbatch=300)
    r_k = dist_randomized_cca(A, B, cfg, jax.random.PRNGKey(2), mesh,
                              engine="kernels", **kw)
    r_j = dist_randomized_cca(A, B, cfg, jax.random.PRNGKey(2), mesh,
                              engine="jnp", **kw)
    np.testing.assert_allclose(np.asarray(r_k.rho), np.asarray(r_j.rho), atol=1e-4)


# --------------------------------------------------------------------------
# autotuner
# --------------------------------------------------------------------------


@pytest.fixture()
def tuned_cache(tmp_path, monkeypatch):
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv("RCCA_AUTOTUNE_CACHE", path)
    autotune.reset()
    yield path
    autotune.reset()


def test_autotune_record_lookup_roundtrip(tuned_cache):
    assert autotune.lookup("matmul_nn", 256, 256, 256, jnp.float32) == \
        autotune.DEFAULT_CAPS
    autotune.record("matmul_nn", 256, 256, 256, jnp.float32, (128, 256, 128),
                    us=12.5)
    assert autotune.lookup("matmul_nn", 256, 256, 256, jnp.float32) == (128, 256, 128)
    # persisted: survives an in-memory reset
    autotune.reset()
    assert autotune.lookup("matmul_nn", 256, 256, 256, jnp.float32) == (128, 256, 128)
    with open(tuned_cache) as f:
        stored = json.load(f)
    assert len(stored) == 1 and "blocks" in next(iter(stored.values()))


def test_autotune_sweep_and_matmul_pickup(tuned_cache):
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 199))
    y = jax.random.normal(jax.random.PRNGKey(1), (199, 256))
    blocks = autotune.autotune_matmul(x, y, interpret=True, iters=1)
    Mp, Kp, Np = 256, 256, 256
    assert Mp % blocks[0] == 0 and Np % blocks[1] == 0 and Kp % blocks[2] == 0
    assert autotune.lookup("matmul_nn", Mp, Kp, Np, jnp.float32) == blocks
    # the default-blocks matmul path resolves through the tuned entry
    from repro.kernels import pallas_matmul

    out = pallas_matmul(x, y, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul_ref(x, y)),
                               atol=2e-4, rtol=2e-4)


def test_autotune_corrupt_cache_falls_back(tuned_cache):
    with open(tuned_cache, "w") as f:
        f.write("{not json")
    autotune.reset()
    assert autotune.lookup("matmul_nn", 512, 512, 512, jnp.float32) == \
        autotune.DEFAULT_CAPS


def test_autotune_malformed_entry_falls_back(tuned_cache):
    """Valid JSON but wrong schema must not break the engine."""
    key = autotune.shape_key("matmul_nn", 256, 256, 256, jnp.float32)
    with open(tuned_cache, "w") as f:
        json.dump({key: {"bm": 128}}, f)
    autotune.reset()
    assert autotune.lookup("matmul_nn", 256, 256, 256, jnp.float32) == \
        autotune.DEFAULT_CAPS
