"""Multi-device tests — run in a subprocess with 8 fake CPU devices so
the main pytest process keeps its single-device view (the dry-run spec
forbids setting the device-count flag globally)."""

import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


def test_dist_rcca_matches_reference():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.rcca import RCCAConfig, randomized_cca
        from repro.core.rcca_dist import dist_randomized_cca
        from repro.core import feasibility_errors
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh((2, 2, 2), ("pod", "data", "model"))
        key = jax.random.PRNGKey(0)
        n, da, db, k = 2048, 64, 32, 5
        kz, ka, kb, kn = jax.random.split(key, 4)
        Z = jax.random.normal(kz, (n, k))
        A = Z @ jax.random.normal(ka, (k, da)) + 0.5 * jax.random.normal(kn, (n, da))
        B = Z @ jax.random.normal(kb, (k, db)) + 0.5 * jax.random.normal(jax.random.PRNGKey(9), (n, db))
        cfg = RCCAConfig(k=k, p=16, q=2, lam_a=1e-3, lam_b=1e-3)
        r_ref = randomized_cca(A, B, cfg, jax.random.PRNGKey(1))
        r_dist = dist_randomized_cca(A, B, cfg, jax.random.PRNGKey(1), mesh, microbatch=128)
        np.testing.assert_allclose(np.asarray(r_ref.rho), np.asarray(r_dist.rho), atol=2e-4)
        errs = feasibility_errors(A, B, jnp.asarray(r_dist.Xa), jnp.asarray(r_dist.Xb), 1e-3, 1e-3)
        assert all(float(v) < 1e-4 for v in errs.values()), errs
        # centered variant
        cfgc = RCCAConfig(k=k, p=16, q=1, lam_a=1e-3, lam_b=1e-3, center=True)
        rd = dist_randomized_cca(A + 3, B - 2, cfgc, jax.random.PRNGKey(1), mesh, microbatch=128)
        rr = randomized_cca(A + 3, B - 2, cfgc, jax.random.PRNGKey(1))
        np.testing.assert_allclose(np.asarray(rd.rho), np.asarray(rr.rho), atol=2e-4)
        print("OK")
    """)


def test_dist_rcca_nu_on_sharded_features():
    """ν-regularized fit with the features sharded: λ = ν‖A‖²_F / d sums
    the trace over every feature shard, so each shard of X is whitened
    with the same λ and the fit is feasible — for every engine and
    collective."""
    run_with_devices("""
        import jax, numpy as np
        from repro.core.rcca import RCCAConfig, randomized_cca
        from repro.core.rcca_dist import dist_randomized_cca
        from repro.core import feasibility_errors
        from repro.launch.mesh import make_host_mesh

        rng = np.random.default_rng(3)
        n, da, db, k = 256, 64, 48, 4
        Z = rng.standard_normal((n, k))
        A = (Z @ rng.standard_normal((k, da))
             + rng.standard_normal((n, da))).astype(np.float32)
        B = (Z @ rng.standard_normal((k, db))
             + rng.standard_normal((n, db))).astype(np.float32)
        cfg = RCCAConfig(k=k, p=12, q=1, nu=0.01)
        key = jax.random.PRNGKey(1)
        ref = randomized_cca(A, B, cfg, key)
        lam = [float(ref.diagnostics[f"lam_{v}"]) for v in "ab"]
        mesh = make_host_mesh((2, 2), ("data", "model"))
        for engine, collective in (("jnp", "fused"), ("kernels", "fused"),
                                   ("kernels", "unfused")):
            r = dist_randomized_cca(A, B, cfg, key, mesh, row_axes=("data",),
                                    col_axis="model", microbatch=32,
                                    engine=engine, collective=collective)
            got = [float(r.diagnostics[f"lam_{v}"]) for v in "ab"]
            np.testing.assert_allclose(got, lam, rtol=1e-5)
            errs = feasibility_errors(A, B, r.Xa, r.Xb, *lam)
            assert all(float(v) < 1e-4 for v in errs.values()), (engine, errs)
            np.testing.assert_allclose(np.asarray(r.rho),
                                       np.asarray(ref.rho), atol=2e-4)
        print("OK")
    """, n=4)


def test_dist_rcca_mesh_shapes_agree():
    """Elastic meshes: (2,2,2), (4,2), (8,) row-only — identical results."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.rcca import RCCAConfig
        from repro.core.rcca_dist import dist_randomized_cca

        key = jax.random.PRNGKey(0)
        n, da, db, k = 1024, 32, 32, 4
        Z = jax.random.normal(key, (n, k))
        A = Z @ jax.random.normal(jax.random.PRNGKey(1), (k, da)) + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (n, da))
        B = Z @ jax.random.normal(jax.random.PRNGKey(3), (k, db)) + 0.3 * jax.random.normal(jax.random.PRNGKey(4), (n, db))
        cfg = RCCAConfig(k=k, p=12, q=1, lam_a=1e-3, lam_b=1e-3)
        rhos = []
        for shape, axes in [((2,2,2), ("pod","data","model")), ((4,2), ("data","model")), ((8,), ("data",))]:
            mesh = jax.make_mesh(shape, axes)
            r = dist_randomized_cca(A, B, cfg, jax.random.PRNGKey(7), mesh, microbatch=128)
            rhos.append(np.asarray(r.rho))
        for other in rhos[1:]:
            np.testing.assert_allclose(rhos[0], other, atol=2e-4)
        print("OK")
    """)


def test_compressed_psum_error_feedback():
    """int8+EF psum: relative error small, EF shrinks bias across rounds."""
    run_with_devices("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import psum_int8_ef
        from repro.kernels.compat import shard_map
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh((4,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 256))

        @functools.partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=(P("data"), P("data")), check_rep=False)
        def one_round(xl):
            out, err = psum_int8_ef(xl[0], "data")
            return out[None], err[None]

        out, err = one_round(x)
        exact = jnp.sum(x, axis=0)
        rel = float(jnp.linalg.norm(out[0] - exact) / jnp.linalg.norm(exact))
        assert rel < 0.02, rel
        # EF: accumulated over rounds, the *sum* of outputs tracks the sum of exact values
        @functools.partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data")), check_rep=False)
        def with_err(xl, errl):
            out, err = psum_int8_ef(xl[0], "data", errl[0])
            return out[None], err[None]
        total_out = out
        for _ in range(4):
            o2, err = with_err(x, err)
            total_out = total_out + o2
        rel2 = float(jnp.linalg.norm(total_out[0] / 5 - exact) / jnp.linalg.norm(exact))
        assert rel2 < rel * 1.5, (rel2, rel)
        print("OK", rel, rel2)
    """)


def test_dryrun_machinery_small_mesh():
    """lower+compile one train and one decode cell of every family on a
    2×2×2 mesh with reduced configs (fast stand-in for the 512-chip run;
    the full run is results/dryrun)."""
    run_with_devices("""
        import jax
        from repro.configs import get_config
        from repro.kernels.compat import cost_analysis
        from repro.launch import steps as S
        import repro.launch.dryrun as D

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        S.SHAPES = {
            "train_4k": S.ShapeSpec("train_4k", "train", 256, 8),
            "decode_32k": S.ShapeSpec("decode_32k", "decode", 512, 8),
        }
        D.get_config = lambda a: get_config(a, smoke=True)
        for arch in ["gemma3-1b", "kimi-k2-1t-a32b", "deepseek-v2-236b",
                     "xlstm-350m", "zamba2-7b", "qwen2-vl-2b"]:
            for shape in ["train_4k", "decode_32k"]:
                lowered, meta = D.lower_cell(arch, shape, mesh, loss_chunks=4)
                compiled = lowered.compile()
                assert cost_analysis(compiled).get("flops", 0) > 0, (arch, shape)
        print("OK")
    """, timeout=1800)
