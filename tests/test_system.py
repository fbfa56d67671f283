"""End-to-end system behaviour: the paper's claims reproduced at test
scale, plus full pipeline integration (train driver, CCA driver,
activation harvesting)."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    HorstConfig,
    cca_objective,
    exact_cca,
    horst_cca,
    randomized_cca,
)
from repro.core.rcca import RCCAConfig
from repro.data import PlantedCCAData


@pytest.fixture(scope="module")
def europarl_like():
    """Train/test split of a planted-correlation corpus (paper §4 setup)."""
    d = PlantedCCAData(n=4000, da=64, db=56, rank=24, decay=0.8, noise=0.6,
                       seed=11, chunk=500)
    A, B = d.materialize()
    n_tr = 3600
    return (jnp.asarray(A[:n_tr]), jnp.asarray(B[:n_tr]),
            jnp.asarray(A[n_tr:]), jnp.asarray(B[n_tr:]))


K = 8


def test_paper_fig2a_objective_improves_with_p_and_q(europarl_like):
    """Fig 2a: the objective increases with oversampling p and passes q,
    approaching the Horst (near-exact) optimum."""
    A, B, _, _ = europarl_like
    lam = 1e-3
    ex = exact_cca(A, B, K, lam, lam)
    opt = float(jnp.sum(ex.rho))

    def obj(p, q, seed=0):
        cfg = RCCAConfig(k=K, p=p, q=q, lam_a=lam, lam_b=lam)
        r = randomized_cca(A, B, cfg, jax.random.PRNGKey(seed))
        return float(jnp.sum(r.rho))

    o_p4_q0 = obj(4, 0)
    o_p16_q0 = obj(16, 0)
    o_p16_q1 = obj(16, 1)
    o_p32_q2 = obj(32, 2)
    assert o_p16_q0 >= o_p4_q0 - 0.02  # more oversampling helps (q=0 row)
    assert o_p16_q1 >= o_p16_q0       # a power pass helps
    assert o_p32_q2 >= 0.995 * opt    # converges to the optimum
    assert o_p32_q2 <= opt + 1e-3     # never exceeds it


def test_paper_inherent_regularization(europarl_like):
    """§4: RandomizedCCA generalizes; its train/test gap is no worse
    than Horst's at the same regularization."""
    A, B, At, Bt = europarl_like
    nu = 0.01
    r = randomized_cca(A, B, RCCAConfig(k=K, p=16, q=1, nu=nu), jax.random.PRNGKey(0))
    h = horst_cca(A, B, HorstConfig(k=K, iters=40, nu=nu), key=jax.random.PRNGKey(1))

    def gap(Xa, Xb):
        tr = float(cca_objective(A, B, Xa, Xb))
        te = float(cca_objective(At, Bt, Xa, Xb))
        return tr - te

    assert gap(r.Xa, r.Xb) <= gap(h.Xa, h.Xb) + 0.05
    # and rcca's test objective is competitive (within 2%)
    te_r = float(cca_objective(At, Bt, r.Xa, r.Xb))
    te_h = float(cca_objective(At, Bt, h.Xa, h.Xb))
    assert te_r >= te_h - 0.02 * abs(te_h)


def test_train_driver_integration(tmp_path):
    """launch.train runs, checkpoints, and resumes."""
    from repro.launch.train import main as train_main

    ck = str(tmp_path / "ck")
    train_main(["--arch", "granite-3-2b", "--smoke", "--steps", "3",
                "--batch", "2", "--seq", "32", "--ckpt-dir", ck,
                "--ckpt-every", "1", "--loss-chunks", "2"])
    train_main(["--arch", "granite-3-2b", "--smoke", "--steps", "5",
                "--batch", "2", "--seq", "32", "--ckpt-dir", ck,
                "--ckpt-every", "2", "--loss-chunks", "2"])


def test_cca_driver_integration():
    from repro.launch.cca_fit import main as cca_main

    report = cca_main(["--smoke", "--mode", "dist"])
    assert report["oracle_gap"] < 0.01, report
    assert all(v < 1e-4 for v in report["feasibility"].values()), report


def test_compile_cache_placement(monkeypatch):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR when
    set (jax reads it; nothing else is configured), otherwise the fixed
    .jax_cache/ directory of the checkout."""
    import pathlib

    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/srv/jax-cache")
    assert compile_cache.use_compile_cache() == "/srv/jax-cache"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.use_compile_cache() == str(checkout / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir",
                        str(checkout / ".jax_cache"))]


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py proves the chip path: on a CPU it exits non-zero
    before any phase and prints no result line."""
    import os
    import pathlib
    import subprocess
    import sys

    checkout = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=checkout,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_serve_driver_integration():
    from repro.launch.serve import main as serve_main

    serve_main(["--arch", "granite-3-2b", "--smoke", "--batch", "2",
                "--prompt-len", "8", "--gen", "4"])


def test_activation_cca_harvest():
    """The paper's technique applied to the model zoo: CCA between two
    LMs' hidden representations of THE SAME token stream recovers high
    canonical correlation; destroying the row alignment (shuffle one
    view) destroys it — CCA finds aligned structure."""
    from repro.configs import get_config
    from repro.core.harvest import activation_views
    from repro.models import build_model

    cfg = get_config("granite-3-2b", smoke=True)
    m1 = build_model(cfg)
    m2 = build_model(cfg)
    p1 = m1.init(jax.random.PRNGKey(0))
    p2 = m2.init(jax.random.PRNGKey(1))  # different weights, same stream

    toks = jax.random.randint(jax.random.PRNGKey(2), (8, 64), 0, cfg.vocab)

    A = activation_views(m1, p1, {"tokens": toks})
    B = activation_views(m2, p2, {"tokens": toks})
    perm = jax.random.permutation(jax.random.PRNGKey(3), B.shape[0])

    k = 4
    cfg_r = RCCAConfig(k=k, p=16, q=2, nu=0.01, center=True)
    r_same = randomized_cca(A, B, cfg_r, jax.random.PRNGKey(4))
    r_shuf = randomized_cca(A, B[perm], cfg_r, jax.random.PRNGKey(4))
    assert float(jnp.sum(r_same.rho)) > float(jnp.sum(r_shuf.rho)) + 0.5
