"""Every program a streaming fit launches outside its chunk folds is one
cached, compiled callable: the Q update (``power_update_Q``), the finish
(``finalize_result``), each pass's zero accumulators (``stats_init_fn``)
and the per-chunk updates (``jit_update_fn``).

A second fit at the same shapes must trace and compile nothing, and the
compiled boundaries must compute what their eager bodies compute.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rcca import (RCCAConfig, finalize_result, jit_update_fn,
                             power_update_Q, randomized_cca_streaming,
                             stats_init_fn)
from repro.data import PlantedCCAData
from repro.exec import PassEngine, StackedChunks

N, DA, DB, CHUNK = 512, 24, 16, 64
CFG = RCCAConfig(k=3, p=5, q=2, nu=0.01, center=True)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


@pytest.fixture(scope="module")
def chunks():
    data = PlantedCCAData(n=N, da=DA, db=DB, rank=8, noise=2.0, seed=3,
                          chunk=CHUNK)
    A, B = data.materialize()
    return (jnp.asarray(A).reshape(N // CHUNK, CHUNK, DA),
            jnp.asarray(B).reshape(N // CHUNK, CHUNK, DB))


def _programs(engine):
    kt = CFG.sketch
    return {"power_update_Q": power_update_Q, "finalize_result": finalize_result,
            **{f"init_{k}": stats_init_fn(k, DA, DB, kt) for k in ("power", "final")},
            **{f"update_{k}": jit_update_fn(k, engine) for k in ("power", "final")}}


@pytest.mark.parametrize("engine", ["jnp", "kernels"])
def test_second_fit_compiles_nothing(chunks, engine):
    progs = _programs(engine)
    for fn in progs.values():
        fn.clear_cache()
    A, B = chunks
    r1 = randomized_cca_streaming(A, B, CFG, jax.random.PRNGKey(0), engine=engine)
    assert {n: fn._cache_size() for n, fn in progs.items()} == dict.fromkeys(progs, 1)

    events = []

    def listen(event, duration, **_):
        if event in COMPILE_EVENTS:
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        r2 = randomized_cca_streaming(A, B, CFG, jax.random.PRNGKey(1), engine=engine)
        jax.block_until_ready(r2.Xa)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert events == []
    assert {n: fn._cache_size() for n, fn in progs.items()} == dict.fromkeys(progs, 1)
    # the same program on other data: a different answer, not a stale one
    assert not np.array_equal(np.asarray(r1.Qa), np.asarray(r2.Qa))


def _boundary_inputs(chunks):
    """The stats and bases each boundary of a small centered fit sees."""
    seen = {}

    def capture(pass_idx, kind, acc, Qa, Qb):
        seen[pass_idx] = (acc.result(), Qa, Qb)

    A, B = chunks
    PassEngine(CFG, engine="jnp").run(StackedChunks(A, B), jax.random.PRNGKey(0),
                                      on_pass_complete=capture)
    return seen


def test_compiled_boundaries_match_eager(chunks):
    """Compiled code fuses differently, so the two agree to f32 round-off
    (1e-5 is ~80 ulps of 1), not bit for bit.  A Q update is compared by
    the subspace its basis spans: within a cluster of the Gram's
    eigenvalues the eigh may rotate the basis by far more than that."""
    seen = _boundary_inputs(chunks)
    for pass_idx in range(CFG.q):
        got = power_update_Q(*seen[pass_idx], CFG)
        with jax.disable_jit():
            want = power_update_Q(*seen[pass_idx], CFG)
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            np.testing.assert_allclose(g @ g.T, w @ w.T, atol=1e-5)
            np.testing.assert_allclose(g.T @ g, np.eye(CFG.sketch), atol=1e-5)

    fstats, Qa, Qb = seen[CFG.q]
    got = finalize_result(fstats, Qa, Qb, CFG, DA, DB)
    with jax.disable_jit():
        want = finalize_result(fstats, Qa, Qb, CFG, DA, DB)
    for name in ("rho", "Xa", "Xb"):
        np.testing.assert_allclose(np.asarray(getattr(got, name)),
                                   np.asarray(getattr(want, name)),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
    assert got.diagnostics.keys() == want.diagnostics.keys()


def test_each_boundary_is_one_program(chunks):
    """The Q update and the finish trace to a single jit call each, so
    the host dispatches one program per boundary."""
    seen = _boundary_inputs(chunks)
    calls = {
        "power_update_Q": (functools.partial(power_update_Q, cfg=CFG), *seen[0]),
        "finalize_result": (functools.partial(finalize_result, cfg=CFG, da=DA, db=DB),
                            *seen[CFG.q]),
        "init_power": (stats_init_fn("power", DA, DB, CFG.sketch),),
    }
    for name, (fn, *args) in calls.items():
        eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
        assert [e.primitive.name for e in eqns] in (["pjit"], ["jit"]), name
