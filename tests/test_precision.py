"""Every f32 matmul of the fit and serving paths runs at full f32 precision.

A TPU computes an f32 matmul as one bf16 pass unless the program asks
for more (~4e-3 relative error, which the fit's whitening amplifies past
its tolerances).  A CPU ignores the request, so these tests read it from
the traced programs: every ``dot_general`` over two f32 operands, Pallas
kernel bodies included, must carry ``Precision.HIGHEST``; a kernel dot
over bf16 operands must not (Mosaic refuses fp32 precision on them).
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from repro.core import exact_cca, randomized_cca, streamed_feasibility_errors
from repro.core.rcca import (RCCAConfig, finalize_result, init_Q,
                             omega_seeds, power_update_Q, seeded_update_fn,
                             stats_init_fn, update_fn)
from repro.kernels import ref
from repro.kernels.compat import sub_jaxprs
from repro.serve import ServedModel
from repro.serve.projector import _project_jit

HIGHEST = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
ROWS, D, KT = 128, 256, 128
CFG = RCCAConfig(k=8, p=KT - 8, q=1, nu=0.01)
F32 = jnp.float32


def _dots(jaxpr):
    """(precision, operand dtypes) of every dot_general, kernel bodies
    and other nested jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield (eqn.params["precision"],
                   tuple(v.aval.dtype for v in eqn.invars))
        for val in eqn.params.values():
            for sub in sub_jaxprs(val):
                yield from _dots(sub)


def _rows(dtype=F32):
    return jnp.ones((ROWS, D), dtype)


def _update(kind, engine, dtype=F32):
    Qa, Qb = init_Q(jax.random.PRNGKey(0), D, D, CFG)
    return (update_fn(kind, engine), stats_init_fn(kind, D, D, KT)(),
            _rows(dtype), _rows(dtype), Qa.astype(dtype), Qb.astype(dtype))


def _seeded(kind):
    sa, sb = omega_seeds(jax.random.PRNGKey(0))
    return (seeded_update_fn(kind, KT, F32), stats_init_fn(kind, D, D, KT)(),
            _rows(), _rows(), sa, sb)


def _q_update():
    Qa, Qb = init_Q(jax.random.PRNGKey(0), D, D, CFG)
    stats = stats_init_fn("power", D, D, KT)()
    stats = stats._replace(Ya=Qa, Yb=Qb, n=jnp.float32(ROWS))
    return (functools.partial(power_update_Q, cfg=CFG), stats, Qa, Qb)


def _finalize():
    Qa, Qb = init_Q(jax.random.PRNGKey(0), D, D, CFG)
    eye = jnp.eye(KT, dtype=F32)
    stats = stats_init_fn("final", D, D, KT)()
    stats = stats._replace(Ca=eye, Cb=eye, F=eye, n=jnp.float32(ROWS),
                           tr_a=jnp.float32(1), tr_b=jnp.float32(1))
    return (functools.partial(finalize_result, cfg=CFG, da=D, db=D),
            stats, Qa, Qb)


def _feasibility():
    X = jnp.ones((D, 8), F32)
    return (lambda a, b, Xa, Xb: streamed_feasibility_errors([(a, b)], Xa, Xb),
            _rows(), _rows(), X, X)


PROGRAMS = {
    "power_jnp": lambda: _update("power", "jnp"),
    "final_jnp": lambda: _update("final", "jnp"),
    "power_kernels": lambda: _update("power", "kernels"),
    "final_kernels": lambda: _update("final", "kernels"),
    "power_seeded": lambda: _seeded("power"),
    "final_seeded": lambda: _seeded("final"),
    "q_update": _q_update,
    "finalize": _finalize,
    "randomized_cca": lambda: (
        lambda A, B, key: randomized_cca(A, B, CFG, key), _rows(), _rows(),
        jax.random.PRNGKey(0)),
    "exact_oracle": lambda: (
        functools.partial(exact_cca, k=8, lam_a=1.0, lam_b=1.0),
        jnp.ones((ROWS, 32), F32), jnp.ones((ROWS, 32), F32)),
    "feasibility": _feasibility,
    "power_ref": lambda: (ref.power_pass_ref,) + _update("power", "jnp")[2:],
    "final_ref": lambda: (ref.final_pass_ref,) + _update("final", "jnp")[2:],
    "serve_projection": lambda: (
        _project_jit(D, 8, 4), jnp.ones((D, 8), F32), jnp.ones((4, D), F32)),
    "served_model": lambda: (
        ServedModel("m", 1, *(jnp.ones((D, 8), F32),) * 2, jnp.ones(8, F32),
                    *(jnp.ones((D, KT), F32),) * 2, {}).project_b,
        jnp.ones((4, D), F32)),
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_f32_matmuls_at_full_precision(name):
    fn, *args = PROGRAMS[name]()
    dots = list(_dots(jax.make_jaxpr(fn)(*args).jaxpr))
    f32_dots = [prec for prec, dts in dots if all(dt == F32 for dt in dts)]
    assert f32_dots, f"{name}: no f32 dot_general traced"
    assert all(prec == HIGHEST for prec in f32_dots), (name, f32_dots)


@pytest.mark.parametrize("kind", ["power", "final"])
def test_bf16_kernel_dots_keep_one_pass(kind):
    """bf16 chunk updates: the kernel dots over two bf16 operands ask for
    the default single pass, even inside the full-f32 scope."""
    fn, *args = _update(kind, "kernels", jnp.bfloat16)
    dots = list(_dots(jax.make_jaxpr(fn)(*args).jaxpr))
    bf16 = [prec for prec, dts in dots
            if all(dt == jnp.bfloat16 for dt in dts)]
    assert bf16, "no bf16 kernel dot traced"
    assert all(prec != HIGHEST for prec in bf16), bf16
