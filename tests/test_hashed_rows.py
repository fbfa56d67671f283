"""Hashed rows on the wire: ``HashingFeaturizer.featurize_batch`` returns
``HashedRows`` (token slots and signs), ``on_device`` copies those, and
the chunk update builds the dense rows inside its own program.

- the dense rows a ``HashedRows`` stands for are bitwise the rows the
  featurizer built densely, pads, repeated tokens and cancelling signs
  included, and every dense user sees the dense array;
- a fit fed ``HashedRows`` is bitwise the fit fed the dense rows, under
  both engines and with a seeded first pass;
- for dense chunks the update is the same program as before, under the
  same name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.rcca import (RCCAConfig, jit_update_fn, randomized_cca_iterator,
                             stats_init_fn, update_fn, update_final_stats,
                             update_final_stats_kernel, update_power_stats,
                             update_power_stats_kernel)
from repro.data import HashingFeaturizer
from repro.data.hashing import HashedRows
from repro.exec.engine import on_device

D = 16


def _dense_reference(f: HashingFeaturizer, token_mat: np.ndarray) -> np.ndarray:
    """The featurizer's dense rows, built as it built them before it
    returned slots: each valid token adds its sign at its slot."""
    n, L = token_mat.shape
    out = np.zeros((n, f.n_slots), np.float32)
    valid = token_mat > 0
    rows = np.repeat(np.arange(n), L)[valid.ravel()]
    toks = token_mat.ravel()[valid.ravel()]
    np.add.at(out, (rows, f.slots(toks)), f.signs(toks))
    return out


def _cancelling_pair(f: HashingFeaturizer) -> tuple:
    """Two token ids that share a slot with opposite signs."""
    toks = np.arange(1, 200)
    slots, signs = f.slots(toks), f.signs(toks)
    for i in range(len(toks)):
        for j in range(i + 1, len(toks)):
            if slots[i] == slots[j] and signs[i] == -signs[j]:
                return int(toks[i]), int(toks[j])
    raise AssertionError("no cancelling pair among the first 199 tokens")


@pytest.fixture
def batch():
    f = HashingFeaturizer(D, seed=7)
    t1, t2 = _cancelling_pair(f)
    m = np.random.default_rng(0).integers(1, 60, size=(6, 10))
    m[1, 4:] = 0              # padding
    m[2, :] = 0               # an empty row
    m[3, :5] = 9              # one token five times
    m[4, :2] = (t1, t2)       # two tokens that cancel
    m[4, 2:] = 0
    m[5, 0], m[5, 9] = t1, t1
    return f, m


def test_dense_rows_are_bitwise_the_featurizers(batch):
    f, m = batch
    hr = f.featurize_batch(m)
    want = _dense_reference(f, m)
    got = np.asarray(hr)
    assert isinstance(hr, HashedRows) and got.dtype == np.float32
    assert got.tobytes() == want.tobytes()
    assert not np.any(got[4])  # the pair cancelled to +0.0
    assert np.array_equal(got, f.featurize([r[r > 0] for r in m]))
    # on the device: the scatter the chunk update runs
    assert np.asarray(jax.jit(HashedRows.to_dense)(hr)).tobytes() == want.tobytes()
    assert hr.slots.dtype == np.int32 and hr.signs.dtype == np.float32
    assert np.all(hr.signs[m == 0] == 0.0)


def test_featurize_span_counts_nonzeros_after_cancelling(batch, tmp_path, monkeypatch):
    f, m = batch
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv("RCCA_TRACE", trace_dir)
    f.featurize_batch(m)
    monkeypatch.delenv("RCCA_TRACE")
    (sp,) = [e for e in obs.load_events(trace_dir)
             if e.get("ev") == "span" and e["name"] == "featurize"]
    want = _dense_reference(f, m)
    assert sp["attrs"] == {"rows": 6, "bytes": 6 * D * 4,
                           "nnz": int(np.count_nonzero(want))}


@pytest.mark.parametrize("idx", [
    0, 3, -1, slice(1, 4), slice(None, None, 2), np.array([4, 0, 4]),
    np.array([True, False, True, False, True, True]), (2, 5), (slice(None), 3),
    (slice(1, 3), slice(2, 9)), Ellipsis, None,
], ids=lambda i: repr(i)[:24])
def test_indexing_is_the_dense_arrays(batch, idx):
    f, m = batch
    hr, dense = f.featurize_batch(m), _dense_reference(f, m)
    got, want = hr[idx], dense[idx]
    assert np.shape(got) == np.shape(want)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_array_protocol_behaves_as_the_dense_array(batch):
    f, m = batch
    hr, dense = f.featurize_batch(m), _dense_reference(f, m)
    assert hr.shape == dense.shape == (6, D)
    assert (hr.dtype, hr.ndim, len(hr)) == (dense.dtype, 2, 6)
    rows = list(hr)
    assert len(rows) == 6 and all(np.array_equal(r, w) for r, w in zip(rows, dense))
    assert np.array_equal(np.stack([hr, hr]), np.stack([dense, dense]))
    j = jnp.asarray(hr)
    assert isinstance(j, jax.Array) and j.dtype == jnp.float32
    assert np.array_equal(np.asarray(j), dense)
    assert np.array_equal(np.asarray(hr, dtype=np.float64), dense.astype(np.float64))


def test_h2d_copies_slots_and_counts_the_dense_bytes(batch, tmp_path, monkeypatch):
    f, m = batch
    hr = f.featurize_batch(m)
    dense = np.asarray(hr)
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv("RCCA_TRACE", trace_dir)
    a, b = on_device(hr, hr)
    da, db = on_device(dense, dense)
    monkeypatch.delenv("RCCA_TRACE")
    assert isinstance(a, HashedRows) and isinstance(a.slots, jax.Array)
    assert on_device(a, b) == (a, b)  # already on the device: no copy
    assert isinstance(da, jax.Array)
    h2d = [e["attrs"] for e in obs.load_events(trace_dir)
           if e.get("ev") == "span" and e["name"] == "h2d"]
    assert h2d == [
        {"bytes": 2 * 6 * D * 4, "wire_bytes": 2 * (hr.slots.nbytes + hr.signs.nbytes),
         "form": "hashed"},
        {"bytes": 2 * 6 * D * 4, "wire_bytes": 2 * 6 * D * 4, "form": "dense"}]
    from repro.obs.report import analyze

    assert analyze(trace_dir)["h2d"] == {
        "dense": {"copies": 1, "bytes": 2 * 6 * D * 4, "wire_bytes": 2 * 6 * D * 4},
        "hashed": {"copies": 1, "bytes": 2 * 6 * D * 4,
                   "wire_bytes": 2 * (hr.slots.nbytes + hr.signs.nbytes)}}


# -- fits


def _fit(docs, d, engine, omega, dense):
    fa, fb = HashingFeaturizer(d, seed=11), HashingFeaturizer(d, seed=12)

    def source():
        for i in range(docs.shape[1]):
            a, b = fa.featurize_batch(docs[0, i]), fb.featurize_batch(docs[1, i])
            yield (np.asarray(a), np.asarray(b)) if dense else (a, b)

    cfg = RCCAConfig(k=3, p=5, q=1, nu=0.01, center=True)
    return randomized_cca_iterator(source, d, d, cfg, jax.random.PRNGKey(3),
                                   n_chunks=docs.shape[1], engine=engine,
                                   merge_group=2, omega=omega)


@pytest.mark.parametrize("engine,omega", [("jnp", "materialized"),
                                          ("kernels", "materialized"),
                                          ("kernels", "seeded")])
def test_fit_fed_hashed_rows_is_bitwise_the_dense_fit(engine, omega):
    docs = np.random.default_rng(1).integers(0, 400, size=(2, 5, 24, 8))
    hashed = _fit(docs, 128, engine, omega, dense=False)
    dense = _fit(docs, 128, engine, omega, dense=True)
    for name in ("rho", "Xa", "Xb"):
        got, want = np.asarray(getattr(hashed, name)), np.asarray(getattr(dense, name))
        assert got.tobytes() == want.tobytes(), name


# -- the update program


_RAW = {("power", "jnp"): update_power_stats, ("power", "kernels"): update_power_stats_kernel,
        ("final", "jnp"): update_final_stats, ("final", "kernels"): update_final_stats_kernel}


def _args(kind, hashed):
    kt, n = 8, 16
    rng = np.random.default_rng(2)
    Q = jnp.asarray(rng.standard_normal((D, kt)), jnp.float32)
    if hashed:
        slots = rng.integers(0, D, size=(n, 5)).astype(np.int32)
        signs = rng.choice(np.float32([-1, 0, 1]), size=(n, 5))
        a = b = HashedRows(slots, signs, D)
    else:
        a = b = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    return stats_init_fn(kind, D, D, kt)(), a, b, Q, Q


@pytest.mark.parametrize("kind,engine", list(_RAW))
def test_dense_chunks_lower_to_the_unwrapped_program(kind, engine):
    args = _args(kind, hashed=False)
    wrapped = jax.jit(update_fn(kind, engine)).lower(*args).as_text()
    assert wrapped == jax.jit(_RAW[kind, engine]).lower(*args).as_text()
    assert update_fn(kind, engine) is update_fn(kind, engine)
    for hashed in (False, True):
        lowered = jit_update_fn(kind, engine).lower(*_args(kind, hashed))
        name = lowered.compile().as_text().split("HloModule ", 1)[1].split(",")[0]
        assert name.startswith(f"jit_update_{kind}_stats"), name
