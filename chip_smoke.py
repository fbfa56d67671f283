#!/usr/bin/env python3
"""Chip run of the streaming RandomizedCCA fit: proof that the main path
runs on a TPU, through the entry points a user calls.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # four chips: the two multi-device paths

One chip, all in this one process (a TPU serves one process at a time):

(a) the ``cca_fit --smoke`` configuration through
    ``repro.launch.cca_fit.main``, in stream and in dist mode, against
    the exact dense oracle (``repro.core.exact``);
(b) the paper's workload (``configs/europarl_cca.py`` at p = 910:
    k = 60, k̃ = 970, q = 1, ν = 0.01, uncentered) on hashed
    bag-of-words rows, streamed through ``randomized_cca_iterator`` with
    the Local fold, ``engine="kernels"`` and ``omega="materialized"``:
    the first power and final chunk updates against ``kernels/ref.py``,
    the whole fit against the same fit with ``engine="jnp"``, and its
    feasibility residuals accumulated chunk by chunk;
(c) that fit published to a ``ModelRegistry`` and served by a
    ``BatchedProjector``, each response against ``x @ Xa``.

With ``--chips 4`` only the two paths that exist across chips run, each
against its reference: the ``Sharded`` group-parallel fold against
``Local`` on one view store (bitwise), and ``dist_randomized_cca``'s
collective-fused feature sharding against its unfused form on the
hashed corpus (ρ, and the canonical subspaces wherever the spectrum
determines them, see ``subspace_sines``), both at k̃ = 256 (see
``FOUR_CHIP_P``).

Each phase prints one JSON object ("chip run" records, not benchmark
numbers).  The last line is ``{"ok": true, "device": {...}}``.  Any
failure, a platform other than TPU included, exits non-zero before it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, compiled programs are cached
there; otherwise in ``.jax_cache/`` of the checkout.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# The paper's Europarl run (configs/europarl_cca.py) and the cuts that
# fit it to one 16 GB chip and to one run.
PUBLISHED = {"d": 1 << 19, "chunk": 8192, "n": 1_235_976}
D1 = {"d": 1 << 18, "chunk": 512, "n_chunks": 16}
CUTS = [
    {"what": "d", "published": PUBLISHED["d"], "here": D1["d"],
     "why": "at d = 2^19 and k~ = 970 the compiled chunk update alone "
            "holds 14.6 GB and Ya, Yb add 4.1 GB: 18.7 GB > 16 GB of HBM"},
    {"what": "chunk rows", "published": PUBLISHED["chunk"],
     "here": D1["chunk"],
     "why": "one dense f32 chunk of 8192 rows is 17 GB per view at "
            "d = 2^19; a 512-row chunk is 0.5 GB per view at d = 2^18"},
    {"what": "n", "published": PUBLISHED["n"],
     "here": D1["chunk"] * D1["n_chunks"],
     "why": "16 chunks: two merge groups of the canonical pairwise tree, "
            "inside one run's time limit"},
]

# Tolerances.  The program runs its matmuls at full f32 precision
# (``repro.core.linalg.full_f32``, ``repro.kernels.matmul.mxu_dot``), so
# every comparison is of f32 sums taken in different orders.
#: |exact Σρ − fit Σρ| on the smoke corpus: randomized CCA with p = 24
#: oversampling and one power pass reaches the optimum to well under
#: 1% of Σρ ≈ 7.9 (the driver's documented healthy gap).
GAP_TOL = 0.01
#: max |Xᵀ(AᵀA + λI)X / n − I| and max off-diagonal cross-covariance on
#: the smoke corpus (k̃ = 32, well conditioned): f32 Grams over n rows
#: relative to O(1) entries; a healthy fit sits near 1e-6.
FEAS_TOL = 1e-4
#: the same residuals at full width.  The whitening solves against
#: Ca + λQᵀQ, which is ill conditioned on hashed Zipf rows (one head
#: token sits in most rows), and amplifies f32 round-off ~1e3-fold: at
#: d = 2^15 on a CPU, f32 throughout, cov_a = 1.8e-4.
FULL_FEAS_TOL = 1e-3
#: normwise relative error of a chunk update against kernels/ref.py:
#: f32 accumulation of at most a few thousand products per entry gives
#: ~1e-6; one bf16 pass (the TPU default) would give ~4e-3.
CHUNK_REL_TOL = 1e-5
#: max |ρ_kernels − ρ_jnp| over the top k at full width: the two engines
#: differ only in f32 summation order, and the whitening amplifies that
#: the same way (9.8e-5 at d = 2^15 on a CPU).
RHO_TOL = 1e-3
#: normwise relative error of a served embedding against x @ Xa: a
#: hashed row has ~30 nonzeros, so each entry is a short f32 sum.
SERVE_REL_TOL = 1e-5
#: fused vs unfused collectives: the ρ tolerance of
#: tests/test_collective_fused.py.
FUSED_RHO = {"rtol": 1e-4, "atol": 1e-5}
#: the perturbation η of the whitened cross-covariance the subspace check
#: allows: FUSED_RHO at ρ ≈ 1, the most a perturbation that size could
#: move any ρ (Weyl).  By Wedin's sinθ theorem it turns the span of the
#: top i canonical directions by sinθ_i ≤ η / (ρ_i − ρ_{i+1} − η); see
#: ``subspace_sines``.
SUBSPACE_ETA = FUSED_RHO["atol"] + FUSED_RHO["rtol"]


class ChipRunError(RuntimeError):
    """A comparison outside its tolerance, or a path that did not run on
    the chip."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise ChipRunError(what)


def emit(record: dict) -> None:
    print(json.dumps(record, default=float), flush=True)


def _import_repo() -> None:
    """Put the checkout's ``src`` on the path; fail outside a checkout."""
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chip_smoke.py: no repro package under {src}; "
                         "run it from a checkout of the repository")
    sys.path.insert(0, src)


def _device() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _peak_bytes() -> int | None:
    """The largest ``peak_bytes_in_use`` over the devices."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def require_mosaic(compiled, what: str) -> None:
    """The chunk update lowered to a Mosaic kernel: no interpreted
    kernel, no jnp stand-in, no CPU device."""
    check("tpu_custom_call" in compiled.as_text(),
          f"{what}: no tpu_custom_call in the compiled program")


def _rel(got, want) -> float:
    import jax.numpy as jnp

    return float(jnp.linalg.norm(got - want) /
                 jnp.maximum(jnp.linalg.norm(want), 1e-30))


def d1_config():
    from repro.configs.europarl_cca import config

    return dataclasses.replace(config().rcca, p=910)


def hashed_chunks(seed: int, d: int, chunk: int, n_chunks: int):
    """``host(i)`` → chunk i of the hashed paired corpus as two dense
    (chunk, d) f32 numpy arrays: ~30 Zipf(1.3) tokens per row from
    ``synth_paired_docs``, hashed by ``HashingFeaturizer`` into d slots."""
    import numpy as np

    from repro.data import HashingFeaturizer, synth_paired_docs

    docs_a, docs_b = synth_paired_docs(chunk * n_chunks, seed=seed)
    ha = HashingFeaturizer(d, seed=seed + 1)
    hb = HashingFeaturizer(d, seed=seed + 2)

    def host(i: int):
        rows = slice(i * chunk, (i + 1) * chunk)
        return (np.asarray(ha.featurize_batch(docs_a[rows])),
                np.asarray(hb.featurize_batch(docs_b[rows])))

    return host


class PassClock:
    """Chunk source for ``randomized_cca_iterator`` that times each data
    pass: from the pass opening its source to its last chunk's fold
    finishing on the device.  Featurizing and the host→device copy of
    every chunk are inside the pass, as in a deployment."""

    def __init__(self, host, n_chunks: int):
        self.host, self.n_chunks = host, n_chunks
        self.seconds: list = []
        self._t0 = 0.0

    def get(self, i: int):
        import jax

        return tuple(jax.device_put(x) for x in self.host(i))

    def source(self):
        self._t0 = time.perf_counter()
        return (self.get(i) for i in range(self.n_chunks))

    def on_chunk(self, pass_idx, chunk_idx, acc, Qa, Qb) -> None:
        import jax

        if chunk_idx == self.n_chunks - 1:
            jax.block_until_ready(acc.state())
            self.seconds.append(time.perf_counter() - self._t0)


# --------------------------------------------------------------------------
# (a) smoke-size fit against the exact oracle
# --------------------------------------------------------------------------


def phase_smoke() -> None:
    import jax

    from repro.configs.europarl_cca import smoke_config
    from repro.core.rcca import jit_update_fn, stats_init_fn
    from repro.launch import cca_fit

    for mode in ("stream", "dist"):
        t0 = time.perf_counter()
        rep = cca_fit.main(["--smoke", "--mode", mode])
        wall = time.perf_counter() - t0
        feas = rep["feasibility"]
        emit({"chip_run": f"a_smoke_{mode}", "wall_s_with_compile": wall,
              "sum_rho": rep["sum_rho"], "oracle_gap": rep["oracle_gap"],
              "gap_tol": GAP_TOL, "feasibility": feas, "feas_tol": FEAS_TOL,
              "peak_bytes_in_use": _peak_bytes()})
        check(abs(rep["oracle_gap"]) < GAP_TOL,
              f"smoke {mode}: oracle gap {rep['oracle_gap']}")
        check(max(feas.values()) < FEAS_TOL,
              f"smoke {mode}: feasibility {feas}")

    # the stream mode's jitted chunk updates ran as Mosaic kernels
    wl = smoke_config()
    kt = wl.rcca.sketch
    f32 = jax.numpy.float32
    args = (jax.ShapeDtypeStruct((wl.chunk, wl.da), f32),
            jax.ShapeDtypeStruct((wl.chunk, wl.db), f32),
            jax.ShapeDtypeStruct((wl.da, kt), f32),
            jax.ShapeDtypeStruct((wl.db, kt), f32))
    for kind in ("power", "final"):
        stats = jax.eval_shape(stats_init_fn(kind, wl.da, wl.db, kt))
        require_mosaic(jit_update_fn(kind, "kernels").lower(stats, *args)
                       .compile(), f"smoke {kind} update")


# --------------------------------------------------------------------------
# (b) full-width fit against references
# --------------------------------------------------------------------------


def phase_full_width(seed: int):
    """Returns the kernels fit (for serving) and the chunk source."""
    import jax
    import numpy as np

    from repro.core import streamed_feasibility_errors
    from repro.core.rcca import (init_Q, jit_update_fn,
                                 randomized_cca_iterator, stats_init_fn)
    from repro.kernels import ops, ref

    cfg = d1_config()
    d, chunk, n_chunks = D1["d"], D1["chunk"], D1["n_chunks"]
    kt = cfg.sketch
    key = jax.random.PRNGKey(seed)
    host = hashed_chunks(seed, d, chunk, n_chunks)
    clock = PassClock(host, n_chunks)
    emit({"chip_run": "b_sizes", "config": "europarl-cca D1",
          "k": cfg.k, "p": cfg.p, "kt": kt, "q": cfg.q, "nu": cfg.nu,
          "center": cfg.center, "d": d, "chunk": chunk,
          "n": chunk * n_chunks, "cuts": CUTS,
          "schedule": {kind: ops.chunk_cost(kind, chunk, d, d, kt)["schedule"]
                       for kind in ("power", "final")}})

    # -- the first power and final chunk updates against kernels/ref.py
    a, b = clock.get(0)
    Qa, Qb = init_Q(key, d, d, cfg)
    compile_s, memory, err = {}, {}, {}
    refs = {"power": jax.jit(ref.power_pass_ref),
            "final": jax.jit(ref.final_pass_ref)}
    fields = {"power": ("Ya", "Yb"), "final": ("Ca", "Cb", "F")}
    for kind in ("power", "final"):
        stats = stats_init_fn(kind, d, d, kt)()
        t0 = time.perf_counter()
        compiled = jit_update_fn(kind, "kernels").lower(
            stats, a, b, Qa, Qb).compile()
        compile_s[kind] = time.perf_counter() - t0
        require_mosaic(compiled, f"full-width {kind} update")
        m = compiled.memory_analysis()
        memory[kind] = {"argument": m.argument_size_in_bytes,
                        "output": m.output_size_in_bytes,
                        "temp": m.temp_size_in_bytes}
        got = compiled(stats, a, b, Qa, Qb)
        want = refs[kind](a, b, Qa, Qb)
        for name, w in zip(fields[kind], want):
            err[name] = _rel(getattr(got, name), w)
        del got, want, stats
    del a, b, Qa, Qb
    emit({"chip_run": "b_chunk_updates", "compile_s": compile_s,
          "compiled_bytes": memory, "rel_err_vs_ref": err,
          "tol": CHUNK_REL_TOL, "peak_bytes_in_use": _peak_bytes()})
    check(max(err.values()) < CHUNK_REL_TOL, f"chunk updates vs ref: {err}")

    # -- the whole fit, kernels against jnp.  The first kernels fit
    # compiles (the k̃ × k̃ factorizations take minutes); the second is
    # timed warm and must repeat the first bit for bit.
    fits, pass_s = {}, {}
    for run in ("kernels_cold", "kernels", "jnp"):
        clock.seconds = []
        t0 = time.perf_counter()
        res = randomized_cca_iterator(
            clock.source, d, d, cfg, key, on_pass_end=clock.on_chunk,
            engine=run.split("_")[0], omega="materialized",
            n_chunks=n_chunks)
        jax.block_until_ready(res.Xa)
        pass_s[run] = {"passes": list(clock.seconds),
                       "fit_wall_s": time.perf_counter() - t0}
        # the bases go to the host: two (d, k̃) arrays would otherwise
        # sit in HBM beside the next fit
        fits[run] = res._replace(Qa=np.asarray(res.Qa),
                                 Qb=np.asarray(res.Qb))
        del res
    repeat = all(np.array_equal(np.asarray(getattr(fits["kernels"], leaf)),
                                np.asarray(getattr(fits["kernels_cold"], leaf)))
                 for leaf in ("Xa", "Xb", "rho", "Qa", "Qb"))
    rho_k = np.asarray(fits["kernels"].rho)
    rho_j = np.asarray(fits["jnp"].rho)
    rho_diff = float(np.max(np.abs(rho_k - rho_j)))

    # -- feasibility of the kernels fit, accumulated chunk by chunk
    res = fits["kernels"]
    lam_a = float(res.diagnostics["lam_a"])
    lam_b = float(res.diagnostics["lam_b"])
    feas = streamed_feasibility_errors(
        (clock.get(i) for i in range(n_chunks)), res.Xa, res.Xb, lam_a, lam_b)
    feas = {k: float(v) for k, v in feas.items()}
    emit({"chip_run": "b_fit", "pass_wall_s": pass_s,
          "kernels_repeat_bitwise": repeat,
          "sum_rho": {"kernels": float(rho_k.sum()),
                      "jnp": float(rho_j.sum())},
          "top5_rho": rho_k[:5].tolist(),
          "max_rho_diff": rho_diff, "rho_tol": RHO_TOL,
          "lam": [lam_a, lam_b], "feasibility": feas,
          "feas_tol": FULL_FEAS_TOL, "peak_bytes_in_use": _peak_bytes()})
    check(repeat, "two kernels fits of one corpus differ")
    check(rho_diff < RHO_TOL, f"kernels vs jnp rho differ by {rho_diff}")
    check(max(feas.values()) < FULL_FEAS_TOL, f"full-width feasibility {feas}")
    return res, host


# --------------------------------------------------------------------------
# (c) serving the published fit
# --------------------------------------------------------------------------


def phase_serve(res, host) -> None:
    import numpy as np

    from repro.core.rcca import algo_meta
    from repro.serve import BatchedProjector, ModelRegistry

    cfg = d1_config()
    a0, b0 = host(0)
    rows = [("a", a0[i]) for i in range(6)] + [("b", b0[i]) for i in range(6)]
    with tempfile.TemporaryDirectory() as root:
        reg = ModelRegistry(root)
        version = reg.publish("europarl-d1", res, fit_meta={
            "engine": "kernels", "omega": "materialized",
            "algo": algo_meta(cfg), "n": D1["chunk"] * D1["n_chunks"]})
        model = reg.load("europarl-d1")
        X = {"a": np.asarray(model.Xa, np.float64),
             "b": np.asarray(model.Xb, np.float64)}
        t0 = time.perf_counter()
        with BatchedProjector(model, max_batch=8) as proj:
            tickets = [proj.submit(view, x) for view, x in rows]
            answers = [t.result(timeout=300) for t in tickets]
        wall = time.perf_counter() - t0
        stats = proj.stats()
    errs, versions = [], []
    for (view, x), ans in zip(rows, answers):
        want = x.astype(np.float64) @ X[view]
        errs.append(float(np.linalg.norm(ans["emb"] - want) /
                          max(np.linalg.norm(want), 1e-30)))
        versions.append(ans["version"])
    emit({"chip_run": "c_serve", "published_version": version,
          "requests": len(rows), "versions_seen": sorted(set(versions)),
          "batches": stats["batches"],
          "mean_occupancy": stats["mean_occupancy"],
          "wall_s_with_compile": wall, "max_rel_err": max(errs),
          "tol": SERVE_REL_TOL, "peak_bytes_in_use": _peak_bytes()})
    check(versions == [version] * len(rows),
          f"responses stamped {versions}, published v{version}")
    check(max(errs) < SERVE_REL_TOL, f"served embeddings vs x @ X: {errs}")


# --------------------------------------------------------------------------
# four chips: Sharded vs Local, fused vs unfused
# --------------------------------------------------------------------------

#: Sharded vs Local on one view store: 4 merge groups, one per chip.  The
#: store is dense f32 on disk: at d = 2^18 these 32 chunks would be 34 GB.
SHARDED = {"d": 1 << 14, "chunk": 512}
#: resident-mode feature sharding on a 2 (rows) × 2 (features) mesh: all
#: n rows live on the mesh, 1.07 GB per view per chip at d = 2^18.
FUSED = {"d": 1 << 18, "n": 4096, "microbatch": 512}
#: Both four-chip paths run at k̃ = 256 (p = 196).  Each of their programs
#: holds k̃ × k̃ eigh/SVD factorizations, which the TPU compiler takes
#: ~70-110 s each to build at k̃ = 970 and ~2 s at 256 (a described
#: v5e:2x2 compile); the data-pass kernels and collectives are the same
#: code at either width.
FOUR_CHIP_P = 196
SKETCH_CUT = {"what": "k~", "published": 970, "here": 60 + FOUR_CHIP_P,
              "why": "k~ x k~ eigh/SVD compile ~100 s each at 970 and ~2 s "
                     "at 256, in every program of both paths"}


def four_chip_config():
    return dataclasses.replace(d1_config(), p=FOUR_CHIP_P)


def whitened_gram(A, W, lam: float):
    """Gram of the columns of W in the metric the fit whitens in,
    ⟨x, y⟩ = (xᵀAᵀAy + λxᵀy) / n, in f64.  A holds small integer counts,
    so a sparse f64 copy forms it exactly enough to resolve angles and
    residuals far under every tolerance here."""
    import numpy as np
    import scipy.sparse as sp

    W = W.astype(np.float64)
    PW = sp.csr_matrix(A) @ W
    return (PW.T @ PW + lam * (W.T @ W)) / A.shape[0]


def subspace_sines(M, k: int):
    """sinθ_i of the largest principal angle between the spans of the
    first i of F's and of U's k columns, i = 1 … k, given the whitened
    Gram ``M`` of [F U].

    Entries of X are not a fair comparison on the hashed corpus: its
    neighbouring ρ lie as close as ~1e-4, and a change of summation
    order turns each direction by ~round-off / gap.  The span of the top
    i directions is determined wherever ρ_i − ρ_{i+1} is large, whatever
    happens inside the clusters."""
    import numpy as np

    sines = []
    for i in range(1, k + 1):
        f, u = np.arange(i), k + np.arange(i)
        Mff, Mfu, Muu = M[np.ix_(f, f)], M[np.ix_(f, u)], M[np.ix_(u, u)]
        # the part of F's span outside U's, against F's own Gram
        S = Mff - Mfu @ np.linalg.solve(Muu, Mfu.T)
        Li = np.linalg.inv(np.linalg.cholesky(Mff))
        s2 = float(np.max(np.linalg.eigvalsh(Li @ S @ Li.T)))
        sines.append(max(s2, 0.0) ** 0.5)
    return np.asarray(sines)


def phase_sharded_vs_local(seed: int) -> None:
    import jax
    import numpy as np

    from repro.exec import MERGE_GROUP_CHUNKS, Local, Sharded, fit
    from repro.store import ingest_chunks

    cfg = four_chip_config()
    d, chunk = SHARDED["d"], SHARDED["chunk"]
    n_chunks = 4 * MERGE_GROUP_CHUNKS
    host = hashed_chunks(seed, d, chunk, n_chunks)
    key = jax.random.PRNGKey(seed)
    wall = {}
    with tempfile.TemporaryDirectory() as tmp:
        reader = ingest_chunks(os.path.join(tmp, "store"),
                               (host(i) for i in range(n_chunks)), chunk=chunk)
        out = {}
        for name, topo in (("local", Local()), ("sharded", Sharded())):
            t0 = time.perf_counter()
            out[name] = fit(reader, cfg, key, topology=topo)
            jax.block_until_ready(out[name].Xa)
            wall[name] = time.perf_counter() - t0
    equal = {leaf: bool(np.array_equal(np.asarray(getattr(out["local"], leaf)),
                                       np.asarray(getattr(out["sharded"], leaf))))
             for leaf in ("Xa", "Xb", "rho", "Qa", "Qb")}
    emit({"chip_run": "sharded_vs_local", "d": d, "chunk": chunk,
          "n": chunk * n_chunks, "merge_groups": n_chunks // MERGE_GROUP_CHUNKS,
          "kt": cfg.sketch,
          "cuts": [{"what": "d", "published": PUBLISHED["d"], "here": d,
                    "why": "the view store is dense f32 on disk; 32 chunks "
                           "at d = 2^18 would be 34 GB"}, SKETCH_CUT],
          "wall_s_with_compile": wall, "array_equal": equal,
          "sum_rho": float(np.sum(np.asarray(out["sharded"].rho))),
          "peak_bytes_in_use": _peak_bytes()})
    check(all(equal.values()), f"Sharded differs from Local: {equal}")


def phase_fused_vs_unfused(seed: int, d: int = FUSED["d"],
                           n: int = FUSED["n"]) -> None:
    import jax
    import numpy as np

    from repro.core.rcca_dist import dist_randomized_cca
    from repro.launch.mesh import make_host_mesh

    cfg = four_chip_config()
    A, B = hashed_chunks(seed, d, n, 1)(0)
    mesh = make_host_mesh((2, 2), ("data", "model"))
    key = jax.random.PRNGKey(seed)
    res, wall = {}, {}
    for collective in ("fused", "unfused"):
        t0 = time.perf_counter()
        r = dist_randomized_cca(A, B, cfg, key, mesh, row_axes=("data",),
                                col_axis="model",
                                microbatch=FUSED["microbatch"],
                                engine="kernels", collective=collective)
        res[collective] = {leaf: np.asarray(getattr(r, leaf))
                           for leaf in ("rho", "Xa", "Xb")}
        res[collective]["lam"] = (float(r.diagnostics["lam_a"]),
                                  float(r.diagnostics["lam_b"]))
        wall[collective] = time.perf_counter() - t0
        del r
    f, u = res["fused"], res["unfused"]
    rho_ok = bool(np.allclose(f["rho"], u["rho"], **FUSED_RHO))

    # each fit feasible on the data, and every cut i whose gap
    # ρ_i − ρ_{i+1} exceeds 2η within Wedin's bound
    eta = SUBSPACE_ETA
    rho = u["rho"].astype(np.float64)
    gaps = rho[:-1] - rho[1:]
    cuts = np.flatnonzero(gaps > 2 * eta)
    bound = eta / (gaps[cuts] - eta)
    k, eye = cfg.k, np.eye(cfg.k)
    feas, max_sin, worst = {}, {}, {}
    for i, (view, X, data) in enumerate((("a", "Xa", A), ("b", "Xb", B))):
        M = whitened_gram(data, np.concatenate([f[X], u[X]], axis=1),
                          u["lam"][i])
        feas[view] = {"fused": float(np.max(np.abs(M[:k, :k] - eye))),
                      "unfused": float(np.max(np.abs(M[k:, k:] - eye)))}
        s = subspace_sines(M, k)[cuts]
        max_sin[view] = float(np.max(s)) if cuts.size else None
        worst[view] = float(np.max(s / bound)) if cuts.size else None
    emit({"chip_run": "fused_vs_unfused", "mesh": {"data": 2, "model": 2},
          "d": d, "n": n, "microbatch": FUSED["microbatch"],
          "kt": cfg.sketch, "corpus": "hashed",
          "cuts": [{"what": "n", "published": PUBLISHED["n"], "here": n,
                    "why": "resident mode holds every row on the mesh: "
                           "4096 rows are 4.3 GB per view"},
                   {"what": "d", "published": PUBLISHED["d"], "here": d,
                    "why": "as the one-chip run, so one chip's feature "
                           "shard matches"}, SKETCH_CUT],
          "wall_s_with_compile": wall,
          "max_rho_diff": float(np.max(np.abs(f["rho"] - u["rho"]))),
          "rho_tol": FUSED_RHO,
          "min_rho_gap": float(np.min(gaps)),
          "max_rho_gap": float(np.max(gaps)),
          "lam": u["lam"], "feasibility": feas, "feas_tol": FULL_FEAS_TOL,
          "subspace_eta": eta, "subspace_cuts_checked": int(cuts.size),
          "max_subspace_sin": max_sin, "worst_sin_over_bound": worst,
          "sum_rho": float(np.sum(f["rho"])),
          "peak_bytes_in_use": _peak_bytes()})
    check(rho_ok, "fused vs unfused rho outside tolerance")
    check(max(max(v.values()) for v in feas.values()) < FULL_FEAS_TOL,
          f"fused vs unfused feasibility on the data: {feas}")
    check(cuts.size > 0, f"no gap in the top {cfg.k} rho exceeds 2 eta = "
                         f"{2 * eta}: the subspaces are not determined")
    check(all(w <= 1.0 for w in worst.values()),
          f"fused vs unfused subspaces outside Wedin's bound: {worst}")


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases (a)-(c) on one chip; 4: only the "
                         "multi-chip paths against their references")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus and of the sketch")
    args = ap.parse_args(argv)

    _import_repo()
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    cached_at_start = _cache_entries(cache_dir)
    device = _device()
    if device["platform"] != "tpu":
        raise SystemExit(f"chip_smoke.py: needs a TPU, jax found {device}")
    if device["count"] < args.chips:
        raise SystemExit(f"chip_smoke.py: --chips {args.chips} but jax "
                         f"found {device['count']} device(s)")
    emit({"chip_run": "device", "device": device, "seed": args.seed})

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded_vs_local(args.seed)
        phase_fused_vs_unfused(args.seed)
    else:
        phase_smoke()
        res, host = phase_full_width(args.seed)
        phase_serve(res, host)
    emit({"chip_run": "done", "wall_s": time.perf_counter() - t0,
          "compile_cache": {"dir": cache_dir,
                            "entries_at_start": cached_at_start,
                            "entries_at_end": _cache_entries(cache_dir)}})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
