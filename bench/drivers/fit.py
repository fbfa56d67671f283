"""Whole CCA fits back to back, through the program's streaming entries.

Traffic keys: ``corpus`` is ``hashed_docs`` (paired token bags, hashed by
the program's ``HashingFeaturizer`` on the host for every chunk of every
pass, fed to ``randomized_cca_iterator``) or ``planted_activations``
(dense activations made on the device in set-up, fed to
``randomized_cca_streaming``); the rest are the corpus generator's
parameters.

The window runs fits one after another on the same corpus and key, so
every fit is the same answer.  It ends at the first chunk fold that
completes after ``--seconds``: the next pull from the source after the
deadline waits for every dispatched fold and stops the fit.  Rows
folded, over every pass, divided by the window is ``fit_rows_per_s``.

On the hashed corpus the window also keeps, for every power pass it
completes, a probe of the pass's accumulated sketch Y = A^T B Omega (the
program's accumulator as ``on_pass_end`` hands it over, times a fixed
Gaussian ``(k~, PROBE_COLS)`` matrix), for ``y_rel_err``.
"""

from __future__ import annotations

import hashlib
import statistics
import sys
import time

import numpy as np

import corpora
import reference
import work


PROBE_COLS = 8


class StopWindow(Exception):
    """Raised from the chunk source once the window is over."""


def _rcca_config(cfg: dict):
    from repro.core.rcca import RCCAConfig

    return RCCAConfig(k=cfg["k"], p=cfg["p"], q=cfg["q"], nu=cfg["nu"],
                      center=cfg["center"])


class _Window:
    """Deadline and fold count shared by the sources of one window."""

    def __init__(self, n_chunks: int, q: int):
        self.n_chunks, self.q = n_chunks, q
        self.deadline = float("inf")
        self.folded = []  # (pass kind, chunk index) per chunk handed to a fit
        self.end = None
        self.last = None  # what to wait on: the accumulator, else the last chunk
        self.probe = None  # (digest fn, Ga, Gb) where the window keeps Y probes
        self.y_probes = []

    def pull(self, kind: str, i: int) -> None:
        """Called before chunk i is made; the previous chunk's fold has
        been dispatched.  Past the deadline, wait for it and stop."""
        if time.perf_counter() >= self.deadline:
            self._sync()
            self.end = time.perf_counter()
            raise StopWindow
        self.folded.append((kind, i))

    def on_fold(self, pass_idx, chunk_idx, acc, Qa, Qb) -> None:
        self.last = acc
        if (self.probe is not None and chunk_idx == self.n_chunks - 1
                and self.kind(pass_idx) == "power"):
            digest, Ga, Gb = self.probe
            y = acc.result()
            self.y_probes.append(digest(y.Ya, y.Yb, Ga, Gb))

    def _sync(self) -> None:
        import jax
        import jax.numpy as jnp

        if hasattr(self.last, "state"):
            jax.block_until_ready(self.last.state())
        elif self.last is not None:
            # A program issued after the last fold; the device runs its
            # programs in issue order.
            jnp.sum(self.last[0, 0]).block_until_ready()

    def kind(self, pass_idx: int) -> str:
        return "power" if pass_idx % (self.q + 1) < self.q else "final"


class _WindowedStack:
    """View A's chunk stack as ``randomized_cca_streaming`` indexes it,
    reporting each pull to the window."""

    def __init__(self, arr, win: _Window):
        self.arr, self.win, self.shape = arr, win, arr.shape
        self.pulls = 0

    def __getitem__(self, i):
        kind = self.win.kind(self.pulls // self.win.n_chunks)
        self.pulls += 1
        self.win.pull(kind, int(i))
        self.win.last = self.arr[i]
        return self.win.last


def corpus(run) -> dict:
    """The cell's inputs from the seed, and the reference's view of them
    (``blocks(i) -> (A_i, B_i)`` on the device)."""
    import jax
    import jax.numpy as jnp

    cfg, tr = run.cell.config, run.cell.traffic
    d, chunk = cfg["d"], cfg["chunk"]
    n_chunks = cfg["n"] // chunk
    st = {"key": corpora.jax_key(run.seed, 0), "n_chunks": n_chunks, "chunk": chunk, "d": d}
    if tr["corpus"] == "hashed_docs":
        docs_a, docs_b = corpora.paired_docs(cfg["n"], run.seed, **tr["docs"])
        hash_a, hash_b = (int(s) for s in corpora.rng_for(run.seed, 4).integers(0, 2**31, 2))
        rows = [slice(i * chunk, (i + 1) * chunk) for i in range(n_chunks)]
        kt = cfg["k"] + cfg["p"]
        ga, gb = jax.random.split(corpora.jax_key(run.seed, 6))
        st.update(docs=(docs_a, docs_b), hash_seeds=(hash_a, hash_b), rows=rows,
                  probe=(jax.random.normal(ga, (kt, PROBE_COLS), jnp.float32),
                         jax.random.normal(gb, (kt, PROBE_COLS), jnp.float32)))
        st["blocks"] = lambda i: (reference.hashed_rows(docs_a[rows[i]], d, hash_a),
                                  reference.hashed_rows(docs_b[rows[i]], d, hash_b))
    elif tr["corpus"] == "planted_activations":
        A, B = corpora.planted_activations(run.seed, n_chunks, chunk, d, d, **tr["activations"])
        st.update(corpus=(A, B))
        st["blocks"] = lambda i: (A[i], B[i])
    else:
        raise ValueError(f"unknown corpus {tr['corpus']!r}")
    return st


def setup(run) -> dict:
    import jax

    from repro.core.rcca import randomized_cca_iterator, randomized_cca_streaming
    from repro.data import HashingFeaturizer

    cfg, tr = run.cell.config, run.cell.traffic
    st = corpus(run)
    jax.block_until_ready(st.get("corpus"))
    print(f"corpus made at {time.perf_counter() - run.t_start:.3f} s", file=sys.stderr)
    d, chunk, n_chunks = st["d"], st["chunk"], st["n_chunks"]
    kt = cfg["k"] + cfg["p"]
    st["rcfg"] = _rcca_config(cfg)
    work_of = {}
    if tr["corpus"] == "hashed_docs":
        docs_a, docs_b = st["docs"]
        hash_a, hash_b = st["hash_seeds"]
        rows = st["rows"]
        fa, fb = HashingFeaturizer(d, seed=hash_a), HashingFeaturizer(d, seed=hash_b)

        def featurize(i):
            with jax.profiler.TraceAnnotation("bench.featurize"):
                return fa.featurize_batch(docs_a[rows[i]]), fb.featurize_batch(docs_b[rows[i]])

        for i in range(n_chunks):
            (na, ca), (nb, cb) = work.hashed_counts(docs_a[rows[i]]), work.hashed_counts(docs_b[rows[i]])
            for kind in ("power", "final"):
                work_of[kind, i] = work.chunk_work(kind, chunk, kt, d, d, na, nb, ca, cb)

        digest = jax.jit(lambda Ya, Yb, Ga, Gb: (reference.sketch_probe(Ya, Ga),
                                               reference.sketch_probe(Yb, Gb)))

        def fit(win: _Window, n: int = n_chunks, merge_group=None):
            win.probe = (digest, *st["probe"])
            passes = iter(range(10**9))

            def source():
                kind = win.kind(next(passes))
                for i in range(n):
                    win.pull(kind, i)
                    yield featurize(i)

            kw = {} if merge_group is None else {"merge_group": merge_group}
            return randomized_cca_iterator(source, d, d, st["rcfg"], st["key"],
                                           n_chunks=n, on_pass_end=win.on_fold, **kw)

        # Warm-up: a fit of two chunks in two merge groups runs every
        # program of the window once on its shapes (updates, group merge,
        # Q update, finish); the corpus has no other shapes.
        fit(_Window(2, cfg["q"]), n=2, merge_group=1)
    else:
        A, B = st["corpus"]
        nnz, cols = work.dense_counts(chunk, d)
        for i in range(n_chunks):
            for kind in ("power", "final"):
                work_of[kind, i] = work.chunk_work(kind, chunk, kt, d, d, nnz, nnz, cols, cols)

        def fit(win: _Window):
            return randomized_cca_streaming(_WindowedStack(A, win), B, st["rcfg"], st["key"])

        # Warm-up: one whole fit.  The program takes chunk i of the stack
        # by a static slice, one program per index, so a shorter fit would
        # leave slices to compile inside the window.
        warm = _Window(n_chunks, cfg["q"])
        jax.block_until_ready(fit(warm).Xa)
        warm._sync()  # the window's closing wait, compiled here
    st["fit"], st["work"] = fit, work_of
    return st


def window(run, st) -> dict:
    import jax

    win = _Window(st["n_chunks"], run.cell.config["q"])
    fits, ends = [], []
    t0 = time.perf_counter()
    win.deadline = t0 + run.seconds
    try:
        while True:
            with jax.profiler.TraceAnnotation("bench.fit"):
                res = st["fit"](win)
                # The user reads the correlations; the projections stay on
                # the device until the check.
                out = {"rho": np.asarray(res.rho), "Xa": res.Xa, "Xb": res.Xb}
            del res
            fits.append(out)
            ends.append(time.perf_counter())
    except StopWindow:
        pass
    window_s = win.end - t0
    if ends:
        took = np.diff([t0] + ends)
        print(f"fits {len(took)}: first {took[0]:.4f} s, median {statistics.median(took):.4f} s, "
              f"slowest {took.max():.4f} s", file=sys.stderr)
    st["fits"] = fits
    st["y_probes"] = win.y_probes
    chunks = len(win.folded)
    cfg = run.cell.config
    kt, d = cfg["k"] + cfg["p"], st["d"]
    # A pass that starts after a power pass follows a Q update; each
    # whole fit ends in a finish.
    q_updates = sum(1 for prev, (kind, i) in zip(win.folded, win.folded[1:])
                    if i == 0 and prev[0] == "power")
    run.records.update(
        chunks_folded=chunks, fits=len(fits), window_s=window_s, q=cfg["q"],
        chunk_work=[st["work"][kind, i] for kind, i in win.folded],
        boundary_ops=(q_updates * work.boundary_ops("power", d, d, kt, cfg["k"])
                      + len(fits) * work.boundary_ops("final", d, d, kt, cfg["k"])))
    return {"fit_rows_per_s": chunks * st["chunk"] / window_s, "window_s": window_s,
            "attempted": len(fits), "failed": 0}


def reference_fit(run, st, precision: str) -> dict:
    cfg = run.cell.config
    return reference.rcca(st["blocks"], st["n_chunks"], st["d"], st["d"], k=cfg["k"],
                          p=cfg["p"], q=cfg["q"], nu=cfg["nu"], center=cfg["center"],
                          key=st["key"], precision=precision, probe=st.get("probe"))


def check(run, st) -> dict:
    """Every whole fit of the window against the plain reference."""
    fits, y_probes = st.pop("fits"), st.pop("y_probes")
    st.pop("fit")
    if not fits:
        return {"missing": 1}
    return compare(st, fits, reference_fit(run, st, run.cell.config["matmul_precision"]),
                   y_probes)


def control(run) -> dict:
    """The reference in the program's place at the precision below the
    configuration's (three-pass bf16 matmuls), held to the same numbers."""
    st = corpus(run)
    ctl = reference_fit(run, st, "high")
    return compare(st, [ctl], reference_fit(run, st, run.cell.config["matmul_precision"]),
                   [ctl["y_probe"]] if "y_probe" in ctl else [])


def compare(st, fits, ref, y_probes=()) -> dict:
    """The gap of each correlation, and how far each fit's projections are
    from a CCA solution of the data with the reference's correlations;
    on the hashed corpus also the relative error of each probe of the
    first power pass's Y."""
    rho_gap = max(float(np.max(np.abs(np.asarray(f["rho"]) - ref["rho"]))) for f in fits)
    residuals = {}
    for f in fits:  # identical fits share one pass over the data
        Xa, Xb = np.asarray(f["Xa"]), np.asarray(f["Xb"])
        h = hashlib.sha256(Xa.tobytes() + Xb.tobytes()).hexdigest()
        if h not in residuals:
            residuals[h] = reference.solution_residuals(
                st["blocks"], st["n_chunks"], Xa, Xb, ref["lam"], ref["mu"], ref["rho"])
    out = {"rho_gap": rho_gap,
           "x_cross_gap": max(r["cross"] for r in residuals.values()),
           "x_feas_gap": max(r["feas"] for r in residuals.values())}
    if "y_probe" in ref:
        # A window that completed no power pass has no probe to compare.
        out["y_rel_err"] = max(
            (reference.rel_err(np.asarray(p), r) for y in y_probes
             for p, r in zip(y, ref["y_probe"])), default=float("inf"))
    return out
