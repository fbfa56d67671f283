"""Open-loop projection queries against the program's serving tier.

Traffic keys: ``rate`` (requests per second, fixed in the cell),
``pool`` (seeded docs a request may ask for), ``popularity_zipf``,
``share_a`` (the share of view-a requests), ``handlers`` (threads that
run the served path), ``docs`` (the doc generator's parameters).

The served model is ``Xa, Xb`` of shape ``(d, k)``, made from the seed,
published through ``repro.serve.ModelRegistry`` and loaded back.  Each
request runs the served path on a handler thread: the program's
featurizer on its one doc, ``BatchedProjector.submit(view, row)``, then
``result()``.  Latency runs from the request's due time to its response.
"""

from __future__ import annotations

import concurrent.futures
import os
import time

import numpy as np

import corpora
import reference

MODEL = "bench"


class _Model:
    """What ``ModelRegistry.publish`` reads of a fit."""

    def __init__(self, Xa, Xb, rho, Q):
        self.Xa, self.Xb, self.rho, self.Qa, self.Qb = Xa, Xb, rho, Q, Q


def corpus(run) -> dict:
    """The doc pool, the hash seeds and the served model ``Xa, Xb`` (made
    on the device in one jitted call), from the seed."""
    import jax
    import jax.numpy as jnp

    cfg, tr = run.cell.config, run.cell.traffic
    d, k = cfg["d"], cfg["k"]
    docs_a, docs_b = corpora.paired_docs(tr["pool"], run.seed, **tr["docs"])
    hash_a, hash_b = (int(s) for s in corpora.rng_for(run.seed, 4).integers(0, 2**31, 2))

    @jax.jit
    def make(key):
        ka, kb = jax.random.split(key)
        scale = 1.0 / np.sqrt(tr["docs"]["doc_len"])
        return (jax.random.normal(ka, (d, k), jnp.float32) * scale,
                jax.random.normal(kb, (d, k), jnp.float32) * scale)

    Xa, Xb = make(corpora.jax_key(run.seed, 5))
    return {"docs": {"a": docs_a, "b": docs_b}, "hash": {"a": hash_a, "b": hash_b},
            "X_dev": (Xa, Xb), "X": {"a": np.asarray(Xa), "b": np.asarray(Xb)},
            "sched": corpora.request_schedule(
                run.seed, rate=tr["rate"], seconds=run.seconds, pool=tr["pool"],
                popularity_zipf=tr["popularity_zipf"], share_a=tr["share_a"])}


def setup(run) -> dict:
    import jax.numpy as jnp

    from repro.data import HashingFeaturizer
    from repro.serve import BatchedProjector, ModelRegistry

    st = corpus(run)
    d, k = run.cell.config["d"], run.cell.config["k"]
    Xa, Xb = st.pop("X_dev")
    rho = jnp.linspace(0.99, 0.5, k, dtype=jnp.float32)
    reg = ModelRegistry(os.path.join(run.out_dir, "registry"))
    version = reg.publish(MODEL, _Model(Xa, Xb, rho, jnp.zeros((1, 1), jnp.float32)))
    model = reg.load(MODEL)
    del Xa, Xb
    feat = {v: HashingFeaturizer(d, seed=st["hash"][v]) for v in "ab"}
    docs = st["docs"]
    proj = BatchedProjector(model)

    # Warm-up: every padded batch shape of both views, each once.
    b = 1
    while b <= proj.max_batch:
        for view in ("a", "b"):
            rows = feat[view].featurize_batch(docs[view][:b])
            tickets = [proj.submit(view, r) for r in rows]
            for t in tickets:
                t.result(timeout=600)
        b <<= 1
    st.update(proj=proj, feat=feat, version=version)
    return st


def window(run, st) -> dict:
    import jax

    tr = run.cell.traffic
    sched = st["sched"]
    n = len(sched["due"])
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    answers = [None] * n
    proj, feat, docs = st["proj"], st["feat"], st["docs"]
    close_wait = 60.0

    def handle(i: int, t0: float) -> None:
        sent[i] = time.perf_counter() - t0
        view = sched["view"][i]
        with jax.profiler.TraceAnnotation("bench.submit"):
            row = feat[view].featurize_batch(docs[view][sched["doc"][i]][None])[0]
            ticket = proj.submit(view, row)
        ans = ticket.result(timeout=run.seconds + close_wait)
        done[i] = time.perf_counter() - t0
        answers[i] = ans

    pool = concurrent.futures.ThreadPoolExecutor(tr["handlers"], "bench-handler")
    futures = []
    t0 = time.perf_counter()
    try:
        for i in range(n):
            wait = t0 + sched["due"][i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            futures.append(pool.submit(handle, i, t0))
        rest = t0 + run.seconds - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
        window_end = time.perf_counter()
        concurrent.futures.wait(futures, timeout=close_wait + run.seconds)
    finally:
        pool.shutdown(wait=True, cancel_futures=True)
    failed = sum(1 for f in futures
                 if not f.done() or f.cancelled() or f.exception() is not None)
    lat = np.where(np.isnan(done), np.inf, done - sched["due"])
    stats = proj.stats()
    st["answers"] = answers
    run.records.update(
        requests=n, gen_late_s=np.nan_to_num(sent - sched["due"], nan=np.inf),
        latency_s=lat, batches=stats["batches"])
    return {"serve_p95_ms": float(np.percentile(lat, 95, method="inverted_cdf")) * 1e3,
            "window_s": window_end - t0, "attempted": n, "failed": failed}


def check(run, st) -> dict:
    """Every response against ``hash(doc) @ X`` in float64: the largest
    relative error, and whether each came stamped with the published
    version."""
    st.pop("proj").close()
    return compare(st, st["answers"], st["version"])


def compare(st, answers, version) -> dict:
    sched = st["sched"]
    ref = {}
    worst, missing, stale = 0.0, 0, 0
    for i, ans in enumerate(answers):
        if ans is None:
            missing += 1
            continue
        view, doc = sched["view"][i], int(sched["doc"][i])
        if (view, doc) not in ref:
            ref[view, doc] = reference.hashed_projection(
                st["docs"][view][doc], st["X"][view], st["hash"][view])
        want = ref[view, doc]
        err = np.linalg.norm(ans["emb"] - want) / max(np.linalg.norm(want), 1e-30)
        worst = max(worst, float(err))
        stale += ans["version"] != version
    return {"emb_rel_err": worst, "wrong_version": stale, "missing": missing}


def control(run) -> dict:
    """The reference in the projector's place at the precision below the
    configuration's: every answer of the schedule as three-pass bf16."""
    import jax

    st = corpus(run)
    Xa, Xb = st.pop("X_dev")
    X = {"a": Xa, "b": Xb}
    dot = jax.jit(reference._dot("high"))
    sched, d = st["sched"], run.cell.config["d"]
    answers = [None] * len(sched["due"])
    for view in "ab":
        idx = np.flatnonzero(sched["view"] == view)
        for lo in range(0, len(idx), 256):
            part = idx[lo:lo + 256]
            rows = reference.hashed_rows(st["docs"][view][sched["doc"][part]], d,
                                         st["hash"][view])
            emb = np.asarray(dot(rows, X[view]))
            for j, i in enumerate(part):
                answers[i] = {"emb": emb[j], "version": 1}
    return compare(st, answers, 1)
