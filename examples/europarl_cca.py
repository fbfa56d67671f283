"""End-to-end driver: the paper's Europarl experiment, faithfully staged.

Pipeline (paper §4):
  1. paired "sentences" → bag-of-words → feature hashing into d slots
     per view (Weinberger et al. hashing, the paper uses 2^19 slots);
  2. RandomizedCCA (Algorithm 1) over the hashed views, streaming the
     corpus in row chunks (out-of-core semantics, q+1 data passes); each
     chunk crosses to the device as its token slots and signs
     (``HashedRows``), and the chunk update builds the dense rows;
  3. report Σρ train/test, feasibility, and the Horst+rcca warm-start
     comparison (paper Table 2b).

Scaled to CPU: n=20k synthetic paired docs, 2^12 hash slots.  Flags let
you push n/d up on bigger hosts; the same code path is what
launch/cca_fit.py runs distributed.

With ``--store DIR`` the hashed views are ingested once into an
on-disk view store (repro.store) and the fit streams from disk through
the async-prefetching PassRunner — the paper's out-of-core setting:
featurize once, then any number of experiments re-read the store
instead of re-hashing.

    PYTHONPATH=src python examples/europarl_cca.py [--store /tmp/europarl]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import HorstConfig, cca_objective, horst_cca
from repro.core.rcca import RCCAConfig, randomized_cca_iterator
from repro.data import HashingFeaturizer, synth_paired_docs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--slots", type=int, default=4096)  # paper: 2**19
    ap.add_argument("--k", type=int, default=16)        # paper: 60
    ap.add_argument("--p", type=int, default=64)        # paper: 910/2000
    ap.add_argument("--q", type=int, default=1)
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--store", default=None, metavar="DIR",
                    help="ingest the hashed train views into an on-disk "
                         "view store and fit from it (out-of-core path "
                         "with async prefetch)")
    args = ap.parse_args()

    print(f"[1/3] hashing {args.n} paired docs into 2×{args.slots} slots...")
    docs_a, docs_b = synth_paired_docs(args.n)
    ha = HashingFeaturizer(args.slots, seed=1)
    hb = HashingFeaturizer(args.slots, seed=2)
    n_tr = int(args.n * 0.9)

    def chunks(lo, hi):
        for s in range(lo, hi, args.chunk):
            e = min(s + args.chunk, hi)
            yield ha.featurize_batch(docs_a[s:e]), hb.featurize_batch(docs_b[s:e])

    def dense(lo, hi, view):
        return jnp.asarray(np.concatenate([np.asarray(c[view]) for c in chunks(lo, hi)]))

    print(f"[2/3] RandomizedCCA k={args.k} p={args.p} q={args.q} "
          f"({args.q + 1} data passes, streamed)...")
    cfg = RCCAConfig(k=args.k, p=args.p, q=args.q, nu=0.01, center=True)
    t0 = time.time()
    if args.store:
        import os

        from repro.store import PassRunner, ViewStoreReader, ingest_chunks
        from repro.store.format import MANIFEST

        if not os.path.exists(os.path.join(args.store, MANIFEST)):
            reader = ingest_chunks(args.store, chunks(0, n_tr), chunk=args.chunk)
            print(f"      ingested {reader.n} hashed rows "
                  f"({reader.nbytes / 1e6:.1f} MB) → {args.store}")
        else:
            reader = ViewStoreReader(args.store)
            if (reader.n, reader.da, reader.db) != (n_tr, args.slots, args.slots):
                raise SystemExit(
                    f"view store {args.store} holds n={reader.n} "
                    f"da={reader.da} db={reader.db} but the flags ask for "
                    f"n={n_tr} slots={args.slots} — point --store at a "
                    "fresh directory (or delete it) to re-ingest")
            print(f"      reusing view store {args.store} (n={reader.n})")
        res = PassRunner(reader, cfg).fit(jax.random.PRNGKey(0))
        print(f"      io: {res.diagnostics['io']}")
    else:
        res = randomized_cca_iterator(
            lambda: chunks(0, n_tr), args.slots, args.slots, cfg, jax.random.PRNGKey(0)
        )
    print(f"      done in {time.time()-t0:.1f}s; sum rho = {float(jnp.sum(res.rho)):.4f}")

    # evaluate train/test objective on materialized matrices (small scale)
    A_tr, B_tr = dense(0, n_tr, 0), dense(0, n_tr, 1)
    A_te, B_te = dense(n_tr, args.n, 0), dense(n_tr, args.n, 1)
    mu_a, mu_b = jnp.mean(A_tr, 0), jnp.mean(B_tr, 0)
    tr = float(cca_objective(A_tr - mu_a, B_tr - mu_b, res.Xa, res.Xb))
    te = float(cca_objective(A_te - mu_a, B_te - mu_b, res.Xa, res.Xb))
    print(f"      objective: train {tr:.4f} / test {te:.4f}")

    print("[3/3] Horst+rcca warm start (paper Table 2b)...")
    t0 = time.time()
    h = horst_cca(A_tr - mu_a, B_tr - mu_b,
                  HorstConfig(k=args.k, iters=10, nu=0.01), init_Xb=res.Xb)
    tr_h = float(cca_objective(A_tr - mu_a, B_tr - mu_b, h.Xa, h.Xb))
    print(f"      10 Horst iterations from rcca init: train {tr_h:.4f} "
          f"(+{tr_h - tr:.4f}) in {time.time()-t0:.1f}s")
    print("OK")


if __name__ == "__main__":
    main()
