"""The benchmark's inputs, made from ``--seed``.

* :func:`paired_docs` -- the paired bag-of-words corpus: about 30
  Zipf(1.3) tokens per row, view B a fixed "translation" of view A with
  20% noise (a copy of ``repro.data.synth_paired_docs``, kept here so
  that the yardstick does not move with the program).
* :func:`planted_activations` -- two views of dense activations with a
  planted shared low-rank signal, made on the device in one jitted call.
* :func:`request_schedule` -- open-loop arrivals and the docs they ask
  for.  Every seed gets the same multiset of gaps, docs and views, in
  another order, so seeds change the order of the work and not its size.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator per ``(seed, stream...)``; any whole seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def jax_key(seed: int, stream: int):
    """A JAX PRNG key drawn from ``(seed, stream)``; seeds past 32 bits
    are fine."""
    import jax

    word = int(rng_for(seed, stream).integers(0, 2**31 - 1))
    return jax.random.PRNGKey(word)


def paired_docs(n: int, seed: int, *, vocab: int = 50_000, doc_len: int = 30,
                zipf: float = 1.3, noise: float = 0.2) -> tuple:
    """Two ``(n, doc_len)`` int64 token matrices (ids >= 1)."""
    rng = rng_for(seed, 1)
    base = rng.zipf(zipf, size=(n, doc_len)).clip(1, vocab - 1)
    translated = (base * 2_654_435_761) % vocab + 1
    noisy = rng.random((n, doc_len)) < noise
    other = rng.zipf(zipf, size=(n, doc_len)).clip(1, vocab - 1)
    paired = np.where(noisy, other, translated)
    return base.astype(np.int64), paired.astype(np.int64)


def planted_activations(seed: int, n_chunks: int, chunk: int, da: int, db: int,
                        *, rank: int, rho_top: float, rho_decay: float,
                        noise: float, feature_spread: float, mean_scale: float):
    """``(A, B)`` of shape ``(n_chunks, chunk, d)`` f32 on the device.

    A = (Z Wa + noise Ea) diag(sa) + mu_a, B likewise from Z' whose
    latent coordinates correlate with Z's by rho_top * rho_decay^i.  The
    per-feature scales sa = exp(feature_spread * N(0, 1)) give the uneven
    feature variances of real activations; mu is their mean offset."""
    import jax
    import jax.numpy as jnp

    def make(key):
        kw, kz = jax.random.split(key)
        kwa, kwb, ksa, ksb, kma, kmb = jax.random.split(kw, 6)
        f32 = jnp.float32
        Wa = jax.random.normal(kwa, (rank, da), f32) / np.sqrt(rank)
        Wb = jax.random.normal(kwb, (rank, db), f32) / np.sqrt(rank)
        sa = jnp.exp(feature_spread * jax.random.normal(ksa, (da,), f32))
        sb = jnp.exp(feature_spread * jax.random.normal(ksb, (db,), f32))
        mu_a = mean_scale * jax.random.normal(kma, (da,), f32)
        mu_b = mean_scale * jax.random.normal(kmb, (db,), f32)
        rho = rho_top * rho_decay ** jnp.arange(rank, dtype=f32)

        def one(kc):
            k1, k2, k3, k4 = jax.random.split(kc, 4)
            z = jax.random.normal(k1, (chunk, rank), f32)
            zb = rho * z + jnp.sqrt(1 - rho**2) * jax.random.normal(k2, (chunk, rank), f32)
            a = (z @ Wa + noise * jax.random.normal(k3, (chunk, da), f32)) * sa + mu_a
            b = (zb @ Wb + noise * jax.random.normal(k4, (chunk, db), f32)) * sb + mu_b
            return a, b

        return jax.lax.map(one, jax.random.split(kz, n_chunks))

    with jax.default_matmul_precision("highest"):
        return jax.jit(make)(jax_key(seed, 2))


def request_schedule(seed: int, *, rate: float, seconds: float, pool: int,
                     popularity_zipf: float, share_a: float) -> dict:
    """Open-loop Poisson arrivals over ``seconds`` at ``rate`` per second.

    The gaps are the quantiles of the exponential law, the docs the
    quantiles of Zipf(popularity_zipf) popularity over the pool, and
    exactly ``share_a`` of the requests are of view a; the seed shuffles
    each of the three."""
    n = max(1, int(round(rate * seconds)))
    rng = rng_for(seed, 3)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    weights = np.arange(1, pool + 1, dtype=np.float64) ** -popularity_zipf
    cdf = np.cumsum(weights) / weights.sum()
    docs = np.minimum(np.searchsorted(cdf, q), pool - 1)
    rng.shuffle(docs)
    views = np.array(["a"] * int(round(share_a * n)) + ["b"] * (n - int(round(share_a * n))))
    rng.shuffle(views)
    return {"due": due, "doc": docs, "view": views}
