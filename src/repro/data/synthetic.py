"""Synthetic corpora.

PlantedCCAData — a Europarl stand-in: two views generated from a shared
latent with a known, power-law canonical-correlation spectrum, so every
benchmark curve (Fig 1/2a/3) has a checkable ground truth.  Generation
is chunked and deterministic per chunk index → the stream can be
replayed from any point (fault-tolerant data passes) and sharded by
row-range across workers without materializing n × d in memory.

synth_paired_docs — the paper's bilingual corpus as token bags: about
30 Zipf(1.3) tokens per row, view B a fixed "translation" of view A with
20% noise, ready for :class:`repro.data.HashingFeaturizer`.

SyntheticTokenStream — deterministic LM token batches for train steps.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Tuple

import numpy as np


@dataclasses.dataclass
class PlantedCCAData:
    """Two views A (n×da), B (n×db) with planted correlations.

    A = Z Wa + σa Ea,  B = Z Wb + σb Eb,  Z ~ N(0, I_r): the canonical
    correlations decay like a power law via per-component latent scales
    s_i = (i+1)^{-decay} — mimicking the paper's Fig-1 spectrum.
    """

    n: int
    da: int
    db: int
    rank: int = 64
    decay: float = 0.7
    noise: float = 0.5
    seed: int = 0
    chunk: int = 1024

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        scales = (np.arange(1, self.rank + 1, dtype=np.float32)) ** (-self.decay)
        self.scales = scales
        self.Wa = rng.standard_normal((self.rank, self.da), np.float32) / np.sqrt(self.da)
        self.Wb = rng.standard_normal((self.rank, self.db), np.float32) / np.sqrt(self.db)

    @property
    def n_chunks(self) -> int:
        return (self.n + self.chunk - 1) // self.chunk

    def get_chunk(self, idx: int) -> Tuple[np.ndarray, np.ndarray]:
        """Deterministic chunk — replayable from any index."""
        lo = idx * self.chunk
        hi = min(lo + self.chunk, self.n)
        m = hi - lo
        rng = np.random.default_rng((self.seed + 1) * 1_000_003 + idx)
        Z = rng.standard_normal((m, self.rank)).astype(np.float32) * self.scales
        Ea = rng.standard_normal((m, self.da)).astype(np.float32)
        Eb = rng.standard_normal((m, self.db)).astype(np.float32)
        A = Z @ self.Wa + self.noise * Ea / np.sqrt(self.da)
        B = Z @ self.Wb + self.noise * Eb / np.sqrt(self.db)
        return A, B

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        for i in range(self.n_chunks):
            yield self.get_chunk(i)

    def materialize(self) -> Tuple[np.ndarray, np.ndarray]:
        """Small-scale only: stack all chunks (tests/benchmarks)."""
        As, Bs = zip(*list(self))
        return np.concatenate(As), np.concatenate(Bs)

    def row_shard(self, shard: int, n_shards: int) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Deterministic chunk assignment for distributed workers:
        worker w streams chunks w, w+n_shards, w+2·n_shards, ..."""
        for i in range(shard, self.n_chunks, n_shards):
            yield self.get_chunk(i)


@dataclasses.dataclass
class SyntheticTokenStream:
    """Deterministic (B, S) int32 token batches."""

    vocab: int
    batch: int
    seq: int
    seed: int = 0

    def get_batch(self, step: int) -> np.ndarray:
        rng = np.random.default_rng(self.seed * 7_919 + step)
        return rng.integers(0, self.vocab, (self.batch, self.seq + 1), dtype=np.int32)

    def __iter__(self):
        step = 0
        while True:
            yield self.get_batch(step)
            step += 1


def planted_views(key_seed: int, n: int, da: int, db: int, rank: int = 8,
                  noise: float = 0.5, decay: float = 0.7):
    """Convenience: materialized planted views as numpy arrays."""
    d = PlantedCCAData(n=n, da=da, db=db, rank=rank, decay=decay, noise=noise,
                       seed=key_seed, chunk=max(256, n // 8))
    return d.materialize()


def synth_paired_docs(n: int, vocab: int = 50_000, doc_len: int = 30,
                      seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Paired 'translations': view B's tokens are a deterministic map of
    view A's plus noise — so the views share latent structure exactly
    like sentence-aligned Europarl.  Returns two (n, doc_len) int64
    token-id matrices (ids ≥ 1; 0 is the featurizer's pad id)."""
    rng = np.random.default_rng(seed)
    # zipfian-ish token draws
    base = rng.zipf(1.3, size=(n, doc_len)).clip(1, vocab - 1)
    translate = lambda t: (t * 2_654_435_761) % vocab + 1  # fixed "dictionary"
    noise_mask = rng.random((n, doc_len)) < 0.2
    other = rng.zipf(1.3, size=(n, doc_len)).clip(1, vocab - 1)
    paired = np.where(noise_mask, other, translate(base))
    return base.astype(np.int64), paired.astype(np.int64)
