"""Feature hashing (Weinberger et al., 2009) — the paper's featurizer.

Bag-of-words composed with inner-product-preserving hashing: token t
maps to slot h(t) mod d with sign s(t) ∈ {±1}.  The paper uses 2^19
slots per language view on Europarl.

:meth:`HashingFeaturizer.featurize_batch` returns a :class:`HashedRows`:
each row's token slots and signs, ``(n, L)`` each, never the dense
``(n, d)`` rows they stand for.  A fit copies these two small arrays to
the device (``exec.engine.on_device``: the ``h2d`` span's ``bytes`` are
the dense rows' bytes, ``wire_bytes`` the bytes copied, and ``form`` is
``"hashed"``), and the jitted chunk update builds the dense rows inside
its own program (``core.rcca.dense_chunk_rows``).  Any other user gets
the dense rows through the array protocol (``np.asarray``,
``jnp.asarray``, ``np.stack``, indexing, iteration).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs


def _mix(x: np.ndarray, seed: int) -> np.ndarray:
    """Cheap splitmix64-style integer hash (vectorized, deterministic)."""
    with np.errstate(over="ignore"):
        x = x.astype(np.uint64) + np.uint64((seed * 0x9E3779B97F4A7C15) % 2**64)
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    return x


def _scatter_rows(slots: np.ndarray, signs: np.ndarray, n_slots: int) -> np.ndarray:
    """``(..., n_slots)`` f32 rows: each entry adds its sign at its slot."""
    lead = slots.shape[:-1]
    out = np.zeros((math.prod(lead), n_slots), np.float32)
    rows = np.repeat(np.arange(out.shape[0]), slots.shape[-1])
    np.add.at(out, (rows, slots.reshape(-1)), signs.reshape(-1))
    return out.reshape(lead + (n_slots,))


@jax.tree_util.register_pytree_node_class
class HashedRows:
    """Hashed rows as their token slots and signs: row i is the sum of
    ``signs[i, j]`` at ``slots[i, j]`` over ``n_slots`` columns.  Pad
    entries carry sign 0.0.

    A JAX pytree (leaves ``slots`` (n, L) int32 and ``signs`` (n, L)
    f32, static ``n_slots``), so it crosses ``jax.device_put`` and
    ``jax.jit`` as the two small arrays.  To everything else it looks
    like the dense ``(n, n_slots)`` f32 array: ``shape``, ``dtype``,
    ``ndim``, ``len``, indexing and iteration give the dense array's,
    and ``__array__`` builds it."""

    dtype = np.dtype(np.float32)

    def __init__(self, slots, signs, n_slots: int):
        self.slots, self.signs, self.n_slots = slots, signs, n_slots

    def tree_flatten(self):
        return (self.slots, self.signs), self.n_slots

    @classmethod
    def tree_unflatten(cls, n_slots, leaves):
        return cls(*leaves, n_slots)

    @property
    def shape(self) -> tuple:
        return tuple(self.slots.shape[:-1]) + (self.n_slots,)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def __len__(self) -> int:
        return self.shape[0]

    def __array__(self, dtype=None, copy=None):
        out = _scatter_rows(np.asarray(self.slots), np.asarray(self.signs), self.n_slots)
        return out if dtype is None else out.astype(dtype)

    def __getitem__(self, idx):
        if isinstance(idx, tuple):  # an index into the columns needs every row
            return np.asarray(self)[idx]
        return _scatter_rows(np.asarray(self.slots)[idx], np.asarray(self.signs)[idx],
                      self.n_slots)

    def __iter__(self):
        return (self[i] for i in range(len(self)))

    def to_dense(self):
        """The dense rows as a jax array, built by one scatter-add; under
        ``jax.jit`` this is part of the traced program."""
        n, L = self.slots.shape
        rows = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, L))
        return jnp.zeros((n, self.n_slots), jnp.float32).at[rows, self.slots].add(self.signs)


class HashingFeaturizer:
    """Maps integer token-id bags to hashed feature rows."""

    def __init__(self, n_slots: int, seed: int = 0):
        self.n_slots = n_slots
        self.seed = seed

    def slots(self, token_ids: np.ndarray) -> np.ndarray:
        return (_mix(token_ids, self.seed) % np.uint64(self.n_slots)).astype(np.int64)

    def signs(self, token_ids: np.ndarray) -> np.ndarray:
        return np.where(_mix(token_ids, self.seed + 1) & np.uint64(1), 1.0, -1.0).astype(np.float32)

    def featurize(self, docs: list[np.ndarray]) -> np.ndarray:
        """docs: list of integer token-id arrays → (len(docs), n_slots)."""
        out = np.zeros((len(docs), self.n_slots), np.float32)
        for i, doc in enumerate(docs):
            if len(doc) == 0:
                continue
            s = self.slots(doc)
            np.add.at(out[i], s, self.signs(doc))
        return out

    def featurize_batch(self, token_mat: np.ndarray) -> HashedRows:
        """token_mat: (n, L) padded token ids (0 = pad) → the (n, n_slots)
        rows as :class:`HashedRows` (pads get sign 0.0).

        Under ``RCCA_TRACE`` a ``featurize`` span with the rows, the dense
        rows' bytes and their nonzeros (``nnz``: the (row, slot) entries
        whose signs do not cancel)."""
        n, L = token_mat.shape
        with obs.span("featurize", rows=n, bytes=n * self.n_slots * 4) as sp:
            valid = token_mat > 0
            slots = self.slots(token_mat).astype(np.int32)
            signs = np.where(valid, self.signs(token_mat), np.float32(0.0))
            if obs.enabled():
                keys = (np.arange(n)[:, None] * self.n_slots + slots)[valid]
                _, entry = np.unique(keys, return_inverse=True)
                sums = np.bincount(entry, weights=signs[valid])
                sp.note(nnz=int(np.count_nonzero(sums)))
        return HashedRows(slots, signs, self.n_slots)
