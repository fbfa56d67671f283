"""The work a chunk update needs, whatever implements it.

``chunk_roofline_pct`` divides the least time of this work by the
measured device time.  The count comes from the chunk's own data (its
nonzeros and the columns they touch), not from the program's kernel
plans, so a dense, sparse, staged or padded implementation is held to
the same work and none can read above 100%.

Operations (k~ = sketch width, r = chunk rows):

* power chunk: 4 k~ (nnz_A + nnz_B)   -- B Q_b, A^T (B Q_b) and the mirror
* final chunk: 2 k~ (nnz_A + nnz_B) + 3 * 2 r k~^2   -- A Q_a, B Q_b, three Grams

Bytes, each term once:

* each view's chunk, in the smaller of dense f32 and value + index form;
* the rows of Q that the chunk's nonzero columns touch;
* power: those rows of Y, read and written;
* final: C_a, C_b and F, read and written.

Least time is max(ops / peak FLOP/s, bytes / HBM bytes/s) with the
chip's bf16 peak for any floating operand.
"""

from __future__ import annotations

import numpy as np

F32 = 4
INDEX = 4


def chunk_bytes(rows: int, d: int, nnz: int) -> int:
    """One view's chunk: dense f32, or a value + index per nonzero."""
    return min(rows * d * F32, nnz * (F32 + INDEX))


def chunk_work(kind: str, rows: int, kt: int, da: int, db: int,
               nnz_a: int, nnz_b: int, cols_a: int, cols_b: int) -> tuple:
    """``(ops, bytes)`` one chunk update needs."""
    nnz = nnz_a + nnz_b
    data = chunk_bytes(rows, da, nnz_a) + chunk_bytes(rows, db, nnz_b)
    q_rows = (cols_a + cols_b) * kt * F32
    if kind == "power":
        return 4 * kt * nnz, data + q_rows + 2 * q_rows
    if kind == "final":
        return (2 * kt * nnz + 3 * 2 * rows * kt * kt,
                data + q_rows + 2 * 3 * kt * kt * F32)
    raise ValueError(f"unknown pass kind {kind!r}")


def boundary_ops(kind: str, da: int, db: int, kt: int, k: int) -> int:
    """Operations a pass boundary needs at least: after a power pass the
    orthonormal bases of Ya and Yb (Householder QR, 2 d k~^2 each);
    after the final pass Xa = Qa Wa and Xb = Qb Wb (2 d k~ k each).  The
    k~^3 terms of the finish are left out, so the count is a floor."""
    if kind == "power":
        return 2 * (da + db) * kt * kt
    if kind == "final":
        return 2 * (da + db) * kt * k
    raise ValueError(f"unknown pass kind {kind!r}")


def dense_counts(rows: int, d: int) -> tuple:
    """``(nnz, touched columns)`` of a dense view chunk."""
    return rows * d, d


def hashed_counts(tokens: np.ndarray, pad: int = 0) -> tuple:
    """``(nnz, touched columns)`` bounds of a hashed chunk from its token
    ids: distinct (row, token) pairs and distinct tokens.  Hashing can
    only merge tokens, so under any hash these bound the chunk's
    nonzeros and touched columns from above."""
    rows = np.repeat(np.arange(tokens.shape[0]), tokens.shape[1])
    toks = tokens.ravel()
    keep = toks != pad
    pairs = np.unique(np.stack([rows[keep], toks[keep]]), axis=1)
    return int(pairs.shape[1]), int(np.unique(toks[keep]).size)


def least_time(ops: float, nbytes: float, peaks: dict) -> tuple:
    """``(seconds, bound)``: the least time the chip could take, and
    whether compute or memory bandwidth bounds it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
