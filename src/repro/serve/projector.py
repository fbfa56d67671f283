"""Batched projection serving: request coalescing + zero-drop hot-swap.

:class:`BatchedProjector` is the traffic front-end: concurrent callers
submit single rows (or small row blocks) of either view; a daemon batch
thread coalesces whatever is queued into one padded device batch per
view, projects it (x ↦ Φᵃx / Φᵇx), and completes each request with its
embedding stamped with the model version that computed it.

Hot-swap contract: ``swap(new_model)`` takes effect at the next batch
boundary.  The in-flight batch completes under the old version; every
queued and future request is served by the new one; no request is ever
dropped or served by a half-installed model, because the batch thread
reads the model exactly once per batch under the queue lock.  The
version stamp on every response is what makes this testable: a response
claiming version v must equal ``x @ Xa(v)`` bitwise.

Padding: a batch of r requests is padded to the next power of two (≤
``max_batch``), so the jitted projection sees a handful of shapes
instead of one per occupancy — the standard serving trade of a few
wasted pad rows for a warm compile cache.

:class:`CorpusIndex` holds one view's projected corpus for cross-view
top-k retrieval: score(query, row) = Σ_k ρ_k·φ_k(query)·φ_k(row), the
correlation-weighted inner product in canonical space.

Everything traces through :mod:`repro.obs`: a ``serve_batch`` span per
batch (occupancy + version), ``serve_occupancy`` counters, and a
``serve_swap`` counter per version flip.
"""

from __future__ import annotations

import functools
import threading
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.linalg import full_f32

from .registry import ServedModel


@functools.lru_cache(maxsize=64)
def _project_jit(dim: int, k: int, bucket: int):
    """One compiled projection per (input dim, k, padded batch) shape."""
    return jax.jit(full_f32(lambda X, x: x @ X))


def _bucket(n: int, cap: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return min(b, max(cap, n))


class _Ticket:
    """One in-flight request; completed by the batch thread."""

    __slots__ = ("view", "x", "_event", "emb", "version", "error")

    def __init__(self, view: str, x: np.ndarray):
        self.view = view
        self.x = x
        self._event = threading.Event()
        self.emb: Optional[np.ndarray] = None
        self.version: Optional[int] = None
        self.error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> Dict[str, Any]:
        """Block for the response: ``{"emb": (k,), "version": int}``."""
        if not self._event.wait(timeout):
            raise TimeoutError("projection request timed out")
        if self.error is not None:
            raise self.error
        return {"emb": self.emb, "version": self.version}


class BatchedProjector:
    """Coalesce concurrent projection requests into padded device
    batches, with hot-swap between batches (module docstring)."""

    def __init__(self, model: ServedModel, *, max_batch: int = 64,
                 max_wait_s: float = 0.002):
        if max_batch < 1:
            raise ValueError("max_batch must be positive")
        self._model = model
        self._pending_model: Optional[ServedModel] = None
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_s)
        self._cond = threading.Condition()
        self._queue: deque[_Ticket] = deque()
        self._stop = False
        self.batches = 0
        self.requests = 0
        self.swaps = 0
        self._occupancy_sum = 0
        self._thread = threading.Thread(
            target=self._loop, name="rcca-serve-batch", daemon=True)
        self._thread.start()

    # -- client side ------------------------------------------------------

    def submit(self, view: str, x) -> _Ticket:
        """Queue one row of ``view`` ("a" or "b") for projection;
        returns a ticket whose ``result()`` blocks for the response."""
        if view not in ("a", "b"):
            raise ValueError(f"view must be 'a' or 'b', got {view!r}")
        x = np.asarray(x, dtype=np.float32).reshape(-1)
        want = self._model.Xa.shape[0] if view == "a" \
            else self._model.Xb.shape[0]
        if x.shape[0] != want:
            raise ValueError(
                f"view {view} rows have {want} features, got {x.shape[0]}")
        t = _Ticket(view, x)
        with self._cond:
            if self._stop:
                raise RuntimeError("projector is shut down")
            self._queue.append(t)
            self._cond.notify_all()
        return t

    def project_a(self, x, timeout: Optional[float] = 30.0) -> Dict[str, Any]:
        return self.submit("a", x).result(timeout)

    def project_b(self, x, timeout: Optional[float] = 30.0) -> Dict[str, Any]:
        return self.submit("b", x).result(timeout)

    def swap(self, model: ServedModel) -> None:
        """Install ``model`` at the next batch boundary — the in-flight
        batch finishes on the old version; nothing is dropped."""
        with self._cond:
            self._pending_model = model
            self._cond.notify_all()

    @property
    def model(self) -> ServedModel:
        with self._cond:
            return self._pending_model or self._model

    def stats(self) -> Dict[str, Any]:
        with self._cond:
            return {
                "batches": self.batches, "requests": self.requests,
                "swaps": self.swaps,
                "mean_occupancy": (self._occupancy_sum / self.batches
                                   if self.batches else 0.0),
            }

    def close(self, timeout: float = 30.0) -> None:
        """Drain the queue, then stop the batch thread."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout)

    def __enter__(self) -> "BatchedProjector":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- batch thread -----------------------------------------------------

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._stop:
                    self._cond.wait(0.05)
                if not self._queue:
                    if self._stop:
                        return
                    continue
                if not self._stop and len(self._queue) < self.max_batch \
                        and self.max_wait_s > 0:
                    # brief coalescing window once traffic has started
                    deadline = obs.monotonic() + self.max_wait_s
                    while len(self._queue) < self.max_batch:
                        left = deadline - obs.monotonic()
                        if left <= 0 or self._stop:
                            break
                        self._cond.wait(left)
                if self._pending_model is not None:  # batch boundary
                    self._model = self._pending_model
                    self._pending_model = None
                    self.swaps += 1
                    obs.counter("serve_swap", version=self._model.version)
                batch = [self._queue.popleft()
                         for _ in range(min(len(self._queue),
                                            self.max_batch))]
                model = self._model
                self.batches += 1
                self.requests += len(batch)
                self._occupancy_sum += len(batch)
            self._run_batch(model, batch)

    def _run_batch(self, model: ServedModel, batch: List[_Ticket]) -> None:
        with obs.span("serve_batch", occupancy=len(batch),
                      version=model.version):
            for view in ("a", "b"):
                group = [t for t in batch if t.view == view]
                if not group:
                    continue
                X = model.Xa if view == "a" else model.Xb
                try:
                    rows = np.stack([t.x for t in group])
                    b = _bucket(len(group), self.max_batch)
                    if b > len(group):  # pad to the shape bucket
                        rows = np.concatenate(
                            [rows, np.zeros((b - len(group), rows.shape[1]),
                                            rows.dtype)])
                    fn = _project_jit(X.shape[0], X.shape[1], b)
                    emb = np.asarray(fn(X.astype(jnp.float32), rows))
                    for i, t in enumerate(group):
                        t.emb = emb[i]
                        t.version = model.version
                        t._event.set()
                except BaseException as e:  # complete, never strand
                    for t in group:
                        if not t.done():
                            t.error = e
                            t._event.set()
            obs.counter("serve_occupancy", occupancy=len(batch),
                        max_batch=self.max_batch, version=model.version)


class CorpusIndex:
    """One view's projected corpus, indexed for cross-view top-k.

    Rows are projected once at build time (chunk-streamed from a view
    store — the corpus never materializes beyond its embeddings);
    ``topk`` scores a query embedding from the *other* view with the
    correlation-weighted inner product and returns the best rows.
    """

    def __init__(self, model: ServedModel, view: str, emb: np.ndarray):
        if view not in ("a", "b"):
            raise ValueError(f"view must be 'a' or 'b', got {view!r}")
        self.model = model
        self.view = view
        self.emb = np.asarray(emb, dtype=np.float32)  # (n, k)
        if self.emb.ndim != 2 or self.emb.shape[1] != model.k:
            raise ValueError(
                f"embeddings must be (n, k={model.k}), got {self.emb.shape}")

    @classmethod
    @full_f32
    def from_store(cls, model: ServedModel, store, view: str = "b",
                   *, max_rows: Optional[int] = None) -> "CorpusIndex":
        """Project one view of a store chunk-by-chunk into an index."""
        from repro.store import ViewStoreReader

        reader = store if isinstance(store, ViewStoreReader) \
            else ViewStoreReader(store)
        X = model.Xa if view == "a" else model.Xb
        parts, rows = [], 0
        with obs.span("index_build", view=view, n=reader.n):
            for a, b in reader.iter_chunks():
                block = a if view == "a" else b
                parts.append(np.asarray(
                    jnp.asarray(block, dtype=jnp.float32) @ X))
                rows += block.shape[0]
                if max_rows is not None and rows >= max_rows:
                    break
        emb = np.concatenate(parts)
        return cls(model, view, emb if max_rows is None else emb[:max_rows])

    def topk(self, query_emb, k: int = 10) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k corpus rows for a query embedding from the other view:
        returns ``(indices, scores)``, scores descending."""
        q = np.asarray(query_emb, dtype=np.float32).reshape(-1)
        weighted = q * np.asarray(self.model.rho, dtype=np.float32)
        scores = self.emb @ weighted
        k = min(k, scores.shape[0])
        idx = np.argpartition(-scores, k - 1)[:k]
        order = np.argsort(-scores[idx], kind="stable")
        idx = idx[order]
        return idx, scores[idx]
