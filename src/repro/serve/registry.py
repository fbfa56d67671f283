"""Versioned CCA model registry: atomic publish, content hashes.

Registry layout (same staging+rename discipline as ``repro.store`` —
a reader can never observe a torn artifact)::

    registry/
      <name>/
        v00001/                # one save_pytree dir per version
          manifest.json        #   Xa/Xb/rho/Qa/Qb leaves + metadata
          Xa.npy ...
        v00002/
        current.json           # atomically-replaced version pointer

Each version directory is written by ``repro.ckpt.save_pytree`` (tmp +
rename) and is immutable once published; ``current.json`` is the only
mutable file and flips via ``os.replace``.  Version metadata carries a
content hash (sha256 over the projection leaves), the store
fingerprint + algo binding inherited from the fit, and the parent
version — the provenance chain a drift investigation walks.

``prune(name, keep=N)`` is the garbage collector: it removes old
versions while never touching the current version, its recorded parent
(the rollback target), or the newest N — and deletes via
rename-then-rmtree so a concurrent reader can never open a half-deleted
artifact.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
import shutil
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.ckpt import load_flat, load_metadata, save_pytree
from repro.core.linalg import full_f32


_LEAVES = ("Xa", "Xb", "rho", "Qa", "Qb")
_VDIR_RE = re.compile(r"^v(\d{5})$")


def _content_hash(arrays: Dict[str, np.ndarray]) -> str:
    h = hashlib.sha256()
    for name in _LEAVES:
        arr = np.ascontiguousarray(np.asarray(arrays[name]))
        h.update(name.encode())
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


@dataclasses.dataclass(frozen=True)
class ServedModel:
    """One immutable published model version, loaded for serving."""

    name: str
    version: int
    Xa: jnp.ndarray  # (da, k) view-A projection
    Xb: jnp.ndarray  # (db, k)
    rho: jnp.ndarray  # (k,) canonical correlations
    Qa: jnp.ndarray
    Qb: jnp.ndarray
    meta: Dict[str, Any]

    @property
    def k(self) -> int:
        return int(self.Xa.shape[1])

    @full_f32
    def project_a(self, x) -> jnp.ndarray:
        """x ↦ Φᵃx: rows of view A into the canonical space."""
        return jnp.asarray(x) @ self.Xa

    @full_f32
    def project_b(self, x) -> jnp.ndarray:
        return jnp.asarray(x) @ self.Xb

    def score(self, ea, eb) -> jnp.ndarray:
        """Correlation score of paired embeddings: Σ_k ρ_k·φᵃ_k·φᵇ_k
        (rows of ``ea``/``eb`` are already-projected pairs)."""
        return jnp.sum(jnp.asarray(ea) * jnp.asarray(eb) * self.rho, axis=-1)


class ModelRegistry:
    """Versioned model artifacts with atomic publish + flip.

    ``publish`` writes the next version directory (atomic via
    save_pytree's staging rename), then flips ``current.json`` with
    ``os.replace`` — readers either see the old current or the new one,
    never a half-published artifact.  Versions are immutable; rollback
    is ``set_current(name, older_version)``.
    """

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)

    # -- paths ------------------------------------------------------------

    def _model_dir(self, name: str) -> str:
        if not name or "/" in name or name.startswith("."):
            raise ValueError(f"bad model name {name!r}")
        return os.path.join(self.root, name)

    def _version_dir(self, name: str, version: int) -> str:
        return os.path.join(self._model_dir(name), f"v{version:05d}")

    # -- enumeration ------------------------------------------------------

    def models(self) -> List[str]:
        return sorted(d for d in os.listdir(self.root)
                      if os.path.isdir(os.path.join(self.root, d)))

    def versions(self, name: str) -> List[int]:
        d = self._model_dir(name)
        if not os.path.isdir(d):
            return []
        out = []
        for entry in os.listdir(d):
            m = _VDIR_RE.match(entry)
            if m and os.path.exists(os.path.join(d, entry, "manifest.json")):
                out.append(int(m.group(1)))
        return sorted(out)

    def current_version(self, name: str) -> Optional[int]:
        path = os.path.join(self._model_dir(name), "current.json")
        try:
            with open(path) as f:
                return int(json.load(f)["version"])
        except (FileNotFoundError, KeyError, ValueError):
            return None

    # -- publish ----------------------------------------------------------

    def publish(self, name: str, result, *, fit_meta: Optional[dict] = None,
                parent: Optional[int] = None,
                make_current: bool = True) -> int:
        """Publish an ``RCCAResult`` (or anything with Xa/Xb/rho/Qa/Qb
        attributes) as the next version of ``name``; returns it.

        ``fit_meta`` is the binding/provenance to record (a FitState's
        ``meta`` — store fingerprint, algo, engine); ``parent`` the
        version this one refitted from (defaults to the current one).
        """
        arrays = {leaf: np.asarray(jax.device_get(getattr(result, leaf)))
                  for leaf in _LEAVES}
        versions = self.versions(name)
        version = (versions[-1] + 1) if versions else 1
        if parent is None:
            parent = self.current_version(name)
        meta = {
            "name": name, "version": version, "parent": parent,
            "content_sha256": _content_hash(arrays),
            "k": int(arrays["Xa"].shape[1]),
            "da": int(arrays["Xa"].shape[0]),
            "db": int(arrays["Xb"].shape[0]),
        }
        if fit_meta:
            meta["fit"] = {k: v for k, v in fit_meta.items()
                           if k in ("engine", "omega", "merge_group",
                                    "algo", "fingerprint", "n")}
        vdir = self._version_dir(name, version)
        os.makedirs(self._model_dir(name), exist_ok=True)
        save_pytree(arrays, vdir, metadata=meta)  # atomic (tmp + rename)
        obs.counter("registry_publish", model=name, version=version)
        if make_current:
            self.set_current(name, version)
        return version

    def set_current(self, name: str, version: int) -> None:
        """Atomically flip the served-version pointer."""
        if version not in self.versions(name):
            raise ValueError(f"{name!r} has no published version {version}")
        d = self._model_dir(name)
        tmp = os.path.join(d, f".current.{os.getpid()}.tmp")
        with open(tmp, "w") as f:
            json.dump({"version": version}, f)
        os.replace(tmp, os.path.join(d, "current.json"))

    # -- load -------------------------------------------------------------

    def load(self, name: str, version: Optional[int] = None) -> ServedModel:
        """Load a version (default: current) for serving, verifying the
        content hash — a corrupted artifact fails here, not in traffic."""
        if version is None:
            version = self.current_version(name)
            if version is None:
                versions = self.versions(name)
                if not versions:
                    raise FileNotFoundError(
                        f"no published versions of {name!r} under "
                        f"{self.root!r}")
                version = versions[-1]
        vdir = self._version_dir(name, version)
        flat, meta = load_flat(vdir)
        got = _content_hash(flat)
        if got != meta.get("content_sha256"):
            raise ValueError(
                f"{name} v{version} content hash mismatch: artifact "
                f"corrupted ({got[:12]}… != "
                f"{str(meta.get('content_sha256'))[:12]}…)")
        return ServedModel(
            name=name, version=version,
            Xa=jnp.asarray(flat["Xa"]), Xb=jnp.asarray(flat["Xb"]),
            rho=jnp.asarray(flat["rho"]), Qa=jnp.asarray(flat["Qa"]),
            Qb=jnp.asarray(flat["Qb"]), meta=meta)

    def meta(self, name: str, version: int) -> dict:
        return load_metadata(self._version_dir(name, version))

    # -- garbage collection ----------------------------------------------

    def prune(self, name: str, *, keep: int) -> List[int]:
        """Delete old versions of ``name``, keeping the newest ``keep``
        plus everything a rollback could land on; returns the versions
        removed (ascending).

        Protected, never pruned: the current version, its recorded
        ``parent`` (the rollback target ``set_current`` lands on when a
        swap goes bad), and the newest ``keep`` versions.  Deletion is
        reader-safe: a version directory is first renamed out of the
        registry namespace (atomic, so :meth:`versions` / :meth:`load`
        never see a half-deleted artifact — a concurrent ``load`` either
        opened the manifest before the rename and reads the moved inode,
        or misses the version entirely) and only then removed.
        """
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        versions = self.versions(name)
        protected = set(versions[-keep:])
        cur = self.current_version(name)
        if cur is not None:
            protected.add(cur)
            try:
                parent = self.meta(name, cur).get("parent")
            except (OSError, ValueError):
                parent = None
            if parent is not None:
                protected.add(int(parent))
        pruned: List[int] = []
        d = self._model_dir(name)
        for version in versions:
            if version in protected:
                continue
            vdir = self._version_dir(name, version)
            trash = os.path.join(d, f".trash.v{version:05d}.{os.getpid()}")
            try:
                os.rename(vdir, trash)
            except FileNotFoundError:
                continue  # concurrent prune got it first
            shutil.rmtree(trash, ignore_errors=True)
            pruned.append(version)
        if pruned:
            obs.counter("registry_prune", model=name, n=len(pruned),
                        kept=len(versions) - len(pruned))
        return pruned
