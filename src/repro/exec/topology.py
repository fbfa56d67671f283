"""Execution topologies: WHERE the canonical pass structure is cut.

Every topology runs the same algorithm over the same canonical
accumulation structure (chunk → merge group → pairwise tree, see
:mod:`repro.exec.accumulate`); they differ only in which physical
resources fold which merge groups:

- :class:`Local` — one process, one device: chunks fold sequentially,
  groups push straight into the pairwise tree.
- :class:`Sharded` — one process, shard_map over the local device
  mesh: whole merge groups are folded data-parallel (one group per
  device per step); group sums still enter the SAME tree in the SAME
  order, so the result is bitwise that of :class:`Local`.  A non-None
  ``col_axis`` additionally shards the FEATURE axis for resident-mode
  fits (the ``repro.core.rcca_dist`` path — feature psums reassociate
  the row sums, so that mode trades bitwise reproducibility for
  per-device HBM headroom).
- :class:`Cluster` — one process per worker, each folding whole merge
  groups sequentially and publishing per-group partials; the
  coordinator streams the tree from disk.
- :class:`Hybrid` — the ROADMAP's row-parallelism × device-parallelism
  marriage: cluster workers that each run their merge groups through
  shard_map over their local device mesh and publish already-reduced
  group partials in the same versioned-partial format.  The
  coordinator's fixed tree merge — and therefore the final result —
  is bit-identical to single-process streaming for any
  (workers × devices) layout.

Topologies are frozen declarative values: they carry the layout, not
operational knobs (timeouts, checkpoint periods stay with the drivers).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Union

import numpy as np


@dataclasses.dataclass(frozen=True)
class Local:
    """Single-process, single-device sequential execution."""

    name: str = dataclasses.field(default="local", init=False, repr=False)


@dataclasses.dataclass(frozen=True)
class Sharded:
    """Single-process execution over the local device mesh.

    ``mesh``:     a ``jax.sharding.Mesh`` whose FIRST axis is the
                  group-parallel axis; ``None`` builds a 1-D mesh over
                  all visible devices at fit time (use
                  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
                  to fake N devices on CPU).
    ``col_axis``: optional mesh axis name sharding the FEATURE
                  dimension — only meaningful for resident-mode fits
                  through ``repro.core.rcca_dist`` (streaming fits
                  require ``col_axis=None``; feature psums break the
                  bitwise contract).
    """

    mesh: Optional[object] = None  # jax.sharding.Mesh; untyped to stay importable pre-jax
    col_axis: Optional[str] = None
    name: str = dataclasses.field(default="sharded", init=False, repr=False)

    def build_mesh(self) -> object:
        """The group-parallel mesh: the given one, or all local devices
        on a single ``"dev"`` axis."""
        if self.mesh is not None:
            return self.mesh
        import jax
        from jax.sharding import Mesh

        devs = jax.devices()
        return Mesh(np.array(devs), ("dev",))

    @property
    def group_axis(self) -> str:
        mesh = self.mesh
        if mesh is None:
            return "dev"
        return mesh.axis_names[0]


@dataclasses.dataclass(frozen=True)
class Cluster:
    """Multi-process execution: ``n_workers`` map tasks per pass, each
    a single-device process (``python -m repro.cluster.worker``)."""

    n_workers: int = 2
    name: str = dataclasses.field(default="cluster", init=False, repr=False)

    def __post_init__(self) -> None:
        if self.n_workers < 1:
            raise ValueError("need at least one worker")

    @property
    def devices_per_worker(self) -> int:
        return 1


@dataclasses.dataclass(frozen=True)
class Hybrid:
    """Row parallelism across worker processes × group parallelism
    across each worker's local device mesh.  Workers are spawned with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=
    devices_per_worker``, which forces the CPU platform's device count:
    the layout is a CPU rehearsal.  The coordinator refuses TPU hosts,
    where one process per chip leaves no chip for the workers."""

    n_workers: int = 2
    devices_per_worker: int = 4
    name: str = dataclasses.field(default="hybrid", init=False, repr=False)

    def __post_init__(self) -> None:
        if self.n_workers < 1:
            raise ValueError("need at least one worker")
        if self.devices_per_worker < 1:
            raise ValueError("need at least one device per worker")


Topology = Union[Local, Sharded, Cluster, Hybrid]


def as_topology(spec: Union[str, Topology], **kwargs: object) -> Topology:
    """Coerce a CLI-style spec (``"local"``, ``"sharded"``,
    ``"cluster"``, ``"hybrid"``) or an existing topology value."""
    if isinstance(spec, (Local, Sharded, Cluster, Hybrid)):
        return spec
    table = {"local": Local, "sharded": Sharded, "cluster": Cluster,
             "hybrid": Hybrid}
    if spec not in table:
        raise ValueError(
            f"unknown topology {spec!r}; expected one of {sorted(table)}")
    return table[spec](**kwargs)
