"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — required because the dry-run
must set XLA_FLAGS before any jax initialization.

Every mesh is built with ``AxisType.Auto`` axes.  ``jax.make_mesh``
defaults to ``Explicit`` axes, under which a plain ``x @ y`` whose
contracting dimension is sharded raises ``ShardingTypeError``; the
drivers and evaluation code here leave such shardings to the compiler.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("pod", "data", "model")):
    """Small mesh over however many (possibly fake) devices exist —
    used by tests and CPU examples."""
    n = len(jax.devices())
    if shape is None:
        # greedy: pod=1, square-ish data×model
        m = 1
        while (m * 2) ** 2 <= n:
            m *= 2
        shape = (1, max(1, n // m), m) if len(axes) == 3 else (max(1, n // m), m)
    return _auto_mesh(shape, axes[-len(shape):] if len(shape) < len(axes) else axes)


def data_axes(mesh) -> tuple:
    """The row/batch axes present in this mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis(mesh):
    return "model" if "model" in mesh.axis_names else None
