"""Where the entry points keep jax's persistent compilation cache.

A cold run of the chip path spends much of its time compiling, so every
entry point (``chip_smoke.py``, ``repro.launch.cca_fit``,
``repro.launch.cca_serve``, ``repro.cluster.worker``) calls
:func:`use_compile_cache` before its first compile.  Library modules
never call it: placing the cache is the deployment's choice.
"""

from __future__ import annotations

import os
from pathlib import Path

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path inside the checkout, so that the next run finds it.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    this sets nothing.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
