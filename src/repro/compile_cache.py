"""JAX's persistent compilation cache, placed by the entry points.

``chip_smoke.py``, ``bench/run.py`` and the examples call
:func:`enable` once before their first compile.  Importing ``repro``
never touches the cache settings, so library users and the test suite
keep JAX's defaults.

Where the cache goes:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own reading of it stands and
  no other directory is configured;
* otherwise the checkout's git-ignored ``.jax_cache``: a fixed path, so a
  later run of the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

#: <checkout>/.jax_cache (this file is <checkout>/src/repro/compile_cache.py)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on and return the directory it uses.

    Every compile is cached, however short: the engine's Pallas kernels
    each compile in well under JAX's default one-second threshold, and
    there are many of them."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
