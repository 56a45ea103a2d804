"""Where the entry points keep JAX's persistent compilation cache."""

from __future__ import annotations

import os
from pathlib import Path

# fixed, inside the checkout (and gitignored): the directory is part of
# what a later run has to find again, so it never carries a temporary
# name, a process id or a time
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.  Called from the ``main()`` of the entry
    points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``),
    never at import and never by tests.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
