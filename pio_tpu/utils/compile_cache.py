"""Where this checkout keeps JAX's persistent compilation cache.

One home for the rule every entry point follows (the CLI, spawned pool
workers, ``bench.py``, ``__graft_entry__.py``, ``chip_smoke.py``'s
children): an operator who exports ``JAX_COMPILATION_CACHE_DIR`` owns the
location and the program sets nothing — JAX reads the variable itself.
Otherwise the cache lives at ``<checkout>/.jax_cache``, derived from this
package's location. The directory is part of every cache key's lookup, so
it must be the same path on every run: never a temp dir, a pid or a time.
"""

from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"


def checkout_root() -> str:
    """The directory that holds the ``pio_tpu`` package."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def place_compile_cache() -> str:
    """Settle the compile-cache directory before the first backend use;
    returns it. Safe to call repeatedly and before or after ``import
    jax``. The default is exported through the environment so spawned
    workers inherit the same directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    path = os.path.join(checkout_root(), ".jax_cache")
    os.environ[ENV] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read the (then unset) variable at import; tell it directly
        jax.config.update("jax_compilation_cache_dir", path)
    return path
