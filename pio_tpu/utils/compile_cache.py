"""Where this checkout keeps JAX's persistent compilation cache.

One home for the rule every entry point follows (the CLI, spawned pool
workers, ``benchmarks/``, ``__graft_entry__.py``, ``chip_smoke.py``'s
children): an operator who exports ``JAX_COMPILATION_CACHE_DIR`` owns the
location and the program sets nothing — JAX reads the variable itself.
Otherwise the cache lives at ``<checkout>/.jax_cache``, derived from this
package's location. The directory is part of every cache key's lookup, so
it must be the same path on every run: never a temp dir, a pid or a time.

Entries are keyed **with** op metadata. JAX's default key leaves metadata
out, so a program that differs from a cached one only in its
``jax.named_scope`` names (the trainers' ``als.*`` scopes are such names)
finds the old executable and runs it without them: every profile of it
shows the names of whenever it was compiled (measured on the v5e, PR 26: a
cache filled before the scopes existed left 17.48 of 17.48 device seconds
unscoped). With metadata in the key an executable always carries the names
of the code that loaded it; the price is a recompile when a traced line
moves. Source paths enter the key relative to the checkout, so a checkout
at another path still hits.
"""

from __future__ import annotations

import os
import re
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
    jax = sys.modules.get("jax")
    for name, value in (
        ("jax_compilation_cache_include_metadata_in_key", True),
        ("jax_hlo_source_file_canonicalization_regex",
         "^" + re.escape(checkout_root() + os.sep)),
    ):
        if name.upper() in os.environ:
            continue  # the operator's, or an earlier call's: jax reads it
        os.environ[name.upper()] = "1" if value is True else value
        if jax is not None:
            jax.config.update(name, value)
    path = os.environ.get(ENV)
    if path:
        return path
    path = os.path.join(checkout_root(), ".jax_cache")
    os.environ[ENV] = path
    if jax is not None:
        # jax read the (then unset) variable at import; tell it directly
        jax.config.update("jax_compilation_cache_dir", path)
    return path
