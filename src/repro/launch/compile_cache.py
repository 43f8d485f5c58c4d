"""JAX's persistent compilation cache for the launchers and ``chip_smoke.py``.

A compiled program is found again only under the directory it was written
to, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` where it is set
(JAX reads it itself), and ``<checkout>/.jax_cache`` otherwise.

A key includes the program's metadata (JAX leaves it out by default).
Without it, an executable found again carries the ``op_name`` metadata of
whichever code compiled it first, so a change to the named scopes alone
would not reach the profiler's trace.  File names in that metadata are cut
to their base name, so a key does not depend on where the checkout lies.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", r".*/")
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
