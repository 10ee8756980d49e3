"""Where the program keeps JAX's persistent compilation cache.

The cache is keyed on its path, so it lives at one fixed place: the
directory named by ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads the variable itself, and nothing here overrides it), and otherwise
``.jax_cache`` at the root of the checkout (listed in ``.gitignore``).
Entry points call :func:`configure_compile_cache` before their first
compile.
"""
from __future__ import annotations

import os
import re

import jax

CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CHECKOUT_CACHE_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.

    The cache key keeps the programs' metadata: by default JAX strips it,
    and a program whose named scopes (the op names a device trace shows)
    changed would be served the executable compiled before the change.
    Source files enter the metadata relative to the checkout, so that the
    same code checked out elsewhere still hits the cache."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT_ROOT + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
