"""Persistent XLA compilation cache for the launchers.

A full-width decode step takes a minute or more to compile on the chip, and
every launcher process (serve, the replica harness, `chip_smoke.py`)
compiles the same programs. `enable_compile_cache()` points JAX's persistent
cache at one fixed directory so that a second process, or a second serve run
in the same process, loads the executable instead of compiling it again.

Called at the start of each launcher's `main`, never at import: importing a
module must not change JAX's configuration.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is JAX's own setting and wins
    untouched; otherwise the cache lives in `.jax_cache/` at the checkout
    root. The path is part of every entry's key, so it must not move.

    Each key also holds the program's metadata: JAX leaves it out by
    default, and an executable loaded from the cache would then carry the
    op names (`jax.named_scope`) of whichever source compiled it first, so
    a device trace would attribute its ops by stale scopes."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
