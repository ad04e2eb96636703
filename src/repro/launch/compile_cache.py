"""JAX's persistent compilation cache: one rule for every entry point.

``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.train`` and
``benchmarks.run`` call :func:`enable_compile_cache` before they compile
anything, so a second run of the same program on the same machine skips
the minutes a cold TPU compile costs.

Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
cache goes there; no code sets another path.  Otherwise the cache lives
at ``<checkout>/.jax_cache`` — a fixed path, never a temporary, pid- or
time-based one, because a directory that moves never hits.

Entries are keyed on the programs' op metadata too.  JAX leaves it out
of the key by default, but a cached executable keeps the metadata it
was compiled with: the ``repro.*`` scopes (``obs.span``) that a profile
names each device op by would otherwise be those of whatever version of
the code filled the entry first.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_ENV_VAR", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; return it."""
    path = os.environ.get(CACHE_ENV_VAR)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path
