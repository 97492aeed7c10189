"""The persistent compilation cache, placed from outside.

One function, called by every entry point (``examples/*``, ``bench.py``,
``chip_smoke.py``) before the first compile. The directory is part of the
cache's key, so it must never move between runs:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, jax itself reads it and this
  module sets nothing — whoever runs the program (a driver, a chip tool)
  decides where the cache lives and finds it again on the next call;
* where it is not, the cache is ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``), derived from the package's location like
  ``telemetry.provenance`` derives the repo root. Never a temporary,
  per-process or time-stamped path: such a cache cannot hit.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR_ENV", "cache_entry_count", "enable_compile_cache"]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Call before the first compile (jax fixes the cache when it first
    compiles). With ``JAX_COMPILATION_CACHE_DIR`` set this only reports the
    directory jax already took from the environment."""
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return placed
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entry_count(path: str) -> int:
    """How many entries the cache directory holds (0 when it does not exist
    yet) — reported by ``chip_smoke.py`` next to cold and warm compile times."""
    try:
        return sum(1 for entry in os.scandir(path) if entry.is_file())
    except FileNotFoundError:
        return 0
