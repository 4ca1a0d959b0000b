"""Where compiled programs are kept between processes.

The serve path has no warm-up: the first request of every shape compiles
a 28-layer scanned program, and on a cold start that is most of the time
to the first answers. JAX's persistent compilation cache removes it from
the second process on — if every process looks in the same place.

One rule, kept here so no entry point grows its own: whoever set
``JAX_COMPILATION_CACHE_DIR`` placed the cache, and JAX reads that
variable itself; otherwise the cache is one fixed directory inside the
checkout. The path is part of JAX's cache key, so it is never a
temporary name, a pid or a time — a directory that moves never hits.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_MIN_SECS_VAR = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def ensure_compile_cache() -> str:
    """Return the compile-cache directory this process will use, placing
    it at :data:`DEFAULT_DIR` when nothing outside did.

    Call at an entry point, before the first compile. Safe before or
    after ``import jax``: the variable covers a later import (and child
    processes), the config update covers a jax that already read its
    environment."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    path = str(DEFAULT_DIR)
    os.environ[ENV_VAR] = path
    # Keep every program, not only those that took over a second: a cold
    # and a warm run then hold the same entries and can be compared.
    keep_all = _MIN_SECS_VAR not in os.environ
    if keep_all:
        os.environ[_MIN_SECS_VAR] = "0"
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
        if keep_all:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def cache_entries(path: str) -> int:
    """Number of files under the cache directory (0 when it is absent)."""
    return sum(len(files) for _, _, files in os.walk(path))
