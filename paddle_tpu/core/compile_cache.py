"""Where the persistent XLA compile cache lives — decided from outside.

Every entry point that compiles on the chip (chip_smoke.py,
benchmark/run.py, the serving replica launcher, incubate.autotune)
calls ``configure()`` before its first compile:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module
  touches no cache setting, so whoever runs the program owns the
  placement (and what it cached is found again by the next run).
- unset: one fixed directory inside the checkout (git-ignored). The
  path is part of the cache key, so it is never a temp name, a pid or
  a timestamp — a directory that moves never hits.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def configure():
    """Point JAX at the compile cache; returns the directory in use."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # cache every program, not only those that took over JAX's default
    # 1 s to compile: a program near that threshold lands in the cache
    # on some runs and not on others (seen on the chip: a second smoke
    # run added one entry), and a warm run should compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_DIR


def entries(path):
    """Names of the cached executables under ``path`` (empty when the
    directory does not exist yet) — chip_smoke.py prints how many there
    were before a run and which ones it added, so a second run's "adds
    no entry" is checkable."""
    if not os.path.isdir(path):
        return []
    return sorted(n for n in os.listdir(path) if n.endswith("-cache"))
