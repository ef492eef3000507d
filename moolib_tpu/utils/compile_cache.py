"""Persistent XLA compile cache placement (docs/RESILIENCE.md "Recovery budget").

A restarted peer re-compiles the *same* programs its previous incarnation
already compiled, and every process of one chip-tool call compiles what its
siblings did; jax's on-disk compilation cache removes both.  The cache path
is part of the cache key, so it has to be the same for every process that
should share entries.  Placement is decided outside the program:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself at import, in every
  entry point, bench script and child process that inherits the environment.
  This module then sets nothing.
- unset: one fixed directory inside the checkout (``<repo>/.jax_cache``,
  git-ignored) — never a temporary name, a pid or a per-run directory.

Thresholds (what is worth persisting) are jax's own
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` /
``JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES``.
"""

from __future__ import annotations

import os

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def init_compile_cache() -> str:
    """Make sure jax's persistent compilation cache has a directory, and
    return it.  Call before the first jit of the process (jax decides once,
    at its first compile, whether the cache is in use).  Idempotent."""
    placed = os.environ.get(_ENV_DIR)
    if placed:
        return placed
    import jax

    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
