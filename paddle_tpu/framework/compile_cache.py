"""JAX's persistent compilation cache, placeable from outside.

A cold 1.345B train step plus the serving programs is minutes of
compile.  The rule, in ONE place: where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it and this module configures nothing; otherwise the
cache lives at ``<checkout>/.jax_cache`` — a fixed path derived from the
package's location (the path is part of the cache key, so a directory
named after a pid, a time or a temp file would never hit).
"""

from __future__ import annotations

import os

__all__ = ["compile_cache_dir", "enable_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """The directory in force: the environment's, else the checkout's."""
    return os.environ.get(_ENV) or _DEFAULT


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process (idempotent) and
    return its directory.  Entry points call it before their first
    compile: ``chip_smoke.py``, ``GenerationServer.start``;
    ``distributed.launch`` hands :func:`compile_cache_dir` to its
    workers through the environment."""
    if not os.environ.get(_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    return compile_cache_dir()
