"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points that compile for the accelerator (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable` before their first compile, so a
second run of the same shapes loads its programs instead of compiling them.
Nothing calls it on ``import repro``: tests that compile for a described
chip stay off the cache.
"""

from __future__ import annotations

import os

import jax

#: the default cache directory: ``.jax_cache/`` at the repository root — a
#: fixed path, so every run of this checkout finds what earlier runs wrote
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads it
    itself, and no other directory is set here); otherwise the cache goes
    to :data:`DEFAULT_DIR`.  Every program is cached, however short its
    compile: the keyed plane compiles many small programs per chunk shape.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
