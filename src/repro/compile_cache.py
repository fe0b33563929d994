"""Where JAX keeps compiled programs between processes.

A whole-network executor of AlexNet or VGG-16 takes seconds to compile
for a TPU; JAX's persistent compilation cache lets the next process on
the same machine load it instead.  The cache key includes the
directory, so the directory is fixed: the one ``JAX_COMPILATION_CACHE_DIR``
names, else ``.jax_compile_cache/`` at the root of this checkout
(git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory, used when the environment names none
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"

#: compiles shorter than this are not cached: the executors (seconds)
#: are, the float pass's many sub-second eager op compiles are not
MIN_COMPILE_SECS = 2.0

#: size limit of the checkout's cache, least recently used entries
#: evicted first.  A VGG-16 executor, its int8 weights compiled in as
#: constants, serializes to about 332 MB (331,671,186 bytes on a v5e);
#: every program of a ``chip_smoke.py`` run (three per model) fits.
MAX_CACHE_BYTES = 4 * 1024 ** 3


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its
    directory.  With ``JAX_COMPILATION_CACHE_DIR`` set that directory is
    used as it is, with the settings the environment gives; otherwise
    the checkout's directory, caching every compile of at least
    :data:`MIN_COMPILE_SECS` up to :data:`MAX_CACHE_BYTES` in all."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        jax.config.update("jax_compilation_cache_dir", env_dir)
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    jax.config.update("jax_compilation_cache_max_size", MAX_CACHE_BYTES)
    return str(CHECKOUT_CACHE_DIR)
