"""The device plane's door to JAX, written for the one installation there
is (Python 3.12, jax/jaxlib 0.9.0).

Every module that builds a compiled program reaches JAX through here:
the ``shard_map`` sites import the re-export below, and the others
(``flash_attention``, ``make_train_step``, ``make_mesh``) call
:func:`ensure_compile_cache` themselves. Either way the persistent
compilation cache is placed before the process's first compilation.
Never imported from ``fiber_tpu/__init__.py`` — host workers stay
JAX-free.
"""

import os

import jax
from jax import shard_map  # noqa: F401 - re-export for the shard_map sites

#: Where compiled programs persist when nobody said otherwise: one fixed
#: directory inside the checkout, derived from this file's location. The
#: path is part of what a cache hit depends on, so it must not move
#: between runs (no tempfile, pid, timestamp, $HOME or cwd).
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def ensure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory
    in effect. A directory configured from outside wins —
    ``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself) or an
    earlier ``jax.config.update`` — and then this sets nothing.
    Otherwise the cache goes to :data:`COMPILE_CACHE_DIR`. Idempotent;
    JAX initializes the cache lazily at the next compilation, so a
    program compiled before this call merely went uncached."""
    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


ensure_compile_cache()
