"""Compilation bookkeeping shared by the entry points and the tests.

``use_compile_cache`` turns on JAX's persistent compilation cache. Call it
from an entry point's ``main()``, never at import: importing must leave the
process's JAX configuration alone. ``count_compiles`` counts the
executables JAX builds or loads inside a block, through the public
``jax.monitoring`` events.
"""
from __future__ import annotations

import contextlib
import os

import jax

# <checkout>/src/repro/launch/compilation.py -> <checkout>
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def use_compile_cache() -> str:
    """Persist compiled executables across processes; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache is the fixed
    ``<checkout>/.jax_cache``: the directory is part of the cache key, so a
    path made from a temp name, a pid or the time would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def count_compiles():
    """Yield a one-element list that counts the XLA executables compiled, or
    loaded from the persistent cache, while the block runs (read ``n[0]``
    after it). Every compile in the process counts, eager ops included."""
    n = [0]

    def on_compile(event, _secs, **_kw):
        if event == _BACKEND_COMPILE:
            n[0] += 1

    def on_hit(event, **_kw):
        if event == _CACHE_HIT:
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    jax.monitoring.register_event_listener(on_hit)
    try:
        yield n
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        jax.monitoring.unregister_event_listener(on_hit)
