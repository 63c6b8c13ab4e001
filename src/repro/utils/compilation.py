"""Compilation bookkeeping for the entry points: where the persistent
compilation cache lives, and a count of the executables a block builds.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from pathlib import Path

import jax

#: the environment variable through which JAX takes its cache directory
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the cache's home when the environment names none: a fixed path inside
#: the checkout (the path is part of every cache key, so a directory that
#: moved between runs would never hit)
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"

#: the event JAX records once per executable it builds (a persistent
#: cache hit included): every miss of the in-memory jit cache
_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` is left as it is (JAX reads it
    itself); otherwise the cache goes to ``REPO_CACHE``.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)


@dataclasses.dataclass
class CompileCounter:
    """Executables built so far inside a ``count_compiles`` block, and
    the seconds their backend compiles (or cache loads) took."""
    count: int = 0
    seconds: float = 0.0


@contextlib.contextmanager
def count_compiles():
    """Count the executables JAX builds inside the block::

        with count_compiles() as ctr:
            step(x)
        assert ctr.count == 0       # every call hit the jit cache
    """
    ctr = CompileCounter()

    def listener(event, duration_secs, **kwargs):
        if event == _BUILD_EVENT:
            ctr.count += 1
            ctr.seconds += duration_secs

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield ctr
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
