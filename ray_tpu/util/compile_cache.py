"""Where this checkout keeps JAX's persistent compilation cache.

Every process that compiles for a device calls `enable_compile_cache()`
once, where it first needs JAX for compute: engine construction, trainer
construction, stage- and train-worker start (replica, stage and train
workers are separate processes, and the setting is per process).

The directory is placed from outside when `JAX_COMPILATION_CACHE_DIR` is
set: JAX reads that variable itself, and this module then sets nothing.
Otherwise it is `<checkout>/.jax_cache`, derived from the package's own
location. The path is part of what makes a later process find an earlier
one's programs, so it never depends on a temporary directory, a process
id, a session name or the time.

A process pinned to the CPU platform (`JAX_PLATFORMS=cpu`: the tests, their
cluster workers) is left alone: the cache is for device compiles, which
take seconds to minutes. On jax 0.9.0 XLA:CPU's loader also logs a
page-long machine-feature warning for every program it reads back.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def cache_dir() -> str:
    """The directory the cache is (or will be) kept in; touches no JAX."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Switch the persistent compilation cache on; returns its directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        # read from the config, not the backend: a train worker calls this
        # before jax.distributed.initialize, which must come first
        if (jax.config.jax_platforms != "cpu"
                and jax.config.jax_compilation_cache_dir != DEFAULT_DIR):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return cache_dir()
