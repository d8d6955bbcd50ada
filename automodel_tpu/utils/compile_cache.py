"""JAX's persistent compilation cache, at one place for every entry point.

A chip machine starts cold and compiling is a large part of a cold run, so
`cli/app.py:main`, `chip_smoke.py` and `benchmark/run.py` each call
`enable_compile_cache()` once, before anything compiles. The cache's path
is part of its key, so it never moves: where `JAX_COMPILATION_CACHE_DIR`
is set JAX reads it itself and this module sets no other; otherwise the
cache lives in `.jax_cache/` at the root of the checkout. Either way the
key includes each op's metadata (see below).
"""

from __future__ import annotations

import os

import jax

#: `<checkout>/.jax_cache` (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def _pinned_to_cpu() -> bool:
    return jax.config.jax_platforms == "cpu"


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on; returns its directory, or
    None on a run pinned to the CPU. Touches no JAX backend, so it is safe
    before `jax.distributed.initialize`.

    CPU runs stay uncached: jaxlib 0.9.0 reloads a cached XLA:CPU executable
    correctly (collectives included — the abort older jaxlibs had is gone),
    but its AOT loader logs a machine-feature mismatch and a SIGILL warning
    for every executable it reloads.
    """
    if _pinned_to_cpu():
        return None
    # JAX leaves an op's metadata (its `jax.named_scope` path among it) out
    # of the cache key by default, so a program compiled before a scope was
    # added would be served from the cache to the code that added it, the
    # scope gone from every profiler trace: the serve step's `serve.*`
    # scopes are what its per-layer readings are cut by. The price: an edit
    # that moves a traced line (source locations are metadata too)
    # recompiles the programs traced through it, once.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
