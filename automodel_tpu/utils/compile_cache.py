"""JAX's persistent compilation cache, at one place for every entry point.

A chip machine starts cold and compiling is a large part of a cold run, so
`cli/app.py:main`, `chip_smoke.py` and `bench.py` each call
`enable_compile_cache()` once, before anything compiles. The cache's path
is part of its key, so it never moves: where `JAX_COMPILATION_CACHE_DIR`
is set JAX reads it itself and this module sets nothing; otherwise the
cache lives in `.jax_cache/` at the root of the checkout.
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
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
