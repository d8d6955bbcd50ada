"""Force the host (CPU) JAX platform with a virtual device count.

Single home for the recipe used by tests/conftest.py and
__graft_entry__.py. `JAX_PLATFORMS=cpu` alone selects the CPU; this adds
the virtual device count a multi-device mesh needs. Must be called BEFORE
the first JAX backend initialization (importing jax is fine — backends are
lazy).
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_devices(n_devices: int = 1) -> None:
    """Point JAX at an n-device virtual CPU platform, replacing any stale
    device count already present in XLA_FLAGS."""
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "force_cpu_devices() called after a JAX backend was already "
            "initialized — the CPU platform / device count cannot take "
            "effect. Call it before any jax.devices()/computation."
        )
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"{_COUNT_FLAG}={n_devices}"
    if _COUNT_FLAG in flags:
        flags = re.sub(rf"{_COUNT_FLAG}=\d+", flag, flags)
    else:
        flags = (flags + " " + flag).strip()
    os.environ["XLA_FLAGS"] = flags
    # jax read JAX_PLATFORMS when it was imported; set the config too
    jax.config.update("jax_platforms", "cpu")
