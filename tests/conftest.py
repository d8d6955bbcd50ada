"""Test harness: force an 8-device virtual CPU mesh before JAX backend init.

The analog of the reference's FakeStore/fake-process-group trick
(reference: tests/unit_tests/distributed/test_cp_sharder.py) — distributed
semantics are exercised on a host-only mesh with no accelerators.

NOTE: the suite runs without jax's persistent compilation cache. jaxlib
0.9.0 does reload a cached XLA:CPU executable that contains collectives
(re-tested: any shard_map/pp program; the SIGABRT an older jaxlib had on the
second run is gone), but its AOT loader logs a machine-feature mismatch and
a SIGILL warning for every executable it reloads, and the driver's checkout
starts with an empty cache anyway. `utils/compile_cache.py` keeps CPU-pinned
runs uncached for the same reason. Suite wall time is managed by test
tiering (pytest markers) instead.
"""

from automodel_tpu.utils.hostplatform import force_cpu_devices

force_cpu_devices(8)
