"""Test configuration: force an 8-device virtual CPU mesh before JAX loads.

The tests run on the CPU backend — ``JAX_PLATFORMS=cpu`` with eight
virtual devices, so the multi-chip sharding paths are exercised without
an accelerator.  The chip is ``chip_smoke.py``'s business, never a
test's.
"""

import os

# Hard override, not setdefault: whatever the caller's environment
# names, the suite runs on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent compile cache: XLA compiles dominate the suite's wall time
# (most of it building the same tensor-engine programs every run);
# cached re-runs skip straight to execution.  Set through the
# environment, before JAX loads, so that tpu/compile_cache.py leaves it
# alone and every child process a test starts inherits the same place.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jaxcache-cpu")

import jax  # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

# A span or mark whose name is not in telemetry.PHASES raises in the
# tests (ISSUE 25): the table is the one place names live.
from dslabs_tpu.tpu import telemetry as _telemetry  # noqa: E402

_telemetry.check_names = True
