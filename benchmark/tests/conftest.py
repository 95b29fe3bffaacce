"""The benchmark's own tests run by hand (``python -m pytest
benchmark/tests``), on the CPU, outside the repo's tier-1 run.  As in
``tests/conftest.py``: the CPU backend with eight virtual devices and
the tests' persistent compile cache, set before JAX loads."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jaxcache-cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
