"""The benchmark's own tests run by hand (``python -m pytest
benchmark/tests``), on the CPU, outside the repo's tier-1 run.  As in
``tests/conftest.py``: the CPU backend with eight virtual devices and
the tests' persistent compile cache, set before JAX loads."""

import os
import sys

import pytest

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


@pytest.fixture(autouse=True)
def every_compile_is_cached():
    """The runner counts a program compiled AND WRITTEN to the persistent
    cache inside the window as a miss, and JAX writes only what took
    longer than a floor to compile (1 s in these tests).  On the CPU the
    lab twins' programs compile in about that: one that stayed under the
    floor in set-up and passed it in the window would fail the run by
    chance (the lab 3 rehearsals did; in PR 29's whole run a lab 1
    rehearsal came out not correct once, its window three times as slow
    as usual, and passed alone).  With the floor at 0 everything set-up
    compiles is cached, and a miss in the window is a program set-up
    never saw."""
    import jax

    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
