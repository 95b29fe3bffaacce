"""``python -m dslabs_tpu.analysis`` — the soundness-sanitizer CLI
(ISSUE 10).  The env pinning must happen BEFORE anything imports jax:
the audit is static (trace + lower, never compile/dispatch), so it
always runs on a virtual CPU mesh and leaves the accelerator alone —
the same discipline as tests/conftest.py."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

from dslabs_tpu.analysis import main  # noqa: E402

sys.exit(main())
