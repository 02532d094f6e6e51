"""Force the 8-device virtual CPU mesh — shared by every conftest.

Import this BEFORE any jax-using import.  Tests run on the virtual CPU
mesh whatever accelerator the host has, unless PADDLE_TPU_TEST_REAL=1
(the `-m onchip` subset, tests/test_onchip_smoke.py, run on the chip).
"""

import os

if not os.environ.get("PADDLE_TPU_TEST_REAL"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()
