"""Four host CPU devices for the benchmark's tests, requested before JAX
first starts in the test process, as ``tests/conftest.py`` requests them
for the program's tests: the collectives cell runs its collectives over
four chips, and on the CPU these four devices stand in for them."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        f"{_flags} --xla_force_host_platform_device_count=4").strip()
