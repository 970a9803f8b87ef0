"""The benchmark's CPU tests: give jax four host devices before it
starts, so the four-chip serving path runs on a simulated mesh (the
repository's own ``tests/conftest.py`` does the same with eight; a
count already set wins)."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        f"{_flags} --xla_force_host_platform_device_count=4").strip()
