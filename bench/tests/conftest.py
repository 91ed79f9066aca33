"""The benchmark's tests see four simulated CPU devices, so that the
four-chip cell rehearses in the test process as the one-chip cells do.
Set before JAX starts; an XLA_FLAGS that already fixes a device count is
left as it is."""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()
