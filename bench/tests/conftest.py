"""Eight host CPU devices, so that a fleet cell's replicas each get one
of their own: set before JAX first starts its backend (the same flag
and count as ``tests/conftest.py``, whichever of the two loads first)."""
import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
