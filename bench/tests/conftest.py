"""The benchmark's CPU tests see eight host devices, as the repository's
suite does (set before JAX starts its backend), so the sharded reference
and the missing-exchange fault run on a real multi-device mesh."""
import os

if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()
