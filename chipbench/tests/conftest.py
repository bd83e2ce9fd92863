"""Four virtual CPU devices, so that the four-chip cell's layout can be
rehearsed, and no persistent compile cache. Set before JAX starts."""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
