"""The benchmark's own tests run on the CPU with 8 host devices, set
before JAX starts, so a cell that asks for 4 chips runs tiny on a mesh of
CPU devices like any other."""

import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
