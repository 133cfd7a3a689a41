"""Test harness config: simulate an 8-device mesh on CPU.

Mirrors the reference's strategy of testing multi-rank logic without a
cluster (its NumPy prototype simulated all ranks in one process,
`python/conflux.py:40`); here XLA's host-platform device-count flag gives us
8 real XLA devices on CPU so the very same `shard_map` code that runs on a
TPU pod runs in CI.

The tests run on the CPU (`JAX_PLATFORMS=cpu`); jax.config pins it too,
and the environment variable is set for the subprocesses tests start
(fabric workers, CLI runs), so none of them reaches for an accelerator.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
