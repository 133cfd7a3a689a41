"""Described-chip compiles: the Pallas kernels and the LU program, compiled
for a TPU v5e that is described, not attached (the on-chip-measurement
guide's rehearsal). What interpret mode cannot see — block shapes Mosaic
refuses, VMEM a kernel overruns, a program that does not fit HBM — fails
here at no chip time.

The topology is described inside a module fixture, never at import: only
one process may hold libtpu, and a worker that decides at import whether
these tests exist would make xdist's workers collect different tests.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

N_KERNEL, B_KERNEL = 256, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable written to the persistent cache cannot
    # be read back without the chip: keep the cache out of it. x64 (which
    # conftest turns on) is off, as on the chip: Mosaic cannot lower the
    # int64 index maps it would produce
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", ["btrsm_lower", "btrsm_upper", "blu",
                                    "bchol"])
def test_serving_kernel_compiles(one_chip, kernel):
    from conflux_tpu.ops import batched_trsm, pallas_factor

    n, B = N_KERNEL, B_KERNEL
    A = _spec((B, n, n), one_chip)
    if kernel.startswith("btrsm"):
        nb, bs = n // 32, 32
        lowered = batched_trsm._pallas_btrsm.lower(
            A, _spec((B, nb, bs, bs), one_chip), _spec((B, n, 8), one_chip),
            lower=kernel == "btrsm_lower", interpret=False)
    else:
        fn = (pallas_factor._pallas_blu if kernel == "blu"
              else pallas_factor._pallas_bchol)
        lowered = fn.lower(A, _spec((1, n), one_chip), interpret=False)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["btrsm", "factor"])
def test_serving_kernel_compiles_at_its_tpu_limit(one_chip, kernel):
    """The widths the kernels admit on TPU are widths that compile."""
    from conflux_tpu.ops import batched_trsm, pallas_factor

    if kernel == "btrsm":
        n = batched_trsm._MAX_TPU_N
        lowered = batched_trsm._pallas_btrsm.lower(
            _spec((2, n, n), one_chip), _spec((2, n // 32, 32, 32), one_chip),
            _spec((2, n, 8), one_chip), lower=True, interpret=False)
    else:
        n = pallas_factor._MAX_TPU_N
        lowered = pallas_factor._pallas_blu.lower(
            _spec((2, n, n), one_chip), _spec((1, n), one_chip),
            interpret=False)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_panel_kernel_compiles_at_row_ceiling(one_chip):
    from conflux_tpu.ops import blas, pallas_kernels

    m = blas._PALLAS_MAX_ROWS
    compiled = pallas_kernels._lu_block.lower(
        _spec((m, 128), one_chip), _spec((m, 128), one_chip, jnp.int8),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lu_program_compiles_and_fits(topo):
    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.lu.distributed import build_program
    from conflux_tpu.parallel.mesh import AXIS_X, AXIS_Y, make_mesh

    grid = Grid3(1, 1, 1)
    geom = LUGeometry.create(4096, 4096, 1024, grid)
    mesh = make_mesh(grid, devices=topo.devices[:1])
    fn = build_program(geom, mesh, donate=True, dtype=jnp.float32)
    shards = _spec((1, 1, geom.Ml, geom.Nl),
                   NamedSharding(mesh, P(AXIS_X, AXIS_Y, None, None)))
    mem = fn.lower(shards).compile().memory_analysis()
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes  # donated
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
