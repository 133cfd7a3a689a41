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
import re

import numpy as np
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


@pytest.mark.parametrize("m,w", [(32768, 32768), (37888, 75776)],
                         ids=["1x1x1", "2x1x2"])
def test_row_scatter_kernel_compiles_at_full_size(one_chip, m, w):
    """The step-2 row scatter at full size, 1024 rows, written in place (no
    temporary, the matrix aliased to the output): the one-chip cell's
    (32768, 32768) f32 matrix, and a local block of the N=75776 LU on the
    2x1x2 grid, whose width the kernel covers in column stripes to stay
    within VMEM."""
    from conflux_tpu.ops import pallas_kernels

    n = 1024
    compiled = jax.jit(pallas_kernels.row_scatter, donate_argnums=0).lower(
        _spec((m, w), one_chip), _spec((n,), one_chip, jnp.int32),
        _spec((n, w), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == m * w * 4
    assert mem.temp_size_in_bytes == 0


def test_lu_program_compiles_and_fits(topo):
    """The LU program for one v5e: donated, within HBM, and its step-2
    swap rows written by the row-scatter kernel, with no scatter loop over
    the matrix and no temporary copy of it."""
    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.lu.distributed import build_program
    from conflux_tpu.parallel.mesh import AXIS_X, AXIS_Y, make_mesh

    grid = Grid3(1, 1, 1)
    geom = LUGeometry.create(4096, 4096, 1024, grid)
    mesh = make_mesh(grid, devices=topo.devices[:1])
    fn = build_program(geom, mesh, donate=True, dtype=jnp.float32)
    shards = _spec((1, 1, geom.Ml, geom.Nl),
                   NamedSharding(mesh, P(AXIS_X, AXIS_Y, None, None)))
    compiled = fn.lower(shards).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes  # donated
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    assert mem.temp_size_in_bytes < geom.Ml * geom.Nl * 4

    matrix = f"f32[{geom.Ml},{geom.Nl}]"
    lines = compiled.as_text().splitlines()
    kernel = [ln for ln in lines if "tpu_custom_call" in ln
              and "step2_pivotrows" in ln]
    assert kernel and all(f"= {matrix}" in ln for ln in kernel), kernel
    # XLA's lowering of the matrix scatter: a while loop that carries the
    # matrix, or a scatter into it, under the step-2 scope
    loops = [ln for ln in lines if "step2_pivotrows" in ln and matrix in ln
             and (" while(" in ln or " scatter(" in ln)]
    assert not loops, loops


def test_mesh_cell_programs_compile_and_fit(topo):
    """The four-chip cell's programs besides the factor, at full size
    (N=65536 on the 2x2x1 grid of a described v5e:2x2): the distributed
    solve, the benchmark's shard generator and its exact sharded
    residual each fit a chip beside the 4 GiB of factors."""
    from benchmark import reference_mesh
    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.parallel.mesh import (
        AXIS_X, AXIS_Y, make_mesh, mesh_cache_key)
    from conflux_tpu.solvers import _build_lu_solve

    n, v, grid = 65536, 1024, Grid3(2, 2, 1)
    geom = LUGeometry.create(n, n, v, grid)
    mesh = make_mesh(grid, devices=topo.devices[:4])
    shards = _spec((2, 2, geom.Ml, geom.Nl),
                   NamedSharding(mesh, P(AXIS_X, AXIS_Y, None, None)))
    rep = NamedSharding(mesh, P())
    perm, b = _spec((n,), rep, jnp.int32), _spec((n,), rep)
    seed = _spec((), rep, jnp.uint32)
    factors = geom.Ml * geom.Nl * 4
    for fn, args in (
            (_build_lu_solve(geom, mesh_cache_key(mesh)), (shards, perm, b)),
            (reference_mesh.shard_generator(n, v, mesh, (AXIS_X, AXIS_Y)),
             (seed,)),
            (reference_mesh._mesh_ssq(n, v, mesh, AXIS_X, AXIS_Y),
             (shards, perm, seed))):
        mem = fn.lower(*args).compile().memory_analysis()
        # per chip: the factors, the program's own, and what it returns
        assert (factors + mem.temp_size_in_bytes + mem.output_size_in_bytes
                < 12e9), (fn, mem)


@pytest.mark.parametrize("n,grid", [(32768, (1, 1, 1)), (65536, (2, 2, 1))],
                         ids=["1x1x1", "2x2x1"])
def test_lu_solve_moves_only_block_vectors(topo, n, grid):
    """The benchmark cells' distributed solves at full size: no block step
    of `solve_fwd` or `solve_bwd` scatters, every collective in their loops
    reduces at most a (v, nrhs) block (no (v, Nl) row tile), and the solve
    makes no temporary copy of the factors."""
    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.parallel.mesh import (
        AXIS_X, AXIS_Y, make_mesh, mesh_cache_key)
    from conflux_tpu.solvers import _build_lu_solve

    v, g = 1024, Grid3(*grid)
    geom = LUGeometry.create(n, n, v, g)
    mesh = make_mesh(g, devices=topo.devices[:g.P])
    shards = _spec((g.Px, g.Py, geom.Ml, geom.Nl),
                   NamedSharding(mesh, P(AXIS_X, AXIS_Y, None, None)))
    rep = NamedSharding(mesh, P())
    compiled = _build_lu_solve(geom, mesh_cache_key(mesh)).lower(
        shards, _spec((n,), rep, jnp.int32), _spec((n,), rep)).compile()
    factors = geom.Ml * geom.Nl * 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < factors, mem
    assert (factors + mem.temp_size_in_bytes + mem.output_size_in_bytes
            < 12e9), mem

    loop = [ln for ln in compiled.as_text().splitlines()
            if re.search(r"solve_(fwd|bwd)/while/", ln)]
    assert loop
    assert not [ln for ln in loop if " scatter(" in ln]
    reduces = [ln for ln in loop
               if re.search(r" all-reduce(-start)?\(", ln)]
    assert reduces or g.P == 1
    for ln in reduces:
        dims = re.search(r"= \(?f32\[([\d,]*)\]", ln).group(1)
        assert np.prod([int(d) for d in dims.split(",")]) <= v * 1, ln
