"""The Pallas row-scatter kernel (`pallas_kernels.row_scatter`) that writes
the LU's step-2 swap rows on TPU, run here in the TPU interpreter, which
simulates every DMA and semaphore: bit-equal to
`a.at[idx].set(rows, mode="drop")` on the inputs its precondition admits
(kept indices ascend, the rest are >= M), and, forced into the factor
program, bit-equal factors and permutation to the XLA-scatter path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conflux_tpu.geometry import Grid3, LUGeometry
from conflux_tpu.ops import pallas_kernels

_DROP = np.iinfo(np.int32).max


def _case(kind, M, n, rng):
    """(n,) indices: kept ones ascending, the rest dropped (>= M)."""
    idx = np.full(n, M, np.int32)
    idx[::3] = M + 7
    idx[1::5] = _DROP
    if kind == "dropped":
        return idx
    if kind == "spread":  # distinct groups, at random slots
        kept = np.sort(rng.choice(M // 8, n // 2, replace=False)) * 8 \
            + rng.integers(0, 8, n // 2)
    elif kind == "runs":  # several destinations in one 8-row group
        groups = np.sort(rng.choice(M // 8, n // 8, replace=False))
        kept = np.concatenate([g * 8 + np.sort(rng.choice(8, 4, replace=False))
                               for g in groups])
    elif kind == "ends":  # the first and last groups of the matrix
        kept = np.array([0, 1, 7, 8, M - 9, M - 8, M - 2, M - 1])
    slots = np.sort(rng.choice(n, kept.size, replace=False))
    idx[slots] = kept
    return idx


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("width", [128, 1024, 4096])
@pytest.mark.parametrize("kind", ["spread", "runs", "ends", "dropped"])
def test_row_scatter_matches_xla_scatter(dtype, width, kind):
    M, n = 256, 32
    rng = np.random.default_rng(width + len(kind))
    a = (rng.standard_normal((M, width)) * 100).astype(dtype)
    rows = (rng.standard_normal((n, width)) * 100).astype(dtype)
    idx = _case(kind, M, n, rng)
    want = jnp.asarray(a).at[idx].set(rows, mode="drop")
    got = pallas_kernels.row_scatter(jnp.asarray(a), jnp.asarray(idx),
                                     jnp.asarray(rows), interpret=True)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kind", ["spread", "runs", "ends", "dropped"])
def test_row_scatter_in_column_stripes(kind, monkeypatch):
    """A matrix wider than the VMEM budget allows in one piece is written
    in column stripes, one grid step each: a 1024-wide matrix in 256-wide
    stripes gives the same bits as one stripe."""
    monkeypatch.setattr(pallas_kernels, "_SCATTER_VMEM",
                        (pallas_kernels._SCATTER_SLOTS + 2) * 8 * 4 * 256)
    assert pallas_kernels._stripe_cols(1024, 4) == 256
    M, n, width = 256, 32, 1024
    rng = np.random.default_rng(len(kind))
    a = rng.standard_normal((M, width)).astype(np.float32)
    rows = rng.standard_normal((n, width)).astype(np.float32)
    idx = _case(kind, M, n, rng)
    want = jnp.asarray(a).at[idx].set(rows, mode="drop")
    got = pallas_kernels.row_scatter(jnp.asarray(a), jnp.asarray(idx),
                                     jnp.asarray(rows), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_stripe_widths():
    """Stripes divide the width, fit the VMEM budget, and are as wide as
    both allow: the one-chip cell's matrix is one stripe."""
    assert pallas_kernels._stripe_cols(32768, 4) == 32768
    assert pallas_kernels._stripe_cols(75776, 4) == 18944  # 4 stripes
    assert pallas_kernels._stripe_cols(128 * 997, 4) == 128  # a prime count
    budget = pallas_kernels._SCATTER_VMEM
    for w in (128, 1024, 37888, 65536, 75776, 98304):
        cols = pallas_kernels._stripe_cols(w, 4)
        assert w % cols == 0 and cols % 128 == 0
        assert (pallas_kernels._SCATTER_SLOTS + 2) * 8 * cols * 4 <= budget


@pytest.mark.parametrize("grid", [Grid3(1, 1, 1), Grid3(2, 1, 1)], ids=str)
def test_lu_factor_with_row_scatter_kernel_is_bitwise(grid, monkeypatch):
    """The factor program with the kernel forced on (interpreted) gives the
    XLA-scatter program's factors and perm, bit for bit. On 2x1x1 half of
    every step's destinations belong to the other x-rank and are dropped."""
    from conflux_tpu.lu import distributed
    from conflux_tpu.parallel.mesh import make_mesh
    from conflux_tpu.validation import make_test_matrix

    N, v = 512, 128
    geom = LUGeometry.create(N, N, v, grid)
    mesh = make_mesh(grid, devices=jax.devices()[:grid.P])
    shards = jnp.asarray(geom.scatter(
        make_test_matrix(N, N, seed=11, dtype=np.float32)))
    assert not distributed._swap_kernel("cpu", jnp.float32, geom)
    want, want_perm = distributed.lu_factor_distributed(shards, geom, mesh)
    monkeypatch.setattr(distributed, "_swap_kernel", lambda *a: True)
    got, got_perm = distributed.lu_factor_distributed(shards, geom, mesh)
    np.testing.assert_array_equal(np.asarray(got_perm), np.asarray(want_perm))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_swap_kernel_choice():
    """The kernel is chosen from the backend and the storage dtype alone."""
    from conflux_tpu.lu.distributed import _swap_kernel

    geom = LUGeometry.create(4096, 4096, 1024, Grid3(1, 1, 1))
    assert _swap_kernel("tpu", jnp.float32, geom)
    for platform, dtype in [("cpu", jnp.float32), ("gpu", jnp.float32),
                            ("tpu", jnp.bfloat16), ("tpu", jnp.complex64),
                            ("tpu", jnp.float64)]:
        assert not _swap_kernel(platform, dtype, geom), (platform, dtype)
    assert not _swap_kernel("tpu", jnp.float32,
                            LUGeometry.create(256, 256, 64, Grid3(1, 1, 1)))


def test_row_scatter_refuses_what_it_cannot_write():
    a = jnp.zeros((64, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="4-byte"):
        pallas_kernels.row_scatter(a, jnp.zeros((8,), jnp.int32),
                                   jnp.zeros((8, 128), jnp.bfloat16))
    with pytest.raises(ValueError, match="multiples"):
        pallas_kernels.row_scatter(jnp.zeros((64, 100), jnp.float32),
                                   jnp.zeros((8,), jnp.int32),
                                   jnp.zeros((8, 100), jnp.float32))
