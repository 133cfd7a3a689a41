"""Executable-spec tests: the NumPy simulation must (a) produce correct
factorizations under every pivoting strategy and (b) agree with the
shard_map implementation — the cross-validation role the reference's
prototype played for its C++ (`python/compare_res.py`)."""

import numpy as np
import pytest

from conflux_tpu.geometry import Grid3
from conflux_tpu.lu.distributed import full_permutation, lu_distributed_host
from conflux_tpu.spec.numpy_lu import simulate_lu
from conflux_tpu.validation import lu_residual, make_test_matrix, residual_bound


@pytest.mark.parametrize("pivoting", ["tournament", "partial"])
@pytest.mark.parametrize("grid", [Grid3(1, 1, 1), Grid3(2, 2, 1), Grid3(2, 2, 2)], ids=str)
def test_spec_residual(grid, pivoting):
    N, v = 32, 8
    A = make_test_matrix(N, N, seed=grid.P + len(pivoting))
    LU, pivots = simulate_lu(A, grid, v, pivoting=pivoting)
    perm = full_permutation(pivots, N)
    res = lu_residual(A, LU[perm], perm)
    assert res < residual_bound(N, np.float64), (grid, pivoting, res)


def test_spec_nopivot_diag_dominant():
    N, v = 16, 4
    A = make_test_matrix(N, N, seed=1)
    A += N * np.eye(N)  # diagonally dominant: row order is pivot order
    LU, pivots = simulate_lu(A, Grid3(2, 1, 1), v, pivoting="none")
    assert pivots.reshape(-1).tolist() == list(range(N))
    perm = full_permutation(pivots, N)
    assert lu_residual(A, LU[perm], perm) < residual_bound(N, np.float64)


def _assert_no_vmem_override():
    """Default-chunk spec-vs-impl agreement holds only when the impl's
    scoped-VMEM budget equals the spec's pinned default: the spec pins
    `_MODEL_VMEM_BYTES` for host-independence while the impl honors
    env/device overrides, so under an override the two would chunk (and
    can pivot) differently. Guard rather than silently diverge."""
    from conflux_tpu.ops import blas

    assert blas.scoped_vmem_bytes() == blas._MODEL_VMEM_BYTES, (
        "scoped-VMEM override active; default-chunk spec-vs-impl "
        "cross-validation needs an explicit shared panel_chunk")


@pytest.mark.parametrize("grid", [Grid3(2, 2, 1), Grid3(2, 1, 2)], ids=str)
def test_spec_matches_shard_map_implementation(grid):
    """Same algorithm, two implementations: pivot choices must be identical
    and factors must agree to fp tolerance."""
    _assert_no_vmem_override()
    N, v = 32, 8
    A = make_test_matrix(N, N, seed=99)
    LU_spec, piv_spec = simulate_lu(A, grid, v, pivoting="tournament")
    LU_impl, perm_impl, _ = lu_distributed_host(A, grid, v)
    piv_impl = perm_impl[: piv_spec.size].reshape(piv_spec.shape)
    np.testing.assert_array_equal(piv_spec, piv_impl)
    np.testing.assert_allclose(LU_spec, LU_impl, atol=1e-10)


def test_spec_matches_implementation_chunked():
    """Cross-validation must hold in the *chunked* election regime too
    (Ml > chunk locally, Px*v > chunk in the election) — the production
    regime of BASELINE.md's grids."""
    N, v, chunk = 64, 8, 16
    A = make_test_matrix(N, N, seed=101)
    for grid in (Grid3(2, 1, 1), Grid3(2, 2, 1)):
        LU_spec, piv_spec = simulate_lu(A, grid, v, pivoting="tournament",
                                        panel_chunk=chunk)
        LU_impl, perm_impl, _ = lu_distributed_host(A, grid, v,
                                                    panel_chunk=chunk)
        piv_impl = perm_impl[: piv_spec.size].reshape(piv_spec.shape)
        np.testing.assert_array_equal(piv_spec, piv_impl)
        np.testing.assert_allclose(LU_spec, LU_impl, atol=1e-10)
