"""Direct solvers + mixed-precision iterative refinement (HPL-MxP mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

from conflux_tpu.solvers import cholesky_solve, lu_solve, solve
from conflux_tpu.validation import make_spd_matrix, make_test_matrix


def _relerr(A, x, b):
    r = np.asarray(A) @ np.asarray(x) - np.asarray(b)
    return np.linalg.norm(r) / np.linalg.norm(np.asarray(b))


def test_lu_solve_direct():
    N = 128
    A = make_test_matrix(N, N, seed=1)
    b = np.linspace(-1, 1, N)
    from conflux_tpu.lu.single import lu_factor_blocked

    LU, perm = lu_factor_blocked(jnp.asarray(A), v=16)
    x = lu_solve(LU, perm, jnp.asarray(b))
    assert _relerr(A, x, b) < 1e-10


def test_lu_solve_multiple_rhs():
    N = 64
    A = make_test_matrix(N, N, seed=2)
    B = make_test_matrix(N, 3, seed=3)
    from conflux_tpu.lu.single import lu_factor_blocked

    LU, perm = lu_factor_blocked(jnp.asarray(A), v=16)
    X = lu_solve(LU, perm, jnp.asarray(B))
    assert X.shape == (N, 3)
    assert _relerr(A, X, B) < 1e-10


def test_cholesky_solve_direct():
    N = 128
    A = make_spd_matrix(N, seed=4)
    b = np.cos(np.arange(N))
    from conflux_tpu.cholesky.single import cholesky_blocked

    L = cholesky_blocked(jnp.asarray(A), v=32)
    x = cholesky_solve(L, jnp.asarray(b))
    assert _relerr(A, x, b) < 1e-10


@pytest.mark.parametrize("spd", [False, True])
def test_solve_wrapper(spd):
    N = 96
    A = make_spd_matrix(N, seed=5) if spd else make_test_matrix(N, N, seed=5)
    b = np.sin(np.arange(N))
    x = solve(jnp.asarray(A), jnp.asarray(b), v=32, spd=spd)
    assert _relerr(A, x, b) < 1e-10


def test_solve_bf16_factors_refined():
    """bf16 factorization + refinement reaches f32-grade accuracy; without
    refinement it stays at bf16 grade — the HPL-MxP effect. Richardson
    refinement needs cond(A) * err(factors) < 1, so the system is made
    diagonally dominant (the regime the docstring documents)."""
    N = 256
    A = make_test_matrix(N, N, dtype=np.float32, seed=6)
    A[np.arange(N), np.arange(N)] += 16.0
    b = np.linspace(-1, 1, N).astype(np.float32)
    raw = solve(jnp.asarray(A), jnp.asarray(b), v=64,
                factor_dtype=jnp.bfloat16, refine=0)
    ref = solve(jnp.asarray(A), jnp.asarray(b), v=64,
                factor_dtype=jnp.bfloat16, refine=3)
    err_raw = _relerr(A, raw, b)
    err_ref = _relerr(A, ref, b)
    assert err_raw > 1e-4  # bf16 factors alone are coarse
    assert err_ref < 1e-5, (err_raw, err_ref)
    assert err_ref < err_raw / 10


def test_solve_refined_spd():
    N = 256
    A = make_spd_matrix(N, seed=7).astype(np.float32)
    b = np.cos(np.arange(N)).astype(np.float32)
    x = solve(jnp.asarray(A), jnp.asarray(b), v=64, spd=True,
              factor_dtype=jnp.bfloat16, refine=3)
    assert _relerr(A, x, b) < 1e-5


def test_lu_solve_rejects_rectangular():
    from conflux_tpu.lu.single import lu_factor_blocked

    A = make_test_matrix(64, 32, seed=8)
    LU, perm = lu_factor_blocked(jnp.asarray(A), v=16)
    with pytest.raises(ValueError):
        lu_solve(LU, perm, jnp.zeros(32))
    with pytest.raises(ValueError):
        lu_solve(jnp.zeros((32, 32)), jnp.arange(32), jnp.zeros(16))


def test_solve_clamps_tile_size():
    # N=100 is no multiple of the default v: solve identity-pads to 256
    N = 100
    A = make_test_matrix(N, N, seed=9)
    b = np.ones(N)
    x = solve(jnp.asarray(A), jnp.asarray(b))
    assert x.shape == (N,)
    assert _relerr(A, x, b) < 1e-10


def test_solve_prime_dim_pads_not_unrolls():
    # prime N used to fall back to v=1 (N unrolled supersteps at trace
    # time); identity padding keeps the superstep count bounded
    N = 211
    A = make_test_matrix(N, N, seed=12)
    b = np.ones(N)
    x = solve(jnp.asarray(A), jnp.asarray(b), v=64)
    assert x.shape == (N,)
    assert _relerr(A, x, b) < 1e-10
    B = np.stack([b, 2 * b], axis=1)
    X = solve(jnp.asarray(A), jnp.asarray(B), v=64, spd=False)
    assert X.shape == (N, 2)
    assert _relerr(A, X[:, 1], 2 * b) < 1e-10


def test_solve_prime_dim_spd():
    from conflux_tpu.validation import make_spd_matrix

    N = 127
    A = make_spd_matrix(N, seed=3)
    b = np.ones(N)
    x = solve(jnp.asarray(A), jnp.asarray(b), v=64, spd=True)
    assert x.shape == (N,)
    assert _relerr(A, x, b) < 1e-10


def test_top_level_solve_is_callable_twice():
    # the lazy package attribute must not be shadowed by the solvers module
    import conflux_tpu

    for _ in range(2):
        fn = conflux_tpu.solve
        assert callable(fn) and not hasattr(fn, "__path__"), fn


def test_lu_solve_distributed_matches_single():
    import jax

    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh
    from conflux_tpu.solvers import lu_solve_distributed

    N, vt = 64, 8
    grid = Grid3(2, 2, 2)
    geom = LUGeometry.create(N, N, vt, grid)
    mesh = make_mesh(grid, devices=jax.devices()[:8])
    A = make_test_matrix(N, N, seed=12)
    b = np.linspace(-1, 1, N)

    shards, perm = lu_factor_distributed(
        jnp.asarray(geom.scatter(A)), geom, mesh
    )
    x = lu_solve_distributed(shards, perm, geom, mesh, jnp.asarray(b))
    assert x.shape == (N,)
    assert _relerr(A, x, b) < 1e-10


def test_solve_distributed_refined():
    """Full at-scale solve path: distributed factor + mesh solve + IR with
    an f64 residual must reach f64-grade accuracy from f32 factors."""
    import jax

    from conflux_tpu.geometry import Grid3
    from conflux_tpu.solvers import solve_distributed

    N = 128
    A = make_test_matrix(N, N, seed=17, dtype=np.float32)
    b = np.linspace(-1, 1, N).astype(np.float32)
    x = solve_distributed(jnp.asarray(A), jnp.asarray(b), grid=Grid3(2, 2, 1),
                          v=16, mesh=None, refine=3)
    assert _relerr(A, np.asarray(x, np.float64), b) < 1e-9


def test_solve_distributed_bf16_factors():
    from conflux_tpu.geometry import Grid3
    from conflux_tpu.solvers import solve_distributed

    N = 128
    A = make_test_matrix(N, N, seed=18, dtype=np.float32)
    # IR with bf16 factors converges only while cond(A)*eps_bf16 << 1
    # (eps_bf16 ~ 8e-3): boost the diagonal well past the random part's
    # spectral norm (~13 at N=128)
    A += 32 * np.eye(N, dtype=np.float32)
    b = np.ones(N, np.float32)
    x = solve_distributed(jnp.asarray(A), jnp.asarray(b), grid=Grid3(2, 1, 1),
                          v=16, refine=6, factor_dtype=jnp.bfloat16)
    assert _relerr(A, np.asarray(x, np.float64), b) < 1e-7


def test_fgmres_exact_preconditioner_one_cycle():
    """With an exact inverse as preconditioner, FGMRES converges in the
    first Arnoldi step — the identity sanity check of the engine."""
    from conflux_tpu.solvers import fgmres

    rng = np.random.default_rng(7)
    N = 96
    A = rng.standard_normal((N, N)) + 4 * np.eye(N)
    b = rng.standard_normal(N)
    Ad = jnp.asarray(A, jnp.float64)
    Ainv = jnp.asarray(np.linalg.inv(A), jnp.float64)
    x, info = fgmres(lambda v: Ad @ v, lambda r: Ainv @ r,
                     jnp.asarray(b, jnp.float64), tol=1e-12, restart=4)
    assert info["restarts"] == 1
    assert info["residual"] < 1e-12
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(A, b),
                               rtol=1e-9)


def test_fgmres_beats_classic_ir_on_bf16_factors():
    """The GMRES-IR claim (HPL-MxP): on a matrix where classic IR with
    bf16 factors contracts at ~0.7/sweep (cond ~1e3 — measured: 10 sweeps
    still stall above 1e-2), FGMRES preconditioned by the SAME factors
    reaches 1e-6."""
    from conflux_tpu.lu.single import lu_factor_blocked
    from conflux_tpu.solvers import fgmres, lu_solve

    N = 512
    A = make_test_matrix(N, N, dtype=np.float32)  # cond ~1.4e3
    b = np.ones(N, np.float32)
    LU, perm = lu_factor_blocked(jnp.asarray(A).astype(jnp.bfloat16), v=64)
    Ad = jnp.asarray(A)

    # classic IR baseline: verify it genuinely stalls on this problem
    b_r = jnp.asarray(b, jnp.float64)
    x = lu_solve(LU, perm, jnp.asarray(b)).astype(jnp.float64)
    from conflux_tpu.solvers import _residual_strips
    for _ in range(6):
        r = _residual_strips(Ad, x, b_r, jnp.float64)
        x = x + lu_solve(LU, perm, r.astype(jnp.float32)).astype(jnp.float64)
    r = _residual_strips(Ad, x, b_r, jnp.float64)
    classic = float(jnp.linalg.norm(r) / jnp.linalg.norm(b_r))
    assert classic > 1e-4, f"classic IR unexpectedly converged: {classic}"

    xg, info = fgmres(
        lambda v: Ad.astype(jnp.float64) @ v,
        lambda rr: lu_solve(LU, perm, rr.astype(jnp.float32)),
        b_r, tol=1e-6, restart=16, max_restarts=8)
    assert info["residual"] <= 1e-6, info
    assert _relerr(A, np.asarray(xg, np.float64), b) < 1e-6


@pytest.mark.parametrize("low", ["bf16_factors", "precision_high"])
def test_solve_distributed_gmres_ir(low):
    """ir='gmres' end-to-end on the mesh: a low-precision factor + FGMRES
    reach the bar where refine= (classic) cannot on an ill-enough matrix.
    The factor is bf16 storage, or f32 storage with bf16x3 trailing
    GEMMs (`precision=HIGH`, the HPL-MxP mode), held to the 1e-6
    solve bar."""
    from jax import lax

    from conflux_tpu.geometry import Grid3
    from conflux_tpu.solvers import solve_distributed

    N = 128
    A = make_test_matrix(N, N, seed=18, dtype=np.float32)  # no diag boost
    b = np.ones(N, np.float32)
    if low == "bf16_factors":
        kw, bar = {"factor_dtype": jnp.bfloat16}, 1e-8
    else:
        kw, bar = {"precision": lax.Precision.HIGH}, 1e-6
    x = solve_distributed(jnp.asarray(A), jnp.asarray(b), grid=Grid3(2, 1, 1),
                          v=16, ir="gmres", tol=1e-8, restart=16,
                          max_restarts=8, **kw)
    assert _relerr(A, np.asarray(x, np.float64), b) < bar


def test_solve_distributed_rejects_padding():
    import pytest

    from conflux_tpu.geometry import Grid3
    from conflux_tpu.solvers import solve_distributed

    A = make_test_matrix(100, 100, dtype=np.float32)
    with pytest.raises(ValueError, match="multiple"):
        solve_distributed(jnp.asarray(A), jnp.ones(100), grid=Grid3(2, 2, 1),
                          v=16)
    # column-only padding (M fits, N doesn't) must hit the same guard
    B = make_test_matrix(64, 64, dtype=np.float32)
    with pytest.raises(ValueError, match="multiple"):
        solve_distributed(jnp.asarray(B), jnp.ones(64), grid=Grid3(1, 3, 1),
                          v=16)


def test_lu_solve_distributed_asymmetric_grid():
    import jax

    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh
    from conflux_tpu.solvers import lu_solve_distributed

    N, vt = 64, 8
    grid = Grid3(4, 2, 1)
    geom = LUGeometry.create(N, N, vt, grid)
    mesh = make_mesh(grid, devices=jax.devices()[:8])
    A = make_test_matrix(geom.M, geom.N, seed=13)
    b = np.cos(np.arange(geom.M))

    shards, perm = lu_factor_distributed(
        jnp.asarray(geom.scatter(A)), geom, mesh
    )
    x = lu_solve_distributed(shards, perm, geom, mesh, jnp.asarray(b))
    assert _relerr(A, x, b) < 1e-10


def test_mesh_solves_multi_rhs():
    """Multi-RHS (LAPACK getrs/potrs semantics): all columns ride each
    substitution step together and match per-column solves exactly."""
    import jax

    from conflux_tpu.cholesky.distributed import cholesky_factor_distributed
    from conflux_tpu.geometry import CholeskyGeometry, Grid3, LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh
    from conflux_tpu.solvers import (
        cholesky_solve_distributed,
        lu_solve_distributed,
    )
    from conflux_tpu.validation import make_spd_matrix, make_test_matrix

    grid = Grid3(2, 2, 1)
    N, v, k = 64, 8, 3
    mesh = make_mesh(grid, devices=jax.devices()[: grid.P])
    rng = np.random.default_rng(4)
    B = rng.standard_normal((N, k)).astype(np.float32)

    geom = LUGeometry.create(N, N, v, grid)
    A = make_test_matrix(N, N, dtype=np.float32)
    lu_sh, perm = lu_factor_distributed(jnp.asarray(geom.scatter(A)), geom,
                                        mesh)
    X = np.asarray(lu_solve_distributed(lu_sh, perm, geom, mesh, B))
    assert X.shape == (N, k)
    for j in range(k):
        xj = np.asarray(lu_solve_distributed(lu_sh, perm, geom, mesh,
                                             B[:, j]))
        # the blocked triangular solve's rounding depends on the RHS
        # count, so agreement is f32-level, not bitwise
        np.testing.assert_allclose(X[:, j], xj, rtol=2e-4, atol=2e-5)
    assert np.linalg.norm(A @ X - B) / np.linalg.norm(B) < 1e-4

    cgeom = CholeskyGeometry.create(N, v, grid)
    S = make_spd_matrix(N, dtype=np.float32)
    L_sh = cholesky_factor_distributed(jnp.asarray(cgeom.scatter(S)), cgeom,
                                       mesh)
    Xc = np.asarray(cholesky_solve_distributed(L_sh, cgeom, mesh, B))
    assert Xc.shape == (N, k)
    assert np.linalg.norm(S @ Xc - B) / np.linalg.norm(B) < 1e-4


def test_lstsq_single():
    """QR least squares vs np.linalg.lstsq (well-conditioned, tall)."""
    import numpy as np
    from conflux_tpu.solvers import lstsq

    rng = np.random.default_rng(31)
    A = rng.standard_normal((200, 24))
    b = rng.standard_normal(200)
    x = np.asarray(lstsq(jnp.asarray(A), jnp.asarray(b)))
    x_ref = np.linalg.lstsq(A, b, rcond=None)[0]
    np.testing.assert_allclose(x, x_ref, atol=1e-9)


def test_lstsq_distributed_matches_single():
    import numpy as np
    import jax
    from conflux_tpu.geometry import Grid3
    from conflux_tpu.parallel.mesh import make_mesh
    from conflux_tpu.solvers import lstsq, lstsq_distributed

    rng = np.random.default_rng(37)
    Px, Ml, n = 4, 50, 16
    A = rng.standard_normal((Px * Ml, n))
    B = rng.standard_normal((Px * Ml, 3))  # multi-RHS
    mesh = make_mesh(Grid3(Px, 1, 1), devices=jax.devices()[:Px])
    for algo in ("tsqr", "cholesky"):
        X = np.asarray(lstsq_distributed(A.reshape(Px, Ml, n), mesh, B,
                                         algo=algo))
        X1 = np.asarray(lstsq(jnp.asarray(A), jnp.asarray(B)))
        np.testing.assert_allclose(X, X1, atol=1e-9, err_msg=algo)
        # normal-equations optimality: A^T (A X - B) ~ 0
        g = A.T @ (A @ X - B)
        assert np.abs(g).max() < 1e-9 * np.abs(A.T @ B).max() + 1e-8


def test_lstsq_bf16_factors_with_refinement():
    """HPL-MxP recipe on least squares: bf16 QR factors + refinement
    sweeps in f32 recover f32-grade accuracy on a consistent system."""
    import numpy as np
    from conflux_tpu.solvers import lstsq

    rng = np.random.default_rng(53)
    A = rng.standard_normal((256, 32)).astype(np.float32)
    x_true = rng.standard_normal(32).astype(np.float32)
    b = A @ x_true  # consistent: residual-free system
    x0 = np.asarray(lstsq(jnp.asarray(A), jnp.asarray(b),
                          factor_dtype=jnp.bfloat16))
    x3 = np.asarray(lstsq(jnp.asarray(A), jnp.asarray(b),
                          factor_dtype=jnp.bfloat16, refine=3))
    err0 = np.linalg.norm(x0 - x_true) / np.linalg.norm(x_true)
    err3 = np.linalg.norm(x3 - x_true) / np.linalg.norm(x_true)
    assert err0 > 1e-4          # bf16 factors alone are bf16-grade
    assert err3 < 50 * err0
    assert err3 < 1e-5          # refinement lands at f32 grade


def test_lu_solve_transposed():
    import numpy as np
    from conflux_tpu.lu.single import lu_factor_blocked
    from conflux_tpu.solvers import lu_solve_transposed

    rng = np.random.default_rng(73)
    N = 96
    A = (rng.standard_normal((N, N)) + 3 * np.eye(N))
    LU, perm = lu_factor_blocked(jnp.asarray(A), v=16)
    b = rng.standard_normal(N)
    x = np.asarray(lu_solve_transposed(LU, perm, jnp.asarray(b)))
    np.testing.assert_allclose(A.T @ x, b, atol=1e-9)


def test_slogdet_and_cond():
    import numpy as np
    from conflux_tpu.lu.single import lu_factor_blocked
    from conflux_tpu.solvers import cond_estimate_1, slogdet_from_lu

    rng = np.random.default_rng(79)
    N = 64
    A = rng.standard_normal((N, N)) + 3 * np.eye(N)
    LU, perm = lu_factor_blocked(jnp.asarray(A), v=16)
    sign, logabs = slogdet_from_lu(LU, perm)
    s_ref, l_ref = np.linalg.slogdet(A)
    assert sign == s_ref
    np.testing.assert_allclose(logabs, l_ref, rtol=1e-10)

    # Hager's estimate is a lower bound on ||A^{-1}||_1 within a small
    # factor in practice; check bracketing against the exact 1-norm cond
    exact = np.abs(A).sum(axis=0).max() * np.abs(np.linalg.inv(A)).sum(axis=0).max()
    est = cond_estimate_1(A, LU, perm)
    assert 0.1 * exact <= est <= 1.01 * exact, (est, exact)


def test_inv_from_lu():
    import numpy as np
    from conflux_tpu.lu.single import lu_factor_blocked
    from conflux_tpu.solvers import inv_from_lu

    rng = np.random.default_rng(83)
    N = 80
    A = rng.standard_normal((N, N)) + 3 * np.eye(N)
    LU, perm = lu_factor_blocked(jnp.asarray(A), v=16)
    Ainv = np.asarray(inv_from_lu(LU, perm))
    np.testing.assert_allclose(A @ Ainv, np.eye(N), atol=1e-9)


def test_qr_lstsq_distributed():
    """Distributed least squares through the block-cyclic QR factors:
    matches np.linalg.lstsq across grids (incl. Pz > 1) for tall and
    square systems, multi-RHS."""
    import numpy as np
    import jax
    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.parallel.mesh import make_mesh
    from conflux_tpu.qr.distributed import qr_factor_distributed
    from conflux_tpu.solvers import qr_lstsq_distributed

    rng = np.random.default_rng(91)
    for gridspec, (M, N) in [((2, 2, 1), (64, 32)), ((2, 2, 2), (32, 32)),
                             ((4, 2, 1), (96, 48))]:
        grid = Grid3(*gridspec)
        geom = LUGeometry.create(M, N, 8, grid)
        mesh = make_mesh(grid, devices=jax.devices()[: grid.P])
        A = rng.standard_normal((geom.M, geom.N))
        B = rng.standard_normal((geom.M, 3))
        Qs, Rs = qr_factor_distributed(jnp.asarray(geom.scatter(A)), geom,
                                       mesh)
        X = np.asarray(qr_lstsq_distributed(Qs, Rs, geom, mesh, B))
        X_ref = np.linalg.lstsq(A, B, rcond=None)[0]
        np.testing.assert_allclose(X, X_ref, atol=1e-9,
                                   err_msg=str((gridspec, M, N)))

    # single-RHS squeeze semantics
    b = rng.standard_normal(geom.M)
    x = np.asarray(qr_lstsq_distributed(Qs, Rs, geom, mesh, b))
    assert x.shape == (geom.N,)
    np.testing.assert_allclose(x, np.linalg.lstsq(A, b, rcond=None)[0],
                               atol=1e-9)


def test_solver_utilities_complex():
    """Transpose solve / slogdet / inverse on complex inputs (the solver
    utilities must track the complex instantiation set like the cores)."""
    import numpy as np
    from conflux_tpu.lu.single import lu_factor_blocked
    from conflux_tpu.solvers import (
        inv_from_lu,
        lu_solve_transposed,
        slogdet_from_lu,
    )

    rng = np.random.default_rng(103)
    N = 48
    A = (rng.standard_normal((N, N))
         + 1j * rng.standard_normal((N, N))).astype(np.complex128)
    A[np.arange(N), np.arange(N)] += 3.0 + 1.0j
    LU, perm = lu_factor_blocked(jnp.asarray(A), v=16)
    b = (rng.standard_normal(N) + 1j * rng.standard_normal(N))
    x = np.asarray(lu_solve_transposed(LU, perm, jnp.asarray(b)))
    np.testing.assert_allclose(A.T @ x, b, atol=1e-10)
    sign, logabs = slogdet_from_lu(LU, perm)
    s_ref, l_ref = np.linalg.slogdet(A)
    assert np.iscomplexobj(sign)
    np.testing.assert_allclose(sign, s_ref, atol=1e-10)
    np.testing.assert_allclose(logabs, l_ref, rtol=1e-10)
    Ainv = np.asarray(inv_from_lu(LU, perm))
    np.testing.assert_allclose(A @ Ainv, np.eye(N), atol=1e-10)


def _row_tile_psum_block(Aloc, k, vec, known, rhs, tri_solve, gcol, v, Px,
                         Py):
    """The mesh solves' block step as it was first written: row tile k
    psummed whole over 'x', its diagonal block scatter-added out of it and
    psummed over 'y', the (v, nrhs) products psummed over 'y'."""
    from jax import lax

    x_ = lax.axis_index("x")
    li = ((k // Px) * v).astype(jnp.int32)
    part = jnp.where(
        x_ == k % Px,
        lax.dynamic_slice(Aloc, (li, jnp.zeros((), jnp.int32)),
                          (v, Aloc.shape[1])),
        jnp.zeros((), Aloc.dtype))
    rows = lax.psum(part, "x")
    idx = jnp.where((gcol >= k * v) & (gcol < (k + 1) * v), gcol - k * v, v)
    diag = jnp.zeros((v, v), Aloc.dtype).at[:, idx].add(
        jnp.where(idx[None, :] < v, rows, 0.0), mode="drop")
    diag = lax.psum(diag, "y")
    s = jnp.matmul(rows, jnp.where(known[:, None], vec[gcol], 0.0),
                   precision=lax.Precision.HIGHEST)
    s = lax.psum(s, "y")
    return tri_solve(diag, rhs - s)


def _clear_mesh_solve_caches():
    from conflux_tpu import solvers

    for build in (solvers._build_lu_solve, solvers._build_cholesky_solve,
                  solvers._build_qr_lstsq):
        build.cache_clear()


@pytest.mark.parametrize("dtype", [np.float32, np.complex64],
                         ids=["f32", "c64"])
@pytest.mark.parametrize("nrhs", [1, 3])
@pytest.mark.parametrize("grid", [(1, 1, 1), (2, 1, 1), (1, 2, 1), (2, 2, 1),
                                  (2, 1, 2), (4, 2, 1)],
                         ids=lambda g: "x".join(map(str, g)))
@pytest.mark.parametrize("solver", ["lu", "cholesky", "qr"])
def test_mesh_solve_block_step_matches_row_tile_psum(monkeypatch, solver,
                                                     grid, nrhs, dtype):
    """Each mesh solve's block step (partial products summed over the
    grid, the diagonal block read in place by its owner) gives the result
    of the row-tile psum and scatter-added diagonal it replaced: both add
    only exact zeros to the same products."""
    import jax

    from conflux_tpu import solvers
    from conflux_tpu.geometry import CholeskyGeometry, Grid3, LUGeometry
    from conflux_tpu.parallel.mesh import make_mesh

    N, v = 512, 64
    g = Grid3(*grid)
    mesh = make_mesh(g, devices=jax.devices()[: g.P])
    rng = np.random.default_rng(sum(grid) * 10 + nrhs)

    def rand(*shape):
        x = rng.uniform(-1, 1, shape)
        if dtype == np.complex64:
            x = x + 1j * rng.uniform(-1, 1, shape)
        return x

    # factors with small off-diagonal entries and a dominant diagonal, so
    # every substitution stays well scaled
    F = (rand(N, N) / N + 2 * np.eye(N)).astype(dtype)
    b = rand(N, nrhs).astype(dtype)
    if solver == "lu":
        geom = LUGeometry.create(N, N, v, g)
        perm = rng.permutation(N).astype(np.int32)
        sh = jnp.asarray(geom.scatter(F))
        run = lambda: solvers.lu_solve_distributed(sh, perm, geom, mesh, b)
    elif solver == "cholesky":
        geom = CholeskyGeometry.create(N, v, g)
        sh = jnp.asarray(geom.scatter(np.tril(F)))
        run = lambda: solvers.cholesky_solve_distributed(sh, geom, mesh, b)
    else:
        geom = LUGeometry.create(N, N, v, g)
        Qs = jnp.asarray(geom.scatter(F))
        Rs = jnp.asarray(geom.scatter(np.triu(F)))
        run = lambda: solvers.qr_lstsq_distributed(Qs, Rs, geom, mesh, b)

    _clear_mesh_solve_caches()
    try:
        x = np.asarray(run())
        monkeypatch.setattr(solvers, "_solve_block", _row_tile_psum_block)
        _clear_mesh_solve_caches()
        x_old = np.asarray(run())
    finally:
        monkeypatch.undo()
        _clear_mesh_solve_caches()
    assert x.shape == (N, nrhs) and x.dtype == dtype
    assert np.all(np.isfinite(x))
    # bitwise but for the order of the matrix-vector reduction, which
    # XLA's CPU emitter may change when the dot reads a slice in place:
    # within 4 ulp of the largest entry
    tol = 4 * np.finfo(dtype).eps * np.abs(x_old).max()
    np.testing.assert_allclose(x, x_old, rtol=0, atol=tol)
