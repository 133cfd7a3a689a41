"""Direct solvers on the factorizations, with mixed-precision refinement.

The reference stops at the factorization (its miniapps benchmark `LU_rep` /
`parallelCholesky` and validate residuals; there is no solve API). On TPU a
solver is where mixed precision pays: the MXU's native bf16 pass is ~6x the
f32-accurate (HIGHEST) rate, so the HPL-MxP recipe — factor in bf16, then
recover accuracy with a few iterative-refinement sweeps whose residuals are
computed in f32 — turns the cheap factorization into an f32-grade solution.

    x = solve(A, b)                       # f32 factors, direct
    x = solve(A, b, factor_dtype=jnp.bfloat16, refine=3)   # HPL-MxP mode

`lu_solve` / `cholesky_solve` are the plain triangular-substitution halves,
usable with factors from `lu_factor_blocked` / `cholesky_blocked`.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from conflux_tpu.ops import blas
from conflux_tpu.parallel.mesh import mesh_cache_key, shard_map


def _as_2d(b: jax.Array) -> tuple[jax.Array, bool]:
    if b.ndim == 1:
        return b[:, None], True
    return b, False


def lu_solve(LU: jax.Array, perm: jax.Array, b: jax.Array) -> jax.Array:
    """Solve A x = b given packed LU factors with A[perm] == L @ U
    (the contract of `lu_factor_blocked`, square A). b is (N,) or (N, k)."""
    M, N = LU.shape
    if M != N:
        raise ValueError(
            f"lu_solve needs square factors, got {LU.shape} (an M > N "
            "factorization has no unique solve)"
        )
    if b.shape[0] != N:
        raise ValueError(f"b has {b.shape[0]} rows, factors need {N}")
    cdtype = blas.compute_dtype(LU.dtype)
    Lu = LU.astype(cdtype)
    b2, squeeze = _as_2d(b.astype(cdtype))
    # TPU triangular_solve lowers to blocked inversion + matmuls, which at
    # default precision are single bf16 passes — pin the accurate path
    with jax.default_matmul_precision("highest"):
        y = blas.trsm_left_lower_unit(Lu, b2[perm])
        x = blas.trsm_left_upper(Lu, y)
    return x[:, 0] if squeeze else x


def cholesky_solve(L: jax.Array, b: jax.Array) -> jax.Array:
    """Solve A x = b given the lower Cholesky factor L (A = L L^T)."""
    if b.shape[0] != L.shape[0]:
        raise ValueError(f"b has {b.shape[0]} rows, factor needs {L.shape[0]}")
    cdtype = blas.compute_dtype(L.dtype)
    Lc = L.astype(cdtype)
    b2, squeeze = _as_2d(b.astype(cdtype))
    with jax.default_matmul_precision("highest"):
        y = blas.trsm_left_lower(Lc, b2)
        x = blas.trsm_left_lower_t(Lc, y)
    return x[:, 0] if squeeze else x


def _check_solve_rhs(geom, b) -> None:
    """Both mesh solves read b by padded global position: a shorter rhs
    would be silently clamp-read in the padded tiles and the solution
    returned at padded length — reject instead (pad A and b with an
    identity extension first, like `solve` does)."""
    n = geom.N
    rows = np.shape(b)[0] if np.ndim(b) else 0  # list rhs is fine
    if rows != n:
        raise ValueError(
            f"rhs has {rows} rows, the (padded) factorization needs "
            f"{n}; pad the system identity-extended before factoring")


def _solve_block(Aloc, k, vec, known, rhs, tri_solve, gcol, v, Px, Py):
    """Block step k of a mesh triangular sweep, shared by the LU,
    Cholesky-forward and QR mesh solves: returns
    tri_solve(D, rhs - A[tile k, known] @ vec[known]) for row tile k's
    diagonal block D, replicated on every device.

    Each device multiplies its (v, Nl) slice of row tile k by its known
    entries of `vec`; only the x-rank owning the tile keeps the product,
    and one psum over ('x', 'y') of the (v, nrhs) partial products
    (scope `comm_dots`) completes it. The device holding D reads it in
    place and solves; every other device solves against the identity and
    contributes zeros to the psum over ('x', 'y') that replicates the
    (v, nrhs) block (scope `comm_block`). Each sum adds exact zeros, so
    the result is the same on every grid.
    """
    from conflux_tpu.parallel.mesh import AXIS_X, AXIS_Y

    x_ = lax.axis_index(AXIS_X)
    y_ = lax.axis_index(AXIS_Y)
    li = ((k // Px) * v).astype(jnp.int32)
    lj = ((k // Py) * v).astype(jnp.int32)
    zero = jnp.zeros((), Aloc.dtype)
    rows = lax.dynamic_slice(Aloc, (li, jnp.zeros((), jnp.int32)),
                             (v, Aloc.shape[1]))
    s = jnp.matmul(rows, jnp.where(known[:, None], vec[gcol], zero),
                   precision=lax.Precision.HIGHEST)
    row_owner = x_ == k % Px
    with jax.named_scope("comm_dots"):
        s = lax.psum(jnp.where(row_owner, s, zero), (AXIS_X, AXIS_Y))
    owner = row_owner & (y_ == k % Py)
    diag = jnp.where(owner, lax.dynamic_slice(Aloc, (li, lj), (v, v)),
                     jnp.eye(v, dtype=Aloc.dtype))
    blk = tri_solve(diag, rhs - s)
    with jax.named_scope("comm_block"):
        return lax.psum(jnp.where(owner, blk, zero), (AXIS_X, AXIS_Y))


def lu_solve_distributed(shards, perm, geom, mesh, b) -> jax.Array:
    """Solve A x = b on the mesh, from `lu_factor_distributed`'s outputs.

    The factors are block-cyclic in *pivoted row order* (LAPACK layout), so
    the solve is plain block forward/back substitution over tile steps
    (`_solve_block`): per step, the devices owning the diagonal tile's v
    rows dot their columns of them against the already-solved entries, one
    psum over ('x', 'y') of those (v, nrhs) partial products completes the
    inner products, the device holding the diagonal block solves it in
    place, and a second (v, nrhs) psum replicates the solved block. O(N^2/P)
    flops over 2*n_steps latency-bound steps — triangular solves are
    sequential by nature; the reference has no distributed solve at all.
    Each collective sits in a `comm_*` named scope inside `solve_fwd` or
    `solve_bwd`.

    b may be (N,) or (N, nrhs) — multi-RHS runs all columns through each
    substitution step at once (LAPACK getrs semantics). Returns x of b's
    shape, replicated.
    """
    _check_solve_rhs(geom, b)
    fn = _build_lu_solve(geom, mesh_cache_key(mesh))
    return fn(shards, jnp.asarray(perm), jnp.asarray(b))


@functools.lru_cache(maxsize=16)
def _build_lu_solve(geom, mesh_key):
    from jax.sharding import PartitionSpec as P

    from conflux_tpu.parallel.mesh import (
        AXIS_X, AXIS_Y, AXIS_Z, lookup_mesh,
    )

    mesh = lookup_mesh(mesh_key)
    if geom.M != geom.N:
        raise ValueError("distributed solve needs a square factorization")
    v, Px, Py = geom.v, geom.grid.Px, geom.grid.Py
    Ml, Nl, n = geom.Ml, geom.Nl, geom.n_steps

    def device_fn(blk, perm, b):
        y_ = lax.axis_index(AXIS_Y)
        dtype = blas.compute_dtype(blk.dtype)
        with jax.named_scope("solve_load"):
            # z-replicated factors, pivoted order
            Aloc = blk[0, 0].astype(dtype)
            lc = jnp.arange(Nl, dtype=jnp.int32)
            gcol = ((lc // v) * Py + y_) * v + (lc % v)
        with jax.named_scope("solve_perm"):
            bp = b[perm]  # rhs in pivoted row order

        nrhs = bp.shape[1]
        i0 = jnp.zeros((), jnp.int32)

        def fwd(k, yv):
            kv = jnp.asarray(k * v, jnp.int32)
            bk = lax.dynamic_slice(bp, (kv, i0), (v, nrhs))
            yk = _solve_block(
                Aloc, k, yv, gcol < k * v, bk,
                lambda d, r: blas.trsm_left_lower_unit(blas.unit_lower(d), r),
                gcol, v, Px, Py)
            return lax.dynamic_update_slice(yv, yk, (kv, i0))

        with jax.named_scope("solve_fwd"):
            yv = lax.fori_loop(0, n, fwd, jnp.zeros((geom.N, nrhs), dtype))

        def bwd(i, xv):
            k = n - 1 - i
            kv = jnp.asarray(k * v, jnp.int32)
            yk = lax.dynamic_slice(yv, (kv, i0), (v, nrhs))
            xk = _solve_block(
                Aloc, k, xv, gcol >= (k + 1) * v, yk,
                lambda d, r: blas.trsm_left_upper(jnp.triu(d), r),
                gcol, v, Px, Py)
            return lax.dynamic_update_slice(xv, xk, (kv, i0))

        with jax.named_scope("solve_bwd"):
            xv = lax.fori_loop(0, n, bwd, jnp.zeros((geom.N, nrhs), dtype))
            # replicated by construction (pure collectives); replicate (a
            # complex-safe pmax) satisfies the out_spec's replication check
            from conflux_tpu.parallel.mesh import replicate
            with jax.named_scope("comm_replicate"):
                return replicate(xv, (AXIS_X, AXIS_Y, AXIS_Z))

    sharded = shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(P(AXIS_X, AXIS_Y, None, None), P(), P()),
        out_specs=P(),
    )

    def lu_solve(blk, perm, b):
        # the (N,) <-> (N, 1) reshapes and the casts run inside the one
        # program: a solve is a single launch
        with jax.named_scope("solve_perm"):
            b2 = b.astype(blas.compute_dtype(blk.dtype))
            b2 = b2[:, None] if b.ndim == 1 else b2
            perm = perm.astype(jnp.int32)
        x = sharded(blk, perm, b2)
        with jax.named_scope("solve_bwd"):
            return x[:, 0] if b.ndim == 1 else x

    return jax.jit(lu_solve)


def cholesky_solve_distributed(shards, geom, mesh, b) -> jax.Array:
    """Solve A x = b on the mesh from `cholesky_factor_distributed` shards
    (lower triangle = L): block forward substitution with L, then block
    back substitution with L^T. Mirrors `lu_solve_distributed` (which the
    reference lacks entirely); no permutation is involved since Cholesky
    does not pivot.

    b may be (N,) or (N, nrhs) (LAPACK potrs semantics). Returns x of
    b's shape, replicated.
    """
    _check_solve_rhs(geom, b)
    b2, squeeze = _as_2d(jnp.asarray(b, blas.compute_dtype(shards.dtype)))
    fn = _build_cholesky_solve(geom, mesh_cache_key(mesh))
    x = fn(shards, b2)
    return x[:, 0] if squeeze else x


@functools.lru_cache(maxsize=16)
def _build_cholesky_solve(geom, mesh_key):
    from jax.sharding import PartitionSpec as P

    from conflux_tpu.parallel.mesh import (
        AXIS_X, AXIS_Y, AXIS_Z, lookup_mesh,
    )

    mesh = lookup_mesh(mesh_key)
    v, Px, Py = geom.v, geom.grid.Px, geom.grid.Py
    Ml, Nl, n = geom.Ml, geom.Nl, geom.Kappa

    def cholesky_solve(blk, b):
        x_ = lax.axis_index(AXIS_X)
        y_ = lax.axis_index(AXIS_Y)
        dtype = blas.compute_dtype(blk.dtype)
        with jax.named_scope("solve_load"):
            Aloc = blk[0, 0].astype(dtype)  # z-replicated factors, lower = L
        b = b.astype(dtype)

        lr = jnp.arange(Ml, dtype=jnp.int32)
        grow = ((lr // v) * Px + x_) * v + (lr % v)
        lc = jnp.arange(Nl, dtype=jnp.int32)
        gcol = ((lc // v) * Py + y_) * v + (lc % v)

        nrhs = b.shape[1]
        i0 = jnp.zeros((), jnp.int32)

        def fwd(k, yv):
            kv = jnp.asarray(k * v, jnp.int32)
            bk = lax.dynamic_slice(b, (kv, i0), (v, nrhs))
            yk = _solve_block(
                Aloc, k, yv, gcol < k * v, bk,
                lambda d, r: blas.trsm_left_lower(jnp.tril(d), r),
                gcol, v, Px, Py)
            return lax.dynamic_update_slice(yv, yk, (kv, i0))

        with jax.named_scope("solve_fwd"):
            yv = lax.fori_loop(0, n, fwd, jnp.zeros((geom.N, nrhs), dtype))

        def bwd(i, xv):
            k = n - 1 - i
            # column tile k of L: my rows of those v columns
            lj = ((k // Py) * v).astype(jnp.int32)
            cols = lax.psum(
                jnp.where(y_ == k % Py,
                          lax.dynamic_slice(Aloc, (jnp.zeros((), jnp.int32), lj),
                                            (Ml, v)),
                          jnp.zeros((), dtype)), AXIS_Y)
            ahead = grow >= (k + 1) * v
            # conj().T: the back sweep applies L^H for complex dtypes
            s = jnp.matmul(cols.conj().T,
                           jnp.where(ahead[:, None], xv[grow], 0.0),
                           precision=lax.Precision.HIGHEST)
            s = lax.psum(s, AXIS_X)  # (v, nrhs)
            idx = jnp.where((grow >= k * v) & (grow < (k + 1) * v),
                            grow - k * v, v)
            diag = jnp.zeros((v, v), dtype).at[idx].add(
                jnp.where(idx[:, None] < v, cols, 0.0), mode="drop")
            diag = lax.psum(diag, AXIS_X)
            kv = jnp.asarray(k * v, jnp.int32)
            yk = lax.dynamic_slice(yv, (kv, i0), (v, nrhs))
            xk = blas.trsm_left_lower_t(jnp.tril(diag), yk - s)
            return lax.dynamic_update_slice(xv, xk, (kv, i0))

        with jax.named_scope("solve_bwd"):
            xv = lax.fori_loop(0, n, bwd, jnp.zeros((geom.N, nrhs), dtype))
            from conflux_tpu.parallel.mesh import replicate
            return replicate(xv, (AXIS_X, AXIS_Y, AXIS_Z))

    fn = shard_map(
        cholesky_solve,
        mesh=mesh,
        in_specs=(P(AXIS_X, AXIS_Y, None, None), P()),
        out_specs=P(),
    )
    return jax.jit(fn)


def solve_distributed(A, b, *, grid=None, v: int = 1024, mesh=None,
                      refine: int = 0, factor_dtype=None,
                      residual_dtype=None, panel_chunk: int | None = None,
                      precision=None, segs: tuple = (16, 16),
                      tree: str = "pairwise", ir: str = "classic",
                      tol: float = 1e-6, restart: int = 16,
                      max_restarts: int = 12):
    """Factor + solve + iterative refinement on a device mesh.

    The at-scale solve path: the factorization is the distributed program
    (O(1) compile in the superstep count, unlike the unrolled single-device
    path whose trace grows with N/v), the triangular solves run on the
    mesh, and each refinement sweep computes r = b - A x in
    `residual_dtype` (default: float64 when x64 is enabled, else the
    compute dtype).

    Accuracy: with f32 factors the attainable relative residual is floored
    by the *residual computation* precision — an f32 residual stalls near
    eps_f32 * ||A|| * ||x|| / ||b|| (~4e-5 at N=16384 on the standard test
    matrix), while an f64 residual (software-emulated on TPU, but only
    O(N^2) work per sweep, cast strip-wise so no (N, N) f64 buffer exists)
    reaches <= 1e-6 in 2 sweeps — the BASELINE.md acceptance bar. This is
    the HPL-MxP recipe (low-precision O(N^3), high-precision O(N^2)); with
    factor_dtype=bfloat16 the factorization itself rides the fast MXU path
    and a few more sweeps recover the same bar. `precision` reaches the
    trailing GEMMs the same way (lax.Precision.HIGH = bf16x3 passes on f32
    storage — the measured fast path on v5e — vs the default HIGHEST);
    `segs`/`tree` pass through to the factorization untouched.

    A must be the original matrix, (N, N); device placement recommended at
    scale (a host A costs a full transfer). Returns x (N,) in the
    residual/accumulation dtype.
    """
    from conflux_tpu.geometry import LUGeometry, choose_grid
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh

    N = A.shape[0]
    if A.shape[0] != A.shape[1]:
        raise ValueError("solve_distributed needs a square A")
    if ir not in ("classic", "gmres"):
        # before the O(N^3) factorization: a typo must fail in
        # microseconds, not after a multi-minute factor
        raise ValueError(f"unknown ir {ir!r} (classic|gmres)")
    if grid is None:
        grid = choose_grid(jax.device_count(), N, N)
    geom = LUGeometry.create(N, N, v, grid)
    if (geom.M, geom.N) != (N, N):
        raise ValueError(
            f"N={N} must be a multiple of v*Px and v*Py (got padding to "
            f"{geom.M}x{geom.N}); pre-pad with an identity extension")
    if mesh is None:
        mesh = make_mesh(grid)

    fdtype = A.dtype if factor_dtype is None else factor_dtype
    cdtype = blas.compute_dtype(A.dtype)
    if residual_dtype is None:
        residual_dtype = (jnp.float64 if jax.config.jax_enable_x64
                          else cdtype)

    shards = _build_scatter(geom, mesh_cache_key(mesh),
                            jnp.dtype(fdtype).name)(jnp.asarray(A))
    out, perm = lu_factor_distributed(shards, geom, mesh,
                                      panel_chunk=panel_chunk, donate=True,
                                      precision=precision, segs=segs,
                                      tree=tree)

    if ir == "gmres":
        b_r = jnp.asarray(b, residual_dtype)
        # GMRES-IR: the factors precondition FGMRES instead of driving a
        # Richardson iteration — converges where classic IR diverges
        # (cond(A)·eps_factor ~ 1, the bf16/bf16x3 factor regime). This
        # is the actual HPL-MxP algorithm; `refine` is ignored here
        # (tol/restart/max_restarts govern). The callables come from a
        # geometry-keyed cache and the data rides fgmres's `args`, so
        # repeated solves at one geometry share one compiled cycle.
        matvec, precond = _gmres_ops(geom, mesh_cache_key(mesh),
                                     jnp.dtype(residual_dtype).name)
        x, info = fgmres(
            matvec, precond, b_r, args=(jnp.asarray(A), out, perm),
            tol=tol, restart=restart, max_restarts=max_restarts,
            rdtype=residual_dtype)
        if info["residual"] > tol:
            import warnings

            warnings.warn(
                f"GMRES-IR stalled at residual {info['residual']:.3e} "
                f"(> tol {tol:.1e}) after {info['restarts']} restarts "
                "— raise max_restarts/restart or improve the factors",
                RuntimeWarning, stacklevel=2)
        return x
    return refine_classic(
        lambda r: lu_solve_distributed(out, perm, geom, mesh, r),
        A, b, refine, residual_dtype, cdtype)


def refine_classic(solve_fn, A, b, sweeps: int, rdtype, corr_dtype):
    """Classic (Richardson) iterative refinement: x0 = solve(b), then
    `sweeps` rounds of x += solve(b - A x). The single implementation of
    the numerically delicate discipline shared by `solve_distributed`,
    the miniapp's --refine and the bench: x and b stay in the high
    (residual) precision `rdtype` — a b downcast would make IR converge
    to A x = low(b) instead — and only the corrections ride the
    low-precision factors through `solve_fn` (input cast to
    `corr_dtype`)."""
    b_r = jnp.asarray(b, rdtype)
    x = solve_fn(jnp.asarray(b, corr_dtype)).astype(rdtype)
    for _ in range(sweeps):
        r = _residual_strips(A, x, b_r, rdtype)
        x = x + solve_fn(r.astype(corr_dtype)).astype(rdtype)
    return x


def fgmres(matvec, precond, b, *, args=(), x0=None, tol: float = 1e-6,
           restart: int = 16, max_restarts: int = 12, rdtype=None):
    """Flexible GMRES with right preconditioning — the GMRES-IR engine.

    Solves A x = b where `matvec(x, *args)` applies A (accumulate in
    `rdtype`) and `precond(r, *args)` applies an approximate inverse
    (typically a low-precision LU solve: the HPL-MxP recipe — classic
    iterative
    refinement is a Richardson iteration that DIVERGES once
    cond(A)·eps_factor approaches 1, e.g. bf16 factors on a
    cond ~1e3 matrix; FGMRES with the same factors as preconditioner
    converges whenever the preconditioned spectrum clusters).

    TPU-native structure: each restart cycle is ONE jitted program — the
    full Arnoldi process with masked reorthogonalized Gram-Schmidt (CGS2) runs
    device-resident (`lax.fori_loop` over the basis; H and the Krylov
    bases V, Z are fixed-shape carries), so a cycle costs zero host
    round-trips; the only readback per cycle is the small H matrix and
    residual norm for the host-side least-squares update. The basis is
    flexible (Z stores preconditioned vectors), so `precond` may itself
    be any jit-traceable approximate solve.

    `args` rides through to both callables AS JIT ARGUMENTS — pass the
    factors/matrix here (not via closure) so the compiled cycle is
    reused across calls with different data: callers that pass the same
    (matvec, precond, restart, rdtype) identities share one compile.

    Returns (x, info) with info = {'restarts', 'residual'} — residual is
    ||b - A x|| / ||b|| measured with `matvec` at the end.
    """
    if rdtype is None:
        rdtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    b_r = jnp.asarray(b, rdtype)
    N = b_r.shape[0]
    m = int(restart)
    if m < 1:
        raise ValueError(f"restart must be >= 1, got {restart}")
    cycle = _fgmres_cycle(matvec, precond, m, jnp.dtype(rdtype).name)

    x = (jnp.zeros((N,), rdtype) if x0 is None
         else jnp.asarray(x0, rdtype))
    done_restarts = 0
    bnorm = float(jnp.sqrt(jnp.sum(b_r * b_r)))
    if bnorm == 0:
        return x, {"restarts": 0, "residual": 0.0}
    for k in range(max_restarts):
        beta, H, Z = cycle(x, b_r, *args)
        beta_f = float(beta)
        done_restarts = k + 1
        if beta_f / bnorm <= tol:
            break
        # small (m+1, m) least squares on host; breakdown columns (zero
        # subdiagonal) are harmless — lstsq handles the rank
        Hh = np.asarray(H, np.float64)
        e1 = np.zeros(m + 1)
        e1[0] = beta_f
        y, *_ = np.linalg.lstsq(Hh, e1, rcond=None)
        x = x + Z.T @ jnp.asarray(y, rdtype)
        # projected residual estimate: stop next cycle from launching if
        # this one already converged
        if np.linalg.norm(e1 - Hh @ y) / bnorm <= tol:
            break
    r = b_r - matvec(x, *args).astype(rdtype)
    rel = float(jnp.sqrt(jnp.sum(r * r))) / bnorm
    return x, {"restarts": done_restarts, "residual": rel}


@functools.lru_cache(maxsize=16)
def _fgmres_cycle(matvec, precond, m: int, rdtype_name: str):
    """One compiled Arnoldi cycle per (matvec, precond, restart, dtype):
    repeat fgmres calls with the SAME callables (e.g. a bench loop, or
    the restart loop itself) reuse the compiled program instead of
    re-jitting a fresh closure every call. Callers that pass fresh
    lambdas each time simply fall back to one compile per call."""
    rdtype = jnp.dtype(rdtype_name)

    @jax.jit
    def cycle(x, b_r, *args):
        N = b_r.shape[0]
        r = b_r - matvec(x, *args).astype(rdtype)
        beta = jnp.sqrt(jnp.sum(r * r))
        V = jnp.zeros((m + 1, N), rdtype).at[0].set(
            r / jnp.where(beta > 0, beta, 1))
        Z = jnp.zeros((m, N), rdtype)
        H = jnp.zeros((m + 1, m), rdtype)

        def arnoldi(j, carry):
            V, Z, H = carry
            z = precond(V[j], *args).astype(rdtype)
            w = matvec(z, *args).astype(rdtype)
            # masked classical Gram-Schmidt with reorthogonalization
            # (CGS2): two batched projection passes against the whole
            # basis — rows > j are zero so their coefficients vanish and
            # the loop body stays fixed-shape for the one-compile cycle.
            # Single-pass CGS loses orthogonality at O(eps*kappa^2) on
            # ill-conditioned preconditioned operators (exactly the weak-
            # factor regime GMRES-IR exists for); CGS2 restores it at the
            # cost of two extra (m+1, N) GEMVs, and unlike true MGS stays
            # batched (no serial per-column dependence).
            mask = jnp.arange(m + 1) <= j
            h = jnp.where(mask, V @ w, 0)  # (m+1,)
            w = w - V.T @ h
            h2 = jnp.where(mask, V @ w, 0)
            w = w - V.T @ h2
            h = h + h2
            hn = jnp.sqrt(jnp.sum(w * w))
            V = V.at[j + 1].set(w / jnp.where(hn > 0, hn, 1))
            H = H.at[:, j].set(h).at[j + 1, j].set(hn)
            Z = Z.at[j].set(z)
            return V, Z, H

        V, Z, H = lax.fori_loop(0, m, arnoldi, (V, Z, H))
        return beta, H, Z

    return cycle


@functools.lru_cache(maxsize=16)
def _build_scatter(geom, mesh_key, dtype_name: str):
    """Jitted device-side scatter with a sharded output: (M, N) -> block-
    cyclic (Px, Py, Ml, Nl) placed directly with the mesh sharding — no
    single-device staging of the scattered array, no host round trip (the
    host `geom.scatter` costs a full transfer at scale). The factor-dtype
    cast happens inside the same program for the same reason. The layout
    math is `LUGeometry.scatter_blocks`, the single source of the tile
    convention."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from conflux_tpu.parallel.mesh import AXIS_X, AXIS_Y, lookup_mesh

    mesh = lookup_mesh(mesh_key)
    sharding = NamedSharding(mesh, P(AXIS_X, AXIS_Y, None, None))
    return jax.jit(
        lambda A: geom.scatter_blocks(A.astype(dtype_name)),
        out_shardings=sharding,
    )


@functools.partial(jax.jit, static_argnames=("rdtype",))
def _residual_strips(A, x, b, rdtype):
    """r = b - A x with the matvec accumulated in `rdtype`, casting A one
    row-strip at a time (a full (N, N) float64 copy would double the
    matrix footprint — 8 GB at N=32768)."""
    N = A.shape[0]
    strip = max(1, min(4096, N))
    xr = x.astype(rdtype)
    pieces = [
        b[i : i + strip].astype(rdtype)
        - A[i : i + strip].astype(rdtype) @ xr
        for i in range(0, N, strip)
    ]
    return jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]


@functools.lru_cache(maxsize=16)
def _gmres_ops(geom, mesh_key, rdtype_name: str):
    """(matvec, precond) pair for GMRES-IR at one geometry: stable
    function identities (the fgmres cycle-compile cache key) with the
    matrix/factors as runtime arguments."""
    from conflux_tpu.parallel.mesh import lookup_mesh

    mesh = lookup_mesh(mesh_key)
    rdtype = jnp.dtype(rdtype_name)

    def matvec(x, A, shards, perm):
        return _matvec_strips(A, x, rdtype)

    def precond(r, A, shards, perm):
        return lu_solve_distributed(
            shards, perm, geom, mesh,
            r.astype(blas.compute_dtype(shards.dtype)))

    return matvec, precond


def _matvec_strips(A, x, rdtype):
    """A @ x accumulated in `rdtype` with strip-wise casts (same HBM
    discipline as `_residual_strips`); traceable inside fgmres's jitted
    cycle."""
    N = A.shape[0]
    strip = max(1, min(4096, N))
    xr = x.astype(rdtype)
    pieces = [A[i : i + strip].astype(rdtype) @ xr
              for i in range(0, N, strip)]
    return jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]


def solve(A: jax.Array, b: jax.Array, *, v: int = 256,
          factor_dtype=None, refine: int = 0, spd: bool = False) -> jax.Array:
    """Solve A x = b by blocked factorization + optional refinement.

    factor_dtype: dtype the factorization runs in (default: A's dtype).
    Passing jnp.bfloat16 rides the MXU's fast single-pass path — ~6x the
    f32-accurate rate — and `refine` iterative-refinement sweeps (2-3 is
    typical) restore the solution to working-precision accuracy (the
    HPL-MxP trade). Convergence requires the classic IR condition
    cond(A) * err(factors) < 1: with bf16 factors that means reasonably
    well-conditioned (e.g. diagonally dominant) systems; for harder systems
    keep f32 factors or wrap the low-precision solve in GMRES as HPL-MxP
    does at scale.
    refine: number of refinement sweeps; each computes r = b - A x at
    HIGHEST precision in A's dtype and solves for the correction with the
    low-precision factors.
    spd: use Cholesky instead of LU (A must be SPD).
    """
    N = A.shape[0]
    v = min(v, N)
    pad = (-N) % v
    if pad:
        # Pad to the next multiple of v with an identity-extended diagonal
        # (the same trick LUGeometry.scatter uses): the extra rows/cols are
        # decoupled unit pivots, so factors and solution are unchanged and
        # the blocked loops keep a bounded number of supersteps (a divisor
        # fallback here can degenerate to v=1 for prime N, unrolling N
        # supersteps at trace time).
        Np = N + pad
        Ap = jnp.zeros((Np, Np), A.dtype)
        Ap = Ap.at[:N, :N].set(A)
        Ap = Ap.at[jnp.arange(N, Np), jnp.arange(N, Np)].set(1)
        A = Ap
        b2, squeezed = _as_2d(b)
        b = jnp.pad(b2, ((0, pad), (0, 0)))
        if squeezed:
            b = b[:, 0]
    fdtype = A.dtype if factor_dtype is None else factor_dtype
    Af = A.astype(fdtype)
    if spd:
        from conflux_tpu.cholesky.single import cholesky_blocked

        L = cholesky_blocked(Af, v=v)
        solve_corr = lambda r: cholesky_solve(L, r)
    else:
        from conflux_tpu.lu.single import lu_factor_blocked

        LU, perm = lu_factor_blocked(Af, v=v)
        solve_corr = lambda r: lu_solve(LU, perm, r)

    cdtype = blas.compute_dtype(A.dtype)
    Ac = A.astype(cdtype)
    bc = b.astype(cdtype)
    x = solve_corr(b).astype(cdtype)
    for _ in range(refine):
        r = bc - jnp.matmul(Ac, x, precision=lax.Precision.HIGHEST)
        x = x + solve_corr(r).astype(cdtype)
    return x[:N] if pad else x


def solve_updated(A, U, V, b, *, v: int = 256, factor_dtype=None,
                  refine: int = 0, spd: bool = False) -> jax.Array:
    """Solve (A + U V^H) x = b through the factors of A alone.

    The one-shot Sherman-Morrison-Woodbury entry point (the serving form
    is `SolveSession.update`, see `conflux_tpu.update`): A is factored
    once — O(N^3), same `v`/`factor_dtype`/`spd` recipe as :func:`solve`
    — and the rank-k correction rides a k x k capacitance system, so
    solving against MANY drifted variants of one A costs O(N^2 k) each
    instead of a refactorization. U, V are (N, k) with k << N; `refine`
    sweeps compute residuals against the DRIFTED matrix and correct
    through the same Woodbury apply (the classic-IR backstop). `spd`
    refers to A — the drifted matrix need not stay symmetric.
    """
    from conflux_tpu.update import woodbury_solve

    N = A.shape[0]
    if A.shape[0] != A.shape[1]:
        raise ValueError("solve_updated needs a square A")
    if U.shape != V.shape or U.ndim != 2 or U.shape[0] != N:
        raise ValueError(
            f"update factors must both be ({N}, k), got {U.shape} and "
            f"{V.shape}")
    v = min(v, N)
    pad = (-N) % v
    b2, squeeze = _as_2d(jnp.asarray(b))
    if pad:
        # identity-extended A (cf. solve); zero-row U/V leave the
        # extension's unit pivots untouched
        Np = N + pad
        Ap = jnp.zeros((Np, Np), A.dtype).at[:N, :N].set(A)
        A = Ap.at[jnp.arange(N, Np), jnp.arange(N, Np)].set(1)
        U = jnp.pad(jnp.asarray(U), ((0, pad), (0, 0)))
        V = jnp.pad(jnp.asarray(V), ((0, pad), (0, 0)))
        b2 = jnp.pad(b2, ((0, pad), (0, 0)))
    fdtype = A.dtype if factor_dtype is None else factor_dtype
    Af = A.astype(fdtype)
    if spd:
        from conflux_tpu.cholesky.single import cholesky_blocked

        L = cholesky_blocked(Af, v=v)
        base = lambda r: cholesky_solve(L, r)
    else:
        from conflux_tpu.lu.single import lu_factor_blocked

        LU, perm = lu_factor_blocked(Af, v=v)
        base = lambda r: lu_solve(LU, perm, r)
    x = woodbury_solve(base, A if refine else None, U, V, b2, refine=refine)
    if pad:
        x = x[:N]
    return x[:, 0] if squeeze else x


def lstsq(A: jax.Array, b: jax.Array, chunk: int | None = None,
          passes: int = 2, factor_dtype=None, refine: int = 0) -> jax.Array:
    """Least-squares min_x ||A x - b|| for tall full-rank A (M >= n).

    QR route (`qr.single.tall_qr`): x = R^{-1} (Q^T b). Completes the
    solver family (LU for square, Cholesky for SPD, QR for overdetermined)
    — the reference has no solve API at all; see the module docstring.

    `factor_dtype`/`refine` extend the HPL-MxP recipe to least squares:
    factor in a cheap dtype (e.g. bf16), then `refine` sweeps of
    r = b - A x in the accurate dtype with the correction solved through
    the same cheap factors (for consistent systems / small residuals this
    recovers the accurate-dtype solution like the square-solve IR path;
    genuinely inconsistent systems are limited by the normal-equations
    conditioning as usual).
    """
    M, n = A.shape
    if b.shape[0] != M:
        raise ValueError(f"b has {b.shape[0]} rows, A has {M}")
    from conflux_tpu.qr.single import tall_qr

    Af = A.astype(factor_dtype) if factor_dtype is not None else A
    Q, R = tall_qr(Af, chunk=chunk, passes=passes)
    cdtype = blas.compute_dtype(A.dtype)
    Qc, Rc = Q.astype(cdtype), R.astype(cdtype)
    b2, squeeze = _as_2d(b.astype(cdtype))

    def solve_ls(rhs):
        with jax.default_matmul_precision("highest"):
            c = jnp.matmul(Qc.conj().T, rhs,
                           precision=lax.Precision.HIGHEST)
            return blas.trsm_left_upper(Rc, c)

    x = solve_ls(b2)
    if refine:
        Ac = A.astype(cdtype)
        for _ in range(refine):
            r = b2 - jnp.matmul(Ac, x, precision=lax.Precision.HIGHEST)
            x = x + solve_ls(r)
    return x[:, 0] if squeeze else x


@functools.lru_cache(maxsize=32)
def _build_qtb(mesh_key, cdtype_name: str):
    """Compiled c = psum_x(Q_loc^T b_loc) program, cached per mesh/dtype
    (the shapes are traced; rebuilding the shard_map closure per call
    would force a recompile every invocation)."""
    from jax.sharding import PartitionSpec as P

    from conflux_tpu.parallel.mesh import AXIS_X, lookup_mesh

    mesh = lookup_mesh(mesh_key)
    cdtype = jnp.dtype(cdtype_name)

    from conflux_tpu.parallel.mesh import replicate

    def qr_qtb(qblk, bblk):
        c = lax.psum(
            jnp.matmul(qblk[0].astype(cdtype).conj().T, bblk[0],
                       precision=lax.Precision.HIGHEST), AXIS_X)
        return replicate(c, tuple(mesh.axis_names))

    return jax.jit(shard_map(
        qr_qtb, mesh=mesh,
        in_specs=(P(AXIS_X, None, None), P(AXIS_X, None, None)),
        out_specs=P()))


def lstsq_distributed(shards, mesh, b, algo: str = "tsqr",
                      chunk: int | None = None, passes: int = 2):
    """Distributed least squares on x-sharded rows: min_x ||A x - b||.

    shards is (Px, Ml, n) block-row shards (the `qr.distributed` layout),
    b is (M,) or (M, k) with M = Px*Ml. TSQR (or CholeskyQR2) gives
    (Q_shards, R); c = Q^T b is one (n, k) psum over 'x' — the only
    communication beyond the factorization's R reduction — then
    x = R^{-1} c, replicated.
    """
    from conflux_tpu.qr.distributed import (
        cholesky_qr2_distributed,
        tsqr_distributed,
    )

    shards = jnp.asarray(shards)
    Px, Ml, n = shards.shape
    cdtype = blas.compute_dtype(shards.dtype)
    b2, squeeze = _as_2d(jnp.asarray(b, cdtype))
    if b2.shape[0] != Px * Ml:
        # before the factorization: the error should be free
        raise ValueError(f"b has {b2.shape[0]} rows, shards hold {Px * Ml}")
    if algo == "tsqr":
        Qs, R = tsqr_distributed(shards, mesh, chunk=chunk, passes=passes)
    elif algo == "cholesky":
        Qs, R = cholesky_qr2_distributed(shards, mesh, passes=passes)
    else:
        raise ValueError(f"unknown algo {algo!r} (tsqr|cholesky)")
    bs = b2.reshape(Px, Ml, -1)
    c = _build_qtb(mesh_cache_key(mesh), cdtype.name)(Qs, bs)
    with jax.default_matmul_precision("highest"):
        x = blas.trsm_left_upper(jnp.asarray(R, cdtype), c)
    return x[:, 0] if squeeze else x


def lu_solve_transposed(LU: jax.Array, perm: jax.Array,
                        b: jax.Array) -> jax.Array:
    """Solve A^T x = b from the packed LU factors of A (getrs 'T' path:
    A[perm] = L U, so A^T = U^T L^T P and x = P^T (L^T \\ (U^T \\ b)))."""
    N = LU.shape[0]
    if LU.shape[0] != LU.shape[1] or b.shape[0] != N:
        raise ValueError(f"square factors and matching rhs required, "
                         f"got {LU.shape} and {b.shape}")
    cdtype = blas.compute_dtype(LU.dtype)
    Lu = LU.astype(cdtype)
    b2, squeeze = _as_2d(b.astype(cdtype))
    with jax.default_matmul_precision("highest"):
        y = blas.trsm_left_upper_t(Lu, b2)
        z = blas.trsm_left_lower_unit_t(Lu, y)
    x = jnp.zeros_like(z).at[perm].set(z)  # apply P^T
    return x[:, 0] if squeeze else x


def slogdet_from_lu(LU, perm):
    """(sign, log|det|) from packed LU factors (LAPACK getrf->det recipe:
    det = sign(perm) * prod(diag U)), np.linalg.slogdet conventions:
    sign is 0 for an exactly singular matrix, complex for complex input.
    Host-side; perm parity by cycle count."""
    d = np.asarray(jnp.diagonal(jnp.asarray(LU)))
    p = np.asarray(perm)
    n = p.shape[0]
    seen = np.zeros(n, dtype=bool)
    transpositions = 0
    for i in range(n):
        if seen[i]:
            continue
        j, clen = i, 0
        while not seen[j]:
            seen[j] = True
            j = p[j]
            clen += 1
        transpositions += clen - 1
    sign = -1.0 if transpositions % 2 else 1.0
    if (d == 0).any():
        # np convention: zero sign, complex-typed for complex input
        return (0j if np.iscomplexobj(d) else 0.0), float("-inf")
    if np.iscomplexobj(d):
        ang = np.angle(d).sum()
        sign = sign * np.exp(1j * ang)
    else:
        neg = int((d < 0).sum())
        sign = sign * (-1.0 if neg % 2 else 1.0)
    logabs = float(np.log(np.abs(d)).sum())
    return sign, logabs


def cond_estimate_1(A, LU, perm, iters: int = 5) -> float:
    """1-norm condition estimate from the factors (the `gecon` role):
    ||A||_1 * est(||A^{-1}||_1) via Hager's power iteration on A^{-1}
    (each step is one solve + one transpose solve through the factors —
    O(iters * N^2) after the O(N^3) factorization)."""
    A = jnp.asarray(A)
    n = A.shape[0]
    anorm = float(jnp.abs(A).sum(axis=0).max())
    x = jnp.full((n,), 1.0 / n, blas.compute_dtype(A.dtype))
    est = 0.0
    iters = max(1, iters)
    for it in range(iters):
        y = lu_solve(LU, perm, x)                      # y = A^{-1} x
        est_new = float(jnp.abs(y).sum())
        if est_new <= est:  # converged: skip the dead solve pair
            break
        est = est_new
        if it == iters - 1:  # count exit: the x update has no consumer
            break
        xi = jnp.sign(jnp.where(y == 0, 1.0, y))
        z = lu_solve_transposed(LU, perm, xi)          # z = A^{-T} xi
        j = int(jnp.argmax(jnp.abs(z)))
        x = jnp.zeros((n,), x.dtype).at[j].set(1.0)
    return anorm * est


def inv_from_lu(LU: jax.Array, perm: jax.Array) -> jax.Array:
    """A^{-1} from packed LU factors (the `getri` role): solve with the
    identity as RHS — N simultaneous columns through the same blocked
    substitutions, so the MXU sees (N, N) triangular solves, not N
    vector solves."""
    N = LU.shape[0]
    if LU.shape[0] != LU.shape[1]:
        raise ValueError(f"inverse needs square factors, got {LU.shape}")
    return lu_solve(LU, perm, jnp.eye(N, dtype=LU.dtype))


def qr_lstsq_distributed(Q_shards, R_shards, geom, mesh, b) -> jax.Array:
    """Least squares min_x ||A x - b|| on the mesh from the BLOCK-CYCLIC
    QR factors (`qr.qr_factor_distributed` outputs) — the general-matrix
    counterpart of `lstsq_distributed`'s tall x-sharded form, completing
    the distributed-solver matrix (LU square / Cholesky SPD / QR
    overdetermined).

    c = Q^H b is one (Nl, k) partial per device + psums; then R x = c is
    block back substitution over R's own block-cyclic geometry (the
    `lu_solve_distributed` machinery on the upper factor). b is (M,) or
    (M, k) at the PADDED geometry size; x comes back (N,) or (N, k),
    replicated.
    """
    from conflux_tpu.geometry import check_shards
    from conflux_tpu.qr.distributed import r_geometry

    M = geom.M
    rows = np.shape(b)[0] if np.ndim(b) else 0
    if rows != M:
        raise ValueError(
            f"rhs has {rows} rows, the (padded) factorization needs {M}")
    Q_shards = jnp.asarray(Q_shards)
    R_shards = jnp.asarray(R_shards)
    check_shards(Q_shards, geom, "Q_shards")
    check_shards(R_shards, r_geometry(geom), "R_shards")
    b2, squeeze = _as_2d(jnp.asarray(b, blas.compute_dtype(Q_shards.dtype)))
    fn = _build_qr_lstsq(geom, mesh_cache_key(mesh))
    x = fn(Q_shards, R_shards, b2)
    return x[:, 0] if squeeze else x


@functools.lru_cache(maxsize=16)
def _build_qr_lstsq(geom, mesh_key):
    from jax.sharding import PartitionSpec as P

    from conflux_tpu.parallel.mesh import (
        AXIS_X, AXIS_Y, AXIS_Z, lookup_mesh,
    )

    mesh = lookup_mesh(mesh_key)
    v, Px, Py = geom.v, geom.grid.Px, geom.grid.Py
    Ml, Nl = geom.Ml, geom.Nl
    n = geom.Nt  # R row tiles to substitute

    def qr_lstsq(Qblk, Rblk, b):
        x_ = lax.axis_index(AXIS_X)
        y_ = lax.axis_index(AXIS_Y)
        dtype = blas.compute_dtype(Qblk.dtype)
        Qloc = Qblk[0, 0].astype(dtype)
        Rloc = Rblk[0, 0].astype(dtype)
        b = b.astype(dtype)

        lr = jnp.arange(Ml, dtype=jnp.int32)
        grow = ((lr // v) * Px + x_) * v + (lr % v)
        lc = jnp.arange(Nl, dtype=jnp.int32)
        gcol = ((lc // v) * Py + y_) * v + (lc % v)
        nrhs = b.shape[1]
        i0 = jnp.zeros((), jnp.int32)

        # ---- c = Q^H b: local rows contribute, psum over 'x' ---------- #
        part = jnp.matmul(Qloc.conj().T, b[grow],
                          precision=lax.Precision.HIGHEST)  # (Nl, k)
        part = lax.psum(part, AXIS_X)
        # assemble replicated (N, k): each y owns disjoint global cols
        cv = lax.psum(
            jnp.zeros((geom.N, nrhs), dtype).at[gcol].set(part), AXIS_Y)

        # ---- back substitution R x = c over R's geometry -------------- #
        def bwd(i, xv):
            k = n - 1 - i
            kv = jnp.asarray(k * v, jnp.int32)
            ck = lax.dynamic_slice(cv, (kv, i0), (v, nrhs))
            xk = _solve_block(
                Rloc, k, xv, gcol >= (k + 1) * v, ck,
                lambda d, r: blas.trsm_left_upper(jnp.triu(d), r),
                gcol, v, Px, Py)
            return lax.dynamic_update_slice(xv, xk, (kv, i0))

        xv = lax.fori_loop(0, n, bwd, jnp.zeros((geom.N, nrhs), dtype))
        from conflux_tpu.parallel.mesh import replicate

        return replicate(xv, (AXIS_X, AXIS_Y, AXIS_Z))

    fn = shard_map(
        qr_lstsq,
        mesh=mesh,
        in_specs=(P(AXIS_X, AXIS_Y, None, None),
                  P(AXIS_X, AXIS_Y, None, None), P()),
        out_specs=P(),
    )
    return jax.jit(fn)
