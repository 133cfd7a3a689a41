"""Correctness oracles: factorization residuals.

TPU-native equivalent of the reference's CONFLUX_WITH_VALIDATION path, which
assembles the factors in ScaLAPACK layout and computes ||PA - LU||_F with two
`pdgemm_` calls (`examples/conflux_miniapp.cpp:404-500`). Here the residual
is a direct JAX computation — on a single host for tests, or on the gathered
result of a distributed run.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _norm_ratio(rss, ass) -> float:
    """sqrt(sum-of-squares ratio) with the zero-norm guard — shared
    epilogue of the three on-mesh residual oracles."""
    return float(np.sqrt(float(rss)) / max(np.sqrt(float(ass)), 1e-30))


def lu_residual(A, LU, perm) -> float:
    """Normalized ||A[perm] - L U||_F / ||A||_F for packed LU factors.

    Handles rectangular factorizations both ways: L is (M, K) unit-lower
    and U (K, N) upper with K = min(M, N)."""
    A = np.asarray(A)
    LU = np.asarray(LU)
    perm = np.asarray(perm)
    M, N = LU.shape
    K = min(M, N)
    L = np.tril(LU, -1)[:, :K] + np.eye(M, K, dtype=LU.dtype)
    U = np.triu(LU[:K, :])
    R = A[perm, :] - L @ U
    return float(np.linalg.norm(R) / max(np.linalg.norm(A), 1e-30))


def lu_residual_distributed(A_shards, LU_shards, perm, geom, mesh) -> float:
    """Gather-free ||A[perm] - L U||_F / ||A||_F, computed on the mesh.

    The role of the reference's ScaLAPACK validation (COSTA transforms +
    two `pdgemm_` calls, `examples/conflux_miniapp.cpp:404-500`): nothing
    (M, N)-sized ever exists on the host or on a single device. Two
    on-mesh passes, each a fori_loop of (v, Nl)/(Ml, v)-sized collectives:

      1. SUMMA product: for each column tile t, the owner column of L and
         owner row of U are broadcast (masked psums over 'y' / 'x') and
         every device accumulates its (Ml, Nl) share of L @ U.
      2. Row permutation: for each row tile t of *positions*, the original
         rows A[perm[t*v:(t+1)*v]] are assembled by a masked psum over 'x'
         and handed to the position owner — the same pattern as the
         factorization's pivot-row reduction.

    A_shards: the original matrix's block-cyclic shards (Px, Py, Ml, Nl)
    (original row order). LU_shards, perm: `lu_factor_distributed` outputs
    (factors in pivoted order). Returns the relative Frobenius residual.
    """
    from conflux_tpu.parallel.mesh import mesh_cache_key

    fn = _build_lu_residual(geom, mesh_cache_key(mesh))
    rss, ass = fn(A_shards, LU_shards, jnp.asarray(perm, jnp.int32))
    return _norm_ratio(rss, ass)


@functools.lru_cache(maxsize=16)
def _build_lu_residual(geom, mesh_key):
    from jax.sharding import PartitionSpec as P

    from conflux_tpu.parallel.mesh import (
        AXIS_X, AXIS_Y, AXIS_Z, lookup_mesh, pvary, shard_map,
    )

    mesh = lookup_mesh(mesh_key)
    v = geom.v
    Px, Py = geom.grid.Px, geom.grid.Py
    Ml, Nl = geom.Ml, geom.Nl
    Mt, Nt = geom.Mt, geom.Nt

    def lu_residual_sums(Ablk, LUblk, perm):
        x = lax.axis_index(AXIS_X)
        y = lax.axis_index(AXIS_Y)
        Aloc = Ablk[0, 0]
        dtype = jnp.float32 if Aloc.dtype == jnp.bfloat16 else Aloc.dtype
        Aloc = Aloc.astype(dtype)
        LUloc = LUblk[0, 0].astype(dtype)

        lr = jnp.arange(Ml, dtype=jnp.int32)
        gp = ((lr // v) * Px + x) * v + (lr % v)  # global position
        lc = jnp.arange(Nl, dtype=jnp.int32)
        gcol = ((lc // v) * Py + y) * v + (lc % v)
        i0 = jnp.zeros((), jnp.int32)

        # ---- pass 1: SUMMA accumulation of L @ U ---------------------- #
        def summa(t, acc):
            ly = ((t // Py) * v).astype(jnp.int32)
            Lcol = lax.dynamic_slice(LUloc, (i0, ly), (Ml, v))
            colt = t * v + jnp.arange(v, dtype=jnp.int32)
            Lcol = jnp.where(gp[:, None] > colt[None, :], Lcol, 0.0)
            Lcol = Lcol + (gp[:, None] == colt[None, :]).astype(dtype)
            Lcol = lax.psum(
                jnp.where(y == t % Py, Lcol, jnp.zeros((), dtype)), AXIS_Y)
            lx = ((t // Px) * v).astype(jnp.int32)
            Urow = lax.dynamic_slice(LUloc, (lx, i0), (v, Nl))
            Urow = jnp.where(colt[:, None] <= gcol[None, :], Urow, 0.0)
            Urow = lax.psum(
                jnp.where(x == t % Px, Urow, jnp.zeros((), dtype)), AXIS_X)
            return acc + jnp.matmul(Lcol, Urow,
                                    precision=lax.Precision.HIGHEST)

        zero0 = pvary(jnp.zeros((Ml, Nl), dtype),
                      (AXIS_X, AXIS_Y, AXIS_Z))
        prod = lax.fori_loop(0, Nt, summa, zero0)

        # ---- pass 2: assemble A[perm] rows at their positions --------- #
        def permrows(t, Ap):
            pv = lax.dynamic_slice(perm, (t * v,), (v,))  # original rows
            # my local rows holding those original rows (original order!)
            gri = gp  # A shards are in original row order: id == position
            match = gri[:, None] == pv[None, :]  # (Ml, v)
            owned = match.any(axis=0)
            li = jnp.where(owned, jnp.argmax(match, axis=0), Ml)
            part = jnp.take(Aloc, li, axis=0, mode="fill", fill_value=0)
            rows = lax.psum(part, AXIS_X)  # (v, Nl)
            dst = ((t // Px) * v).astype(jnp.int32)
            return jnp.where(
                x == t % Px,
                lax.dynamic_update_slice(Ap, rows, (dst, i0)),
                Ap,
            )

        Ap = lax.fori_loop(
            0, Mt, permrows,
            pvary(jnp.zeros((Ml, Nl), dtype),
                  (AXIS_X, AXIS_Y, AXIS_Z)))

        R = Ap - prod
        rss = lax.psum(jnp.sum((R * jnp.conj(R)).real), (AXIS_X, AXIS_Y))
        ass = lax.psum(jnp.sum((Aloc * jnp.conj(Aloc)).real),
                       (AXIS_X, AXIS_Y))
        # identical across z already; pmax satisfies replication
        return (lax.pmax(rss, AXIS_Z), lax.pmax(ass, AXIS_Z))

    fn = shard_map(
        lu_residual_sums,
        mesh=mesh,
        in_specs=(P(AXIS_X, AXIS_Y, None, None),
                  P(AXIS_X, AXIS_Y, None, None), P()),
        out_specs=(P(), P()),
    )
    return jax.jit(fn)


def cholesky_residual(A, L) -> float:
    """Normalized ||A - L L^H||_F / ||A||_F for a lower Cholesky factor
    (^H == ^T for real dtypes)."""
    A = np.asarray(A)
    L = np.tril(np.asarray(L))
    R = A - L @ L.conj().T
    return float(np.linalg.norm(R) / max(np.linalg.norm(A), 1e-30))


def cholesky_residual_distributed(A_shards, L_shards, geom, mesh) -> float:
    """Gather-free ||A - L L^H||_F / ||A||_F on the mesh — the Cholesky
    counterpart of :func:`lu_residual_distributed` (reference pdgemm
    validation role; ^H == ^T for real dtypes). One SUMMA pass: for each
    column tile t, the lower-triangular column slab of L is y-broadcast
    and its conjugate-transpose-rows are delivered to column owners by the
    same masked-psum exchange the factorization's scatterA11 uses; every
    device accumulates its share of L L^H. No (N, N) array exists
    anywhere.
    """
    from conflux_tpu.parallel.mesh import mesh_cache_key

    fn = _build_cholesky_residual(geom, mesh_cache_key(mesh))
    rss, ass = fn(A_shards, L_shards)
    return _norm_ratio(rss, ass)


@functools.lru_cache(maxsize=16)
def _build_cholesky_residual(geom, mesh_key):
    from jax.sharding import PartitionSpec as P

    from conflux_tpu.parallel.mesh import (
        AXIS_X, AXIS_Y, AXIS_Z, lookup_mesh, pvary, shard_map,
    )

    mesh = lookup_mesh(mesh_key)
    v = geom.v
    Px, Py = geom.grid.Px, geom.grid.Py
    Ml, Nl = geom.Ml, geom.Nl
    Nt = geom.Kappa  # tile columns == supersteps

    def cholesky_residual_sums(Ablk, Lblk):
        x = lax.axis_index(AXIS_X)
        y = lax.axis_index(AXIS_Y)
        Aloc = Ablk[0, 0]
        dtype = jnp.float32 if Aloc.dtype == jnp.bfloat16 else Aloc.dtype
        Aloc = Aloc.astype(dtype)
        Lloc = Lblk[0, 0].astype(dtype)

        lr = jnp.arange(Ml, dtype=jnp.int32)
        gp = ((lr // v) * Px + x) * v + (lr % v)  # global row index
        lc = jnp.arange(Nl, dtype=jnp.int32)
        gcol = ((lc // v) * Py + y) * v + (lc % v)
        col_owner_x = (gcol // v) % Px
        col_local_row = ((gcol // v) // Px) * v + gcol % v
        i0 = jnp.zeros((), jnp.int32)

        def summa(t, acc):
            colt = t * v + jnp.arange(v, dtype=jnp.int32)
            ly = ((t // Py) * v).astype(jnp.int32)
            Lcol = lax.dynamic_slice(Lloc, (i0, ly), (Ml, v))
            Lcol = jnp.where(gp[:, None] >= colt[None, :], Lcol, 0.0)
            Lcol = lax.psum(
                jnp.where(y == t % Py, Lcol, jnp.zeros((), dtype)), AXIS_Y)
            # rows of L^T for my columns: L[gcol, t-block], delivered from
            # each row's x-owner (the scatterA11 exchange pattern)
            from_L = jnp.where(
                (col_owner_x == x)[:, None],
                jnp.take(Lcol, col_local_row, axis=0, mode="fill",
                         fill_value=0),
                jnp.zeros((), dtype))
            # conj().T: the product is L L^H for complex dtypes
            LrowT = lax.psum(from_L, AXIS_X).conj().T  # (v, Nl)
            return acc + jnp.matmul(Lcol, LrowT,
                                    precision=lax.Precision.HIGHEST)

        zero0 = pvary(jnp.zeros((Ml, Nl), dtype),
                      (AXIS_X, AXIS_Y, AXIS_Z))
        prod = lax.fori_loop(0, Nt, summa, zero0)

        R = Aloc - prod
        rss = lax.psum(jnp.sum((R * jnp.conj(R)).real), (AXIS_X, AXIS_Y))
        ass = lax.psum(jnp.sum((Aloc * jnp.conj(Aloc)).real), (AXIS_X, AXIS_Y))
        return (lax.pmax(rss, AXIS_Z), lax.pmax(ass, AXIS_Z))

    fn = shard_map(
        cholesky_residual_sums,
        mesh=mesh,
        in_specs=(P(AXIS_X, AXIS_Y, None, None),
                  P(AXIS_X, AXIS_Y, None, None)),
        out_specs=(P(), P()),
    )
    return jax.jit(fn)


def residual_bound(n: int, dtype) -> float:
    """Acceptance threshold: c * sqrt(n) * eps, with headroom for pivot growth."""
    eps = float(jnp.finfo(dtype).eps)
    return 100.0 * np.sqrt(n) * eps


def make_test_matrix(M: int, N: int, seed: int = 42, dtype=np.float64) -> np.ndarray:
    """Deterministic well-conditioned random matrix (the role of the
    reference's seeded `InitMatrix`, `lu_params.hpp:141-376`, without its
    hard-coded fixtures)."""
    rng = np.random.default_rng(seed)
    A = rng.uniform(-1.0, 1.0, size=(M, N)).astype(dtype)
    # diagonal boost keeps condition number moderate without killing pivoting
    d = min(M, N)
    A[np.arange(d), np.arange(d)] += 2.0
    return A


def make_spd_matrix(N: int, seed: int = 7, dtype=np.float64) -> np.ndarray:
    """Deterministic SPD matrix (role of `CholeskyIO::generateInputMatrixDistributed`,
    `CholeskyIO.cpp:100-172`: random symmetric + diagonal dominance)."""
    rng = np.random.default_rng(seed)
    B = rng.uniform(-1.0, 1.0, size=(N, N)).astype(dtype)
    A = (B + B.T) / 2
    A[np.arange(N), np.arange(N)] += N
    return A


def make_hpd_matrix(N: int, seed: int = 7,
                    dtype=np.complex128) -> np.ndarray:
    """Deterministic Hermitian positive-definite matrix (the complex
    instantiation of :func:`make_spd_matrix`: random Hermitian + diagonal
    dominance; the diagonal is real by construction)."""
    rng = np.random.default_rng(seed)
    B = (rng.uniform(-1.0, 1.0, size=(N, N))
         + 1j * rng.uniform(-1.0, 1.0, size=(N, N))).astype(dtype)
    A = (B + B.conj().T) / 2
    A[np.arange(N), np.arange(N)] += N
    return A


def qr_residual_distributed(A_shards, Q_shards, R_shards, geom, mesh):
    """Gather-free (||A - Q R||_F/||A||_F, ||Q^T Q - I||_F/sqrt(N)) on the
    mesh — the QR counterpart of :func:`lu_residual_distributed` (pdgemm
    validation role). One SUMMA loop over column tiles: the owner's Q
    column slab is y-broadcast and R's row slab x-broadcast (masked
    psums), every device accumulates its share of Q R; the same Q column
    slab also yields an orthogonality strip Q^T Qcol - I. Both error
    checks without any (N, N) array. Complex inputs use the Hermitian
    adjoint throughout."""
    from conflux_tpu.parallel.mesh import mesh_cache_key

    fn = _build_qr_residual(geom, mesh_cache_key(mesh))
    rss, ass, oss = fn(jnp.asarray(A_shards), jnp.asarray(Q_shards),
                       jnp.asarray(R_shards))
    return _norm_ratio(rss, ass), float(np.sqrt(float(oss)) / np.sqrt(geom.N))


@functools.lru_cache(maxsize=16)
def _build_qr_residual(geom, mesh_key):
    from jax.sharding import PartitionSpec as P

    from conflux_tpu.parallel.mesh import (
        AXIS_X, AXIS_Y, AXIS_Z, lookup_mesh, pvary, shard_map,
    )

    mesh = lookup_mesh(mesh_key)
    v, Px, Py = geom.v, geom.grid.Px, geom.grid.Py
    Ml, Nl, Nt = geom.Ml, geom.Nl, geom.Nt

    def qr_residual_sums(Ablk, Qblk, Rblk):
        x = lax.axis_index(AXIS_X)
        y = lax.axis_index(AXIS_Y)
        from conflux_tpu.ops import blas as _blas

        dtype = _blas.compute_dtype(Ablk.dtype)
        Aloc = Ablk[0, 0].astype(dtype)
        Qloc = Qblk[0, 0].astype(dtype)
        Rloc = Rblk[0, 0].astype(dtype)
        lc = jnp.arange(Nl, dtype=jnp.int32)
        gcol = ((lc // v) * Py + y) * v + (lc % v)
        i0 = jnp.zeros((), jnp.int32)

        def body(t, carry):
            prod, oss = carry
            ly = ((t // Py) * v).astype(jnp.int32)
            lx = ((t // Px) * v).astype(jnp.int32)
            Qcol = lax.psum(
                jnp.where(y == t % Py,
                          lax.dynamic_slice(Qloc, (i0, ly), (Ml, v)),
                          jnp.zeros((), dtype)), AXIS_Y)  # (Ml, v)
            Rrow = lax.psum(
                jnp.where(x == t % Px,
                          lax.dynamic_slice(Rloc, (lx, i0), (v, Nl)),
                          jnp.zeros((), dtype)), AXIS_X)  # (v, Nl)
            prod = prod + jnp.matmul(Qcol, Rrow,
                                     precision=lax.Precision.HIGHEST)
            # orthogonality strip: G[my cols, tile-t cols] via psum over
            # rows (x); replicated over x afterwards, so only x == 0
            # devices contribute to the sum of squares
            strip = lax.psum(
                jnp.matmul(Qloc.conj().T, Qcol,
                           precision=lax.Precision.HIGHEST), AXIS_X)
            eye = (gcol[:, None]
                   == (t * v + jnp.arange(v, dtype=jnp.int32))[None, :])
            E = strip - eye.astype(dtype)
            oss = oss + jnp.where(
                x == 0, jnp.sum((E * jnp.conj(E)).real), 0.0)
            return prod, oss

        rdtype = jnp.zeros((), dtype).real.dtype
        zero = pvary(jnp.zeros((Ml, Nl), dtype),
                     (AXIS_X, AXIS_Y, AXIS_Z))
        zoss = pvary(jnp.zeros((), rdtype),
                     (AXIS_X, AXIS_Y, AXIS_Z))
        prod, oss = lax.fori_loop(0, Nt, body, (zero, zoss))
        E = Aloc - prod
        rss = lax.psum(jnp.sum((E * jnp.conj(E)).real), (AXIS_X, AXIS_Y))
        ass = lax.psum(jnp.sum((Aloc * jnp.conj(Aloc)).real), (AXIS_X, AXIS_Y))
        oss = lax.psum(oss, (AXIS_X, AXIS_Y))
        return (lax.pmax(rss, AXIS_Z), lax.pmax(ass, AXIS_Z),
                lax.pmax(oss, AXIS_Z))

    fn = shard_map(
        qr_residual_sums,
        mesh=mesh,
        in_specs=(P(AXIS_X, AXIS_Y, None, None),
                  P(AXIS_X, AXIS_Y, None, None),
                  P(AXIS_X, AXIS_Y, None, None)),
        out_specs=(P(), P(), P()),
    )
    return jax.jit(fn)


# --------------------------------------------------------------------------- #
# Test matrices generated where they live, and oracles that regenerate them
# --------------------------------------------------------------------------- #
#
# At chip scale the matrix cannot come from the host (a 4 GB transfer per
# run) and cannot be kept next to its factors (A + LU + a product exhausts
# a 16 GB chip at N=32768). So entry (i, j) is a counter-based hash of
# (seed, i, j): any block is generated on the device that holds it, with no
# communication, and an oracle regenerates exactly the rows it needs. The
# family is `make_test_matrix`'s — uniform[-1, 1) entries plus 2 on the
# diagonal — and, with `spd=True`, `make_spd_matrix`'s role: symmetric
# uniform entries scaled by 1/sqrt(n) plus 2I (eigenvalues in ~[0.85, 3.2]).


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def seeded_entries(rows, cols, n: int, seed: int, spd: bool = False):
    """Entries A[rows, cols] of the seeded n x n test matrix, float32.
    `rows`/`cols` are broadcastable int32 arrays of global indices.
    Traceable; the same (seed, i, j) always gives the same bits."""
    r = jnp.asarray(rows).astype(jnp.uint32)
    c = jnp.asarray(cols).astype(jnp.uint32)
    if spd:
        r, c = jnp.minimum(r, c), jnp.maximum(r, c)
    h = _fmix32(r * jnp.uint32(n) + c
                ^ _fmix32(jnp.uint32(seed & 0xFFFFFFFF)))
    u = (h >> 8).astype(jnp.float32) * np.float32(2.0 ** -23) - 1.0
    if spd:
        u = u / np.float32(np.sqrt(n))
    eye = (jnp.asarray(rows) == jnp.asarray(cols)).astype(jnp.float32)
    return u + 2.0 * eye


@functools.lru_cache(maxsize=8)
def _build_seeded_shards(geom, mesh_key, seed: int, spd: bool):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from conflux_tpu.parallel.mesh import AXIS_X, AXIS_Y, lookup_mesh

    mesh = lookup_mesh(mesh_key)
    v, Px, Py = geom.v, geom.grid.Px, geom.grid.Py
    n = geom.N

    def make():
        lr = jnp.arange(geom.Ml, dtype=jnp.int32)
        lc = jnp.arange(geom.Nl, dtype=jnp.int32)
        px = jnp.arange(Px, dtype=jnp.int32)[:, None]
        py = jnp.arange(Py, dtype=jnp.int32)[:, None]
        grow = ((lr // v) * Px + px) * v + lr % v  # (Px, Ml)
        gcol = ((lc // v) * Py + py) * v + lc % v  # (Py, Nl)
        return seeded_entries(grow[:, None, :, None], gcol[None, :, None, :],
                            n, seed, spd)

    return jax.jit(make, out_shardings=NamedSharding(
        mesh, P(AXIS_X, AXIS_Y, None, None)))


def seeded_shards(geom, mesh, seed: int, spd: bool = False):
    """Block-cyclic shards (Px, Py, Ml, Nl) of the seeded test matrix,
    each generated on the device that holds it. `geom` is an LUGeometry
    or CholeskyGeometry of a square problem."""
    from conflux_tpu.parallel.mesh import mesh_cache_key

    return _build_seeded_shards(geom, mesh_cache_key(mesh), int(seed),
                              bool(spd))()


@functools.lru_cache(maxsize=8)
def _build_strips(n: int, blk: int, seed: int, spd: bool):
    @jax.jit
    def ssq(F, perm):
        rows = jnp.arange(n, dtype=jnp.int32)
        F = F.astype(jnp.float32)
        rss = jnp.zeros((), jnp.float32)
        ass = jnp.zeros((), jnp.float32)
        for i in range(0, n, blk):
            ri = rows[i:i + blk, None]
            # A[perm] strip regenerated: A itself never exists here
            Ap_i = seeded_entries(ri if spd else perm[i:i + blk, None],
                                  rows[None, :], n, seed, spd)
            if spd:
                Li = jnp.where(ri >= rows[None, :], F[i:i + blk], 0.0)
            else:
                Li = jnp.where(ri > rows[None, :], F[i:i + blk], 0.0) \
                    + jnp.eye(blk, n, i, dtype=F.dtype)
            acc = jnp.zeros((blk, n), jnp.float32)
            for j in range(0, n, blk):
                if spd:  # (L^T)[:, j-strip] = (lower part of rows j..)^T
                    Uj = jnp.where(rows[j:j + blk, None] >= rows[None, :],
                                   F[j:j + blk], 0.0).T
                else:
                    Uj = jnp.where(rows[:, None] <= rows[None, j:j + blk],
                                   F[:, j:j + blk], 0.0)
                acc = lax.dynamic_update_slice(
                    acc, jnp.matmul(Li, Uj, precision=lax.Precision.HIGHEST),
                    (0, j))
            R = Ap_i - acc
            rss = rss + jnp.sum(R * R)
            ass = ass + jnp.sum(Ap_i * Ap_i)
        return rss, ass

    return ssq


def _strips_residual(F, perm, seed: int, spd: bool, block: int) -> float:
    n = F.shape[0]
    blk = math.gcd(n, block)
    if n // blk > 64:
        raise ValueError(
            f"residual strips need a height dividing n={n} and {block}; "
            f"gcd {blk} would unroll {n // blk} strips")
    if perm is None:
        perm = jnp.arange(n, dtype=jnp.int32)
    rss, ass = _build_strips(n, blk, int(seed), spd)(F, perm)
    return _norm_ratio(rss, ass)


def lu_residual_strips(LU, perm, seed: int, block: int = 4096) -> float:
    """||A[perm] - L U||_F / ||A||_F on ONE device for the seeded test
    matrix A (`seeded_entries`), exact. (blk, n) strips of L and (n, blk)
    strips of U, with A[perm]'s strip regenerated in place, keep peak
    memory at LU + O(strip): 2 n^3 flops, ~3 s at n=32768 on a v5e. The
    strip height is gcd(n, block); sizes whose gcd would unroll into
    more than 64 strips are rejected."""
    return _strips_residual(LU, perm, seed, False, block)


def cholesky_residual_strips(L, seed: int, block: int = 4096) -> float:
    """||S - L L^T||_F / ||S||_F on ONE device for the seeded SPD test
    matrix S (`seeded_entries(..., spd=True)`), exact, in the strips of
    :func:`lu_residual_strips` (only L's lower triangle is read)."""
    return _strips_residual(L, None, seed, True, block)


def lu_residual_projected(LU_shards, perm, geom, mesh, seed: int,
                          k: int = 16) -> float:
    """Projected ||A[perm] - L U||_F / ||A||_F of distributed LU factors
    of the seeded test matrix, on the mesh in O(n^2 k) work: with X an
    (n, k) standard normal block, E ||R X||_F^2 = k ||R||_F^2, so
    ||R X||_F / sqrt(k) estimates ||R||_F (relative spread ~sqrt(2/k)).
    U X, then L (U X), then A[perm] X with A regenerated tile by tile —
    nothing but the factors is resident, which is what lets the check run
    next to N=65536 factors on 16 GB chips. ||A||_F is exact."""
    from conflux_tpu.parallel.mesh import mesh_cache_key

    fn = _build_lu_projected(geom, mesh_cache_key(mesh), int(seed), int(k))
    X = jax.random.normal(jax.random.PRNGKey(seed), (geom.N, k),
                          jnp.float32)
    rss, ass = fn(LU_shards, jnp.asarray(perm, jnp.int32), X)
    return float(np.sqrt(float(rss) / k) / max(np.sqrt(float(ass)), 1e-30))


@functools.lru_cache(maxsize=8)
def _build_lu_projected(geom, mesh_key, seed: int, k: int):
    from jax.sharding import PartitionSpec as P

    from conflux_tpu.parallel.mesh import (
        AXIS_X, AXIS_Y, AXIS_Z, lookup_mesh, pvary, shard_map,
    )

    mesh = lookup_mesh(mesh_key)
    v, Px, Py = geom.v, geom.grid.Px, geom.grid.Py
    Ml, Nl, n = geom.Ml, geom.Nl, geom.N
    Ntl = Nl // v

    def lu_projected_sums(LUblk, perm, X):
        x = lax.axis_index(AXIS_X)
        y = lax.axis_index(AXIS_Y)
        LUloc = LUblk[0, 0].astype(jnp.float32)
        lr = jnp.arange(Ml, dtype=jnp.int32)
        gp = ((lr // v) * Px + x) * v + lr % v  # my factor rows' positions
        lc = jnp.arange(Nl, dtype=jnp.int32)
        gcol = ((lc // v) * Py + y) * v + lc % v
        orig = jnp.take(perm, gp)  # original rows behind those positions
        i0 = jnp.zeros((), jnp.int32)
        axes = (AXIS_X, AXIS_Y, AXIS_Z)

        def tile(t):
            c0 = (t * v).astype(jnp.int32)
            return (lax.dynamic_slice(LUloc, (i0, c0), (Ml, v)),
                    lax.dynamic_slice(gcol, (c0,), (v,)))

        def ux_step(t, acc):
            blk, gct = tile(t)
            Ut = jnp.where(gp[:, None] <= gct[None, :], blk, 0.0)
            return acc + jnp.matmul(Ut, jnp.take(X, gct, axis=0),
                                    precision=lax.Precision.HIGHEST)

        UXp = lax.psum(lax.fori_loop(
            0, Ntl, ux_step, pvary(jnp.zeros((Ml, k), jnp.float32), axes)),
            AXIS_Y)
        # every device needs U X at its own columns: assemble it whole
        UX = lax.psum(jnp.zeros((n, k), jnp.float32).at[gp].set(UXp),
                      AXIS_X)

        def lx_step(t, carry):
            lux, ax, asq = carry
            blk, gct = tile(t)
            Lt = (jnp.where(gp[:, None] > gct[None, :], blk, 0.0)
                  + (gp[:, None] == gct[None, :]).astype(jnp.float32))
            lux = lux + jnp.matmul(Lt, jnp.take(UX, gct, axis=0),
                                   precision=lax.Precision.HIGHEST)
            At = seeded_entries(orig[:, None], gct[None, :], n, seed)
            ax = ax + jnp.matmul(At, jnp.take(X, gct, axis=0),
                                 precision=lax.Precision.HIGHEST)
            return lux, ax, asq + jnp.sum(At * At)

        z = pvary(jnp.zeros((Ml, k), jnp.float32), axes)
        lux, ax, asq = lax.fori_loop(
            0, Ntl, lx_step,
            (z, z, pvary(jnp.zeros((), jnp.float32), axes)))
        R = lax.psum(ax - lux, AXIS_Y)
        rss = lax.psum(jnp.sum(R * R), AXIS_X)
        ass = lax.psum(asq, (AXIS_X, AXIS_Y))
        # identical across y and z already; pmax satisfies replication
        return (lax.pmax(lax.pmax(rss, AXIS_Y), AXIS_Z),
                lax.pmax(ass, AXIS_Z))

    fn = shard_map(
        lu_projected_sums,
        mesh=mesh,
        in_specs=(P(AXIS_X, AXIS_Y, None, None), P(), P()),
        out_specs=(P(), P()),
    )
    return jax.jit(fn)
