"""Distributed 2.5D Cholesky over the (x, y, z) mesh.

TPU-native re-design of the reference's CONFCHOX driver
(`Cholesky.cpp:743-784` phases: choleskyA00 -> updateComputeA10 ->
computeA11 -> reduceA11 -> scatterA11). Same design language as
`conflux_tpu.lu.distributed`, minus pivoting:

 - block-cyclic (x, y) tile shards holding z-partial sums; the true matrix
   is the sum over 'z'; factors are written on layer z==0 only;
 - panel column k: one `psum` over ('y','z') (reference reduceA11 +
   scatterA11 rolled into a single collective);
 - diagonal tile broadcast (reference's shrinking-bcast-comm machinery,
   `Processor.cpp:131-250`): a masked `psum` over 'x' — fixed mesh
   collectives make the ladder of communicators unnecessary (SURVEY P7);
 - L10^T redistribution row-owners -> column-owners (reference's
   MPI_SUBTILE Isend mesh, `Cholesky.cpp:459-480`): a masked-gather `psum`
   over 'x' delivering exactly the rows each device's columns need;
 - trailing update: each z layer multiplies its nlayr-wide slab of the
   panel (reference's subtile split `l = v/Pz`), sharing the syrk flops
   across layers; `MPI_Waitany`-driven overlap (reference
   `Cholesky.cpp:487-550`) is the XLA latency-hiding scheduler's job, not
   ours.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from conflux_tpu.geometry import CholeskyGeometry, Grid3, ragged_segments
from conflux_tpu.ops import blas
from conflux_tpu.parallel.mesh import (
    AXIS_X,
    AXIS_Y,
    AXIS_Z,
    lookup_mesh,
    make_mesh,
    mesh_cache_key,
    shard_map,
)


@functools.lru_cache(maxsize=32)
def _build(geom: CholeskyGeometry, mesh_key, precision, backend: str,
           donate: bool = False, resumable: bool = False,
           lookahead: bool = False, segs: tuple = (8, 8)):
    mesh = lookup_mesh(mesh_key)
    v = geom.v
    Px, Py, Pz = geom.grid.Px, geom.grid.Py, geom.grid.Pz
    Ml, Nl = geom.Ml, geom.Nl
    nlayr = geom.nlayr
    n_steps = geom.Kappa
    v_pad = Pz * nlayr

    # trailing-update segmentation (same idea as lu.distributed): both the
    # live rows (rtile > k) and live columns (ctile > k) are contiguous
    # local suffixes under the block-cyclic map, so ceil-divide each axis
    # into ragged segments and skip dead (row, col) blocks with lax.cond —
    # GEMM work stays near the true N^3/3P instead of the 3x a
    # full-local-shape masked update would spend. Segments whose tiles lie
    # ENTIRELY above the diagonal are skipped too: the factorization never
    # reads the strict upper triangle (future panels mask rows above their
    # diagonal), so updating it is pure waste — segment-level triangle
    # skipping approaches the reference's lower-triangle-only owner set
    # (`Cholesky.cpp:333-355`) as the segmentation refines; mixed segments
    # still update their (unread, unspecified) upper elements.
    row_bounds = ragged_segments(Ml // v, v, segs[0])
    col_bounds = ragged_segments(Nl // v, v, segs[1])

    def cholesky_factor(blk, k0=0, k_end=n_steps):
        x = lax.axis_index(AXIS_X)
        y = lax.axis_index(AXIS_Y)
        z = lax.axis_index(AXIS_Z)
        dtype = blk.dtype

        Aloc = jnp.where(z == 0, blk[0, 0], jnp.zeros((), dtype))

        lr = jnp.arange(Ml, dtype=jnp.int32)
        rtile = (lr // v) * Px + x  # global row-tile id per local row
        lc = jnp.arange(Nl, dtype=jnp.int32)
        ctile = (lc // v) * Py + y  # global col-tile id per local col
        # my local columns' global row coordinates (for the L10^T exchange):
        # column with global index g corresponds to row g, owned by x-rank
        # (g // v) % Px at local row ((g // v) // Px) * v + g % v
        gcol = ctile * v + (lc % v)
        col_owner_x = (gcol // v) % Px
        col_local_row = ((gcol // v) // Px) * v + gcol % v

        cdtype = blas.compute_dtype(dtype)

        def panel_reduce(Aloc, k):
            """Panel column k: z-reduce + y-broadcast (reference
            reduceA11 + scatterA11 rolled into one collective); panel
            math runs in the compute dtype (f32 when storage is bf16)."""
            yo = k % Py
            lj = jnp.asarray((k // Py) * v, jnp.int32)  # k may be a py int
            panel_loc = lax.dynamic_slice(
                Aloc, (jnp.zeros((), jnp.int32), lj), (Ml, v))
            return lax.psum(
                jnp.where(y == yo, panel_loc, jnp.zeros((), dtype)),
                (AXIS_Y, AXIS_Z),
            ).astype(cdtype)

        def body_core(k, Aloc, panel):
            i0 = jnp.zeros((), jnp.int32)
            xo = (k % Px).astype(jnp.int32)  # diag tile row owner
            yo = (k % Py).astype(jnp.int32)  # panel column owner
            lj = ((k // Py) * v).astype(jnp.int32)
            ldiag = ((k // Px) * v).astype(jnp.int32)

            # ---- diagonal tile: x-broadcast + potrf ----------------------- #
            with jax.named_scope("choleskyA00"):
                diag_slice = lax.dynamic_slice(panel, (ldiag, i0), (v, v))
                Akk = lax.psum(
                    jnp.where(x == xo, diag_slice, jnp.zeros((), cdtype)), AXIS_X
                )
                L00 = blas.potrf(Akk)

            # ---- L10 for rows below the diagonal (row-segmented) ---------- #
            # segment liveness as scalar tile-index compares (liveness is
            # monotone in the local tile index; see lu.distributed)
            def seg_r_live(rhi):
                return ((rhi - 1) // v) * Px + x > k

            def seg_c_live(chi):
                return ((chi - 1) // v) * Py + y > k

            with jax.named_scope("updateA10"):
                below = rtile > k
                pieces = []
                for rlo, rhi in row_bounds:
                    rm = below[rlo:rhi]
                    pieces.append(lax.cond(
                        seg_r_live(rhi),
                        lambda p, m: blas.trsm_right_lower_t(
                            L00, jnp.where(m[:, None], p,
                                           jnp.zeros((), cdtype))),
                        lambda p, m: jnp.zeros_like(p),
                        panel[rlo:rhi], rm,
                    ))
                L10 = (jnp.concatenate(pieces, axis=0)
                       if len(pieces) > 1 else pieces[0])  # (Ml, v)

            # ---- L10^T redistribution to column owners over 'x' ----------- #
            # row g of the global panel -> every device whose columns include
            # g; diag-tile columns take L00 rows
            with jax.named_scope("scatterA11"):
                from_L10 = jnp.where(
                    (col_owner_x == x)[:, None], L10[col_local_row],
                    jnp.zeros((), cdtype)
                )
                Lc = lax.psum(from_L10, AXIS_X)  # (Nl, v) = L10 rows for my cols
                diag_cols = ctile == k
                L00_rows = L00[gcol % v]  # (Nl, v), valid where diag_cols
                Lc = jnp.where(diag_cols[:, None], L00_rows, Lc)

            # ---- trailing syrk-style update on this layer's slab ---------- #
            # GEMM rides the storage dtype (bf16 fast path when selected)
            L10p = jnp.pad(L10.astype(dtype), ((0, 0), (0, v_pad - v)))
            Lcp = jnp.pad(Lc.astype(dtype), ((0, 0), (0, v_pad - v)))
            zoff = (z * nlayr).astype(jnp.int32)
            L10s = lax.dynamic_slice(L10p, (i0, zoff), (Ml, nlayr))
            Lcs = lax.dynamic_slice(Lcp, (i0, zoff), (Nl, nlayr))
            col_trail = ctile > k

            # (reference computeA11 phase) — in-place cond'd DUS per live
            # segment; a slice->concat formulation materializes the full
            # local matrix every step (measured ~26 ms/step of pure copies
            # in the LU loop at N=32768 before the same change)
            with jax.named_scope("computeA11"):
                Anew = Aloc
                for rlo, rhi in row_bounds:
                    rm = below[rlo:rhi]
                    for clo, chi in col_bounds:
                        cm = col_trail[clo:chi]

                        def seg_update(A, rlo=rlo, rhi=rhi, clo=clo, chi=chi,
                                       rm=rm, cm=cm):
                            a_seg = lax.slice(A, (rlo, clo), (rhi, chi))
                            # conj().T = herk for complex dtypes (no-op
                            # conj on real, folded by XLA)
                            upd = blas.gemm(L10s[rlo:rhi],
                                            Lcs[clo:chi].conj().T,
                                            precision=precision,
                                            backend=backend)
                            keep = rm[:, None] & cm[None, :]
                            new = a_seg - jnp.where(keep, upd,
                                                    jnp.zeros((), dtype))
                            return lax.dynamic_update_slice(A, new,
                                                            (rlo, clo))

                        # touches_lower: the segment's last row tile
                        # reaches (or passes) its first column tile —
                        # false means every tile is strictly upper and
                        # the segment's content is never read again
                        touches_lower = (
                            ((rhi - 1) // v) * Px + x
                            >= (clo // v) * Py + y)
                        Anew = lax.cond(
                            seg_r_live(rhi) & seg_c_live(chi)
                            & touches_lower,
                            seg_update, lambda A: A, Anew)

            # ---- factor writes: panel column on layer z==0 ---------------- #
            on_diag = rtile == k
            L00_local = jnp.where(
                z == 0, jnp.tril(L00)[lr % v].astype(dtype), jnp.zeros((), dtype)
            )
            pcol_cur = lax.dynamic_slice(Anew, (i0, lj), (Ml, v))
            pcol_new = jnp.where(
                on_diag[:, None],
                L00_local,
                jnp.where(below[:, None],
                          jnp.where(z == 0, L10.astype(dtype), jnp.zeros((), dtype)),
                          pcol_cur),
            )
            Anew = jnp.where(
                y == yo,
                lax.dynamic_update_slice(Anew, pcol_new, (i0, lj)),
                Anew,
            )
            return Anew, dict(L10s=L10s, Lcs=Lcs, below=below)

        def body(k, carry):
            Aloc = carry
            with jax.named_scope("reduceA11"):
                panel = panel_reduce(Aloc, k)
            Anew, _ = body_core(k, Aloc, panel)
            return Anew

        def body_la(k, carry):
            # software-pipelined body (see lu.distributed.body_la): the
            # panel for step k rides the carry; step k+1's panel comes from
            # a separately-updated column slab of the PRE-update matrix, so
            # its reduce has no data dependence on the trailing GEMMs and
            # can overlap them on a mesh. Slab math mirrors the segment
            # updates operand-for-operand (bitwise-identical results).
            Aloc, panel = carry
            Anew, art = body_core(k, Aloc, panel)
            kn = k + 1
            i0 = jnp.zeros((), jnp.int32)

            def compute_next(_):
                with jax.named_scope("reduceA11"):
                    lj1 = ((kn // Py) * v).astype(jnp.int32)
                    slab = lax.dynamic_slice(Aloc, (i0, lj1), (Ml, v))
                    upd = blas.gemm(
                        art["L10s"],
                        lax.dynamic_slice(art["Lcs"], (lj1, i0),
                                          (v, nlayr)).conj().T,
                        precision=precision, backend=backend)
                    slab = slab - jnp.where(art["below"][:, None], upd,
                                            jnp.zeros((), dtype))
                    yo1 = (kn % Py).astype(jnp.int32)
                    return lax.psum(
                        jnp.where(y == yo1, slab, jnp.zeros((), dtype)),
                        (AXIS_Y, AXIS_Z)).astype(cdtype)

            panel_next = lax.cond(kn < k_end, compute_next,
                                  lambda _: panel, 0)
            return Anew, panel_next

        if lookahead:
            with jax.named_scope("reduceA11"):
                panel0 = panel_reduce(Aloc, k0)
            Aloc, _ = lax.fori_loop(k0, k_end, body_la, (Aloc, panel0))
        else:
            Aloc = lax.fori_loop(k0, k_end, body, Aloc)
        Aout = lax.psum(Aloc, AXIS_Z)
        return Aout[None, None]

    shard_spec = P(AXIS_X, AXIS_Y, None, None)
    if resumable:
        in_specs, out_specs = (shard_spec, P(), P()), shard_spec
    else:
        in_specs, out_specs = shard_spec, shard_spec
    fn = shard_map(cholesky_factor, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def build_program(geom: CholeskyGeometry, mesh, precision=None,
                  backend: str | None = None, donate: bool = False,
                  resumable: bool = False, lookahead: bool = False,
                  segs: tuple = (8, 8)):
    """The jitted distributed-Cholesky program (cached per config) — the
    single point resolving trace-time defaults and the CPU donate guard;
    `cholesky_factor_distributed` goes through here. Direct use is for
    callers needing compile artifacts — e.g. the miniapp's `--profile`
    per-phase device table (see `lu.distributed.build_program`)."""
    precision = blas.matmul_precision() if precision is None else precision
    backend = blas.get_backend() if backend is None else backend
    if donate and next(iter(mesh.devices.flat)).platform == "cpu":
        donate = False  # CPU PJRT has no buffer donation (warns per call)
    if len(segs) != 2 or segs[0] < 1 or segs[1] < 1:
        raise ValueError(
            f"segs must be two positive segment counts, got {segs!r} "
            "(non-positive counts would silently skip trailing updates)")
    return _build(geom, mesh_cache_key(mesh), precision, backend, donate,
                  resumable, lookahead, tuple(segs))


def cholesky_factor_steps(shards, geom: CholeskyGeometry, mesh,
                          k0: int, k1: int, precision=None,
                          backend: str | None = None, donate: bool = False,
                          segs: tuple = (8, 8)):
    """Factor supersteps [k0, k1) only — checkpoint/restart for Cholesky
    (no pivot state to carry, unlike `lu.distributed.lu_factor_steps`):
    feed each call's output shards into the next; after the last call the
    lower triangle holds L as `cholesky_factor_distributed` computes it —
    bit-identically when Pz == 1; with Pz > 1 the checkpoint consolidates
    the 2.5D z-partial sums, so a resumed run is numerically equivalent
    but re-associates f32 additions (same caveat as `lu_factor_steps`).
    `segs` matches `cholesky_factor_distributed` so a resumed run keeps
    the tuned segmentation of the run it resumes (segmentation is
    math-invariant; only performance differs).
    """
    if not (0 <= k0 < k1 <= geom.Kappa):
        raise ValueError(f"step range [{k0}, {k1}) outside [0, {geom.Kappa})")
    # traced step bounds: one compiled program serves every segment
    fn = build_program(geom, mesh, precision=precision, backend=backend,
                       donate=donate, resumable=True, segs=segs)
    return fn(shards, jnp.int32(k0), jnp.int32(k1))


def cholesky_factor_distributed(shards, geom: CholeskyGeometry, mesh,
                                precision=None, backend: str | None = None,
                                donate: bool = False,
                                lookahead: bool = False,
                                segs: tuple = (8, 8)):
    """Factor block-cyclic shards of an SPD matrix; returns factored shards
    (lower triangle = L, upper triangle unspecified). `donate=True`
    aliases the input into the output — without it the superstep loop
    cannot update in place (an immutable input forces a full-buffer copy
    per step, measured ~6 ms/step at N=16384 on a v5e). `segs` = (row,
    col) trailing-update segment counts (see `lu.distributed`)."""
    from conflux_tpu.geometry import check_shards

    shards = jnp.asarray(shards)
    check_shards(shards, geom)
    fn = build_program(geom, mesh, precision=precision, backend=backend,
                       donate=donate, lookahead=lookahead, segs=segs)
    return fn(shards)


def cholesky_distributed_host(A: np.ndarray, grid: Grid3, v: int, mesh=None,
                              precision=None, backend: str | None = None,
                              segs: tuple = (8, 8)):
    """Scatter an SPD matrix, factor on the mesh, gather L back.

    Role of the reference's initialize/parallelCholesky/finalize sequence
    (`Cholesky.h:19-23`). Returns (L (N, N) lower-triangular, geom).
    """
    if A.shape[0] != A.shape[1]:
        raise ValueError(f"matrix must be square, got {A.shape}")
    geom = CholeskyGeometry.create(A.shape[0], v, grid)
    if mesh is None:
        mesh = make_mesh(grid)
    if geom.N != A.shape[0]:
        Ap = np.eye(geom.N, dtype=A.dtype)
        Ap[: A.shape[0], : A.shape[0]] = A
        A = Ap
    shards = geom.scatter(A)
    out = cholesky_factor_distributed(
        jnp.asarray(shards), geom, mesh, precision=precision, backend=backend,
        segs=segs,
    )
    L = np.tril(geom.gather(np.asarray(out)))
    return L, geom
