"""Distributed tall-skinny QR over the mesh x axis (TSQR / CholeskyQR2).

The communication-optimal QR member of the family: rows are block-
distributed over AXIS_X (no pivoting, so no cyclic interleave is needed),
and only (n, n) R factors ever cross the interconnect — the same
"reduce small blocks, keep the tall data local" pattern as the
reference's tournament panel reduction (`conflux_opt.hpp:220-336`),
with QR as the combiner instead of pivoted LU.

Two elections are offered:

 - `tsqr_distributed`: local chunked QR tree -> `all_gather` of the
   (n, n) local Rs over 'x' -> replicated tree reduction (every device
   computes the same global R, so no broadcast is needed — the same
   replicated-election trick the LU loop uses); Q by TRSM + a second
   pass. Robust at any conditioning.
 - `cholesky_qr2_distributed`: G = psum(A_loc^T A_loc) over 'x',
   R = chol(G)^T, Q = A R^{-1}, twice. One (n, n) psum per pass and
   pure GEMM/TRSM otherwise — the fastest MXU form, valid while
   cond(A)^2 stays below 1/eps of the compute dtype (the classical
   CholeskyQR2 regime); the Gram matrix is accumulated in f32-or-wider
   regardless of storage dtype.

Both return (Q_shards, R) with R replicated and diag(R) >= 0; results
are bitwise-identical across Px by construction of the replicated
reduction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import lax

from conflux_tpu.geometry import LUGeometry, ragged_segments
from conflux_tpu.ops import blas
from conflux_tpu.parallel.mesh import (
    AXIS_X,
    AXIS_Y,
    AXIS_Z,
    butterfly_allreduce,
    lookup_mesh,
    make_mesh,
    mesh_cache_key,
    pvary,
    shard_map,
    replicate,
)
from conflux_tpu.qr.single import _positive_diag, _tree_r


def _two_pass_tsqr(A, Px: int, chunk: int, passes: int, prec,
                   tree: str = "gather"):
    """Replicated TSQR election: local chunked tree, then a cross-x
    reduction of the (n, n) R factors; Q by TRSM, refined over `passes`
    sweeps; positive-diagonal normalized. Shared by the tall-skinny
    entry points and the block-cyclic loop's panel step.

    tree='gather' (default): one all_gather + replicated tree — a single
    optimized collective. tree='butterfly': the canonical TSQR hypercube
    — log2(Px) `ppermute` rounds each QR-reducing a pair-ordered
    (2n, n) stack, only n rows per round; pair ordering by the lower
    x-coordinate keeps every device's reduction bit-identical, so the
    result is replicated without a broadcast. Any Px: non-power-of-two
    axes fold their overflow ranks in/out with two extra ppermute
    rounds (see `butterfly_allreduce`)."""
    n = A.shape[1]
    R = None
    for _ in range(max(1, passes)):
        Ri = _tree_r(A, chunk)
        if tree == "butterfly":
            # ZERO-FILL CONTRACT (butterfly_allreduce): on odd-Px folds
            # the off-subcube lanes reduce ppermute's zero fill; the
            # reducer must stay total on an all-zero stack. _tree_r of
            # zeros is R=0 (geqrf of 0: finite, no NaN/Inf), and the
            # garbage is discarded by the coordinate selects — never
            # branch on the received values here (tests/test_ops.py
            # pins this with the real reducer at odd Px).
            (Ri,) = butterfly_allreduce(
                (Ri,), Px, AXIS_X,
                lambda top, bot: (_tree_r(
                    jnp.concatenate([top[0], bot[0]], axis=0), chunk),))
        elif Px > 1:
            allr = lax.all_gather(Ri, AXIS_X).reshape(Px * n, n)
            Ri = _tree_r(allr, chunk)
        A = blas.trsm_right_upper(Ri, A)
        R = Ri if R is None else jnp.matmul(Ri, R, precision=prec)
    return _positive_diag(A, R)


@functools.lru_cache(maxsize=32)
def _build(mesh_key, algo: str, shape, dtype_name: str, chunk: int,
           passes: int, tree: str = "gather"):
    mesh = lookup_mesh(mesh_key)
    Px = mesh.shape[AXIS_X]
    Ml, n = shape
    dtype = jnp.dtype(dtype_name)
    prec = blas.matmul_precision()

    def qr_tall_factor(blk):
        A = blk[0].astype(blas.compute_dtype(dtype))
        if algo == "tsqr":
            Q, R = _two_pass_tsqr(A, Px, chunk, passes, prec, tree=tree)
        else:  # cholesky: Gram psum + potrf election per pass
            R = None
            for _ in range(max(1, passes)):
                G = jax.lax.psum(
                    jnp.matmul(A.conj().T, A, precision=prec), AXIS_X)
                # G = L L^H (Hermitian), so the upper factor is L^H
                Ri = blas.potrf(G).conj().T
                A = blas.trsm_right_upper(Ri, A)
                R = Ri if R is None else jnp.matmul(Ri, R, precision=prec)
            Q, R = _positive_diag(A, R)
        # R is identical on every device already (replicated reduction /
        # psum'd Gram); re-establish replication for the out_spec, same
        # as the LU loop's perm output (complex-safe helper)
        R = replicate(R, tuple(mesh.axis_names))
        return Q.astype(dtype)[None], R.astype(dtype)

    fn = shard_map(qr_tall_factor, mesh=mesh,
                       in_specs=P(AXIS_X, None, None),
                       out_specs=(P(AXIS_X, None, None), P()))
    return jax.jit(fn)


def _factor(shards, mesh, algo: str, chunk: int | None, passes: int,
            tree: str = "gather"):
    shards = jnp.asarray(shards)
    if shards.ndim != 3:
        raise ValueError(
            f"expected (Px, Ml, n) row-block shards, got {shards.shape}")
    Px, Ml, n = shards.shape
    if Px != mesh.shape[AXIS_X]:
        raise ValueError(
            f"shards leading dim {Px} != mesh x extent {mesh.shape[AXIS_X]}")
    if Px * Ml < n:
        raise ValueError(f"need M = {Px * Ml} >= n = {n}")
    if chunk is None:
        chunk = blas.batched_call_rows(
            n, blas.compute_dtype(shards.dtype))
    if tree not in ("gather", "butterfly"):
        raise ValueError(f"unknown tree {tree!r} (gather|butterfly)")
    fn = _build(mesh_cache_key(mesh), algo, (Ml, n), shards.dtype.name,
                chunk, passes, tree)
    return fn(shards)


def tsqr_distributed(shards, mesh, chunk: int | None = None,
                     passes: int = 2, tree: str = "gather"):
    """(Q_shards, R) of an x-sharded (Px, Ml, n) tall matrix via the QR
    reduction tree. Every QR call is height-bounded by
    max(chunk, 2n, Px*n-tree levels); robust at any conditioning.
    tree='butterfly' selects the log-depth ppermute hypercube reduction
    (any Px — odd axes fold their overflow ranks with two extra rounds;
    see `_two_pass_tsqr`)."""
    return _factor(shards, mesh, "tsqr", chunk, passes, tree)


def cholesky_qr2_distributed(shards, mesh, passes: int = 2):
    """(Q_shards, R) via Gram-matrix CholeskyQR with `passes` refinement
    sweeps — one (n, n) psum per pass, everything else GEMM/TRSM.
    Requires cond(A)^2 * eps < 1 (use `tsqr_distributed` otherwise)."""
    return _factor(shards, mesh, "cholesky", None, passes)


def qr_distributed_host(A: np.ndarray, Px: int, mesh=None,
                        algo: str = "tsqr", chunk: int | None = None,
                        passes: int = 2):
    """Host convenience: block-row scatter, factor on the mesh, return
    (Q (M, n), R (n, n)). M is zero-padded up to a multiple of Px (zero
    rows leave R unchanged; the pad rows of Q are dropped)."""
    from conflux_tpu.geometry import Grid3

    M, n = A.shape
    if M < n:
        # the padded row count could pass _factor's check while the true
        # matrix is rank-deficient-by-shape -> silently non-orthogonal Q
        raise ValueError(f"need M >= n, got {A.shape}")
    Ml = -(-M // Px)
    if mesh is None:
        mesh = make_mesh(Grid3(Px, 1, 1))
    Ap = np.zeros((Px * Ml, n), A.dtype)
    Ap[:M] = A
    shards = Ap.reshape(Px, Ml, n)
    if algo == "tsqr":
        Qs, R = tsqr_distributed(shards, mesh, chunk=chunk, passes=passes)
    elif algo == "cholesky":
        Qs, R = cholesky_qr2_distributed(shards, mesh, passes=passes)
    else:
        raise ValueError(f"unknown algo {algo!r} (tsqr|cholesky)")
    Q = np.asarray(Qs).reshape(Px * Ml, n)[:M]
    return Q, np.asarray(R)


# --------------------------------------------------------------------------- #
# General (block-cyclic) distributed QR — the CAQR role
# --------------------------------------------------------------------------- #


@functools.lru_cache(maxsize=32)
def _build_full(geom, mesh_key, precision, backend: str, chunk: int,
                donate: bool = False, resumable: bool = False,
                csegs: int = 8, lookahead: bool = False):
    """Blocked distributed QR over the full (x, y, z) mesh.

    The general-matrix companion of `tsqr_distributed`, in the same design
    language as the LU/Cholesky superstep loops (one jitted shard_map +
    fori_loop, block-cyclic shards, z-partial-sum invariant):

     - column panel k: psum over ('y','z') -> replicated (Ml, v) panel;
     - BCGS2 re-projection: one more sweep of P -= Q_done (Q_done^T P)
       against the already-computed Q columns (the right-looking trailing
       update below is the first sweep), which is what keeps global
       orthogonality at eps without a second full factorization pass;
       the correction W rides into R's rows;
     - panel factorization: the two-pass TSQR election of
       `tsqr_distributed` (local chunked tree + all_gather of (v, v) Rs
       over 'x' + replicated tree reduction — no pivoting, so unlike LU
       no ids travel with the candidates);
     - trailing update: C = psum_{x,z}(Qp^T A) then A -= Qp C, with Qp
       split into nlayr = v/Pz z-slabs so the layers share the GEMM flops
       exactly like the LU/Cholesky 2.5D scheme; columns retire left to
       right (rows never retire — Q is full height), so only column
       segmentation is needed;
     - R is block-cyclic over its own (N, N) geometry — nothing
       replicated at scale: the panel's (v, v) R block lands on its
       (x, y) owner, C lands in R's row-tile k, and W is redistributed
       from column-owners to R's row-owners by a masked gather + psum
       over 'y' (the transpose-exchange idiom of the Cholesky loop's
       L10^T scatter).

    Q comes back thin (M, N) in A's layout; A = Q R with diag(R) >= 0.
    Rank-deficient panels leave their block's columns/rows unspecified
    (same contract as the LU loop's degenerate supersteps).

    lookahead=True selects the software-pipelined loop (the LU/Cholesky
    body_la pattern, P8): step k+1's panel reduce, BCGS2 re-projection
    and TSQR election are computed at the END of step k from (a) the
    pre-update matrix with ONLY the Q-column write applied — value-
    identical at every done column to the post-step matrix, but with no
    dataflow edge from the trailing segment GEMMs — and (b) a panel-slab
    GEMM mirroring the segment update's z-slab operands at width v.
    Value-equivalent to the plain loop; bitwise-verified on the CPU
    backend only — the slab GEMM is a width-v slice of work the plain
    loop computes at segment width, and TPU kernel accumulation order is
    shape-dependent (same caveat as the LU block update), so the TPU
    result may differ in final bits. XLA's scheduler can overlap the
    election collectives (panel psum, W/D psums, TSQR all_gather) with
    the trailing update on a mesh. Cost: one redundant (Ml, v)-slab GEMM
    per superstep.
    """
    mesh = lookup_mesh(mesh_key)
    v = geom.v
    Px, Py, Pz = geom.grid.Px, geom.grid.Py, geom.grid.Pz
    Ml, Nl = geom.Ml, geom.Nl
    if geom.M < geom.N:
        raise ValueError(f"distributed QR needs M >= N, got {geom.M}x{geom.N}")
    nlayr = -(-v // Pz)
    v_pad = Pz * nlayr
    n_steps = geom.Nt
    # R's own block-cyclic geometry over (N, N): local row count per
    # x-rank, padded so every x-rank holds whole tiles (r_geometry pads
    # the global row count the same way; pad tiles are never written)
    Nlr = (geom.Nt // Px + (1 if geom.Nt % Px else 0)) * v
    # trailing-update column segmentation (`csegs` segments): the QR
    # loop's analogue of the LU/Cholesky segs knob (rows are never
    # segmented here — every local row participates in every panel)
    col_segs = ragged_segments(geom.Ntl, v, csegs)

    def _vary(val):
        # mark a literal as varying over every mesh axis so lax.cond
        # branch output types match the mask-dependent compute branches
        return pvary(val, (AXIS_X, AXIS_Y, AXIS_Z))

    def qr_factor(blk, rblk=None, k0=0, k_end=n_steps):
        x = lax.axis_index(AXIS_X)
        y = lax.axis_index(AXIS_Y)
        z = lax.axis_index(AXIS_Z)
        dtype = blk.dtype
        cdtype = blas.compute_dtype(dtype)
        prec = precision

        # z-partial invariant (same line as the LU loop): data enters on
        # z == 0; a resumed z-replicated state round-trips through it
        Aloc = jnp.where(z == 0, blk[0, 0], jnp.zeros((), dtype))
        if rblk is None:
            # R starts as a literal zero block: mark it varying over the
            # mesh axes so the fori_loop carry type matches the body
            Rloc = _vary(jnp.zeros((Nlr, Nl), dtype))
        else:
            # R is only ever written on layer 0; restore that invariant
            Rloc = jnp.where(z == 0, rblk[0, 0], jnp.zeros((), dtype))

        lc = jnp.arange(Nl, dtype=jnp.int32)
        ctile = (lc // v) * Py + y  # global col-tile id per local col
        # R-local rows -> global R row ids (for the W transpose-exchange)
        lrr = jnp.arange(Nlr, dtype=jnp.int32)
        grow_r = ((lrr // v) * Px + x) * v + (lrr % v)
        # source local column (on the y owner) holding R row g
        wsrc_y = (grow_r // v) % Py
        wsrc_col = ((grow_r // v) // Py) * v + grow_r % v

        def tsqr_panel(P_):
            """Two-pass replicated TSQR election on the (Ml, v) panel."""
            return _two_pass_tsqr(P_, Px, chunk, 2, prec)

        def panel_reduce(Asrc, k):
            """Column panel k: one psum over ('y','z')."""
            i0 = jnp.zeros((), jnp.int32)
            lj = jnp.asarray((k // Py) * v, jnp.int32)  # k may be a py int
            panel_loc = lax.dynamic_slice(Asrc, (i0, lj), (Ml, v))
            return lax.psum(
                jnp.where(y == k % Py, panel_loc, jnp.zeros((), dtype)),
                (AXIS_Y, AXIS_Z)).astype(cdtype)

        def reproject(Asrc, P_, k):
            """BCGS2 re-projection of panel P_ against the finished Q
            columns of Asrc (tiles < k). Returns (W, P_reprojected);
            `Asrc` is the loop matrix in body, or the Q-write-only
            matrix A_q in the lookahead carry computation."""
            z0 = z == 0
            col_done = ctile < k

            def seg_c_done(clo):
                return (clo // v) * Py + y < k

            # W = Q_done^T P, (Nl, v), rows indexed by my local cols;
            # Q columns live on layer 0 only
            wparts = []
            for clo, chi in col_segs:
                dm = col_done[clo:chi]
                wparts.append(lax.cond(
                    seg_c_done(clo),
                    lambda a, m: jnp.matmul(
                        jnp.where(m[:, None],
                                  a.conj().T.astype(cdtype), 0.0),
                        P_, precision=prec),
                    # pvary matches the compute branch's varying
                    # axes (a: x/z, m: y) for the cond output type
                    lambda a, m: _vary(jnp.zeros((a.shape[1], v),
                                                 cdtype)),
                    jnp.where(z0, lax.slice(
                        Asrc, (0, clo), (Ml, chi)), jnp.zeros((), dtype)),
                    dm,
                ))
            W = lax.psum(
                jnp.concatenate(wparts, axis=0) if len(wparts) > 1
                else wparts[0],
                (AXIS_X, AXIS_Z))  # (Nl, v) replicated over x, z
            # P -= Q_done W: per-segment local partials (NO
            # collective inside the cond — divergent predicates across
            # y would deadlock a psum), one unconditional psum over 'y'
            # (columns are y-partitioned; rows stay local to x) + 'z'
            # (Q lives on layer 0) at the end
            Dacc = _vary(jnp.zeros((Ml, v), cdtype))
            for clo, chi in col_segs:
                dm = col_done[clo:chi]

                def proj(acc, clo=clo, chi=chi, dm=dm):
                    Qseg = jnp.where(
                        dm[:, None].T & z0,
                        lax.slice(Asrc, (0, clo), (Ml, chi)).astype(cdtype),
                        0.0)
                    return acc + jnp.matmul(Qseg, W[clo:chi],
                                            precision=prec)

                Dacc = lax.cond(seg_c_done(clo), proj,
                                lambda acc: acc, Dacc)
            return W, P_ - lax.psum(Dacc, (AXIS_Y, AXIS_Z))

        def elect(Asrc, k):
            """panel reduce + BCGS2 re-projection + TSQR election: the
            whole per-step panel pipeline (everything the lookahead
            carries ahead)."""
            with jax.named_scope("qr_panel_reduce"):
                P_ = panel_reduce(Asrc, k)
            with jax.named_scope("qr_reproject"):
                W, P_ = reproject(Asrc, P_, k)
            with jax.named_scope("qr_panel_tsqr"):
                Qp, Rp = tsqr_panel(P_)
            return W, Qp, Rp

        def body_core(k, Aloc, Rloc, W, Qp, Rp):
            i0 = jnp.zeros((), jnp.int32)
            z0 = z == 0
            yo = k % Py
            xo = k % Px
            lj = ((k // Py) * v).astype(jnp.int32)
            lir = ((k // Px) * v).astype(jnp.int32)  # R-local row slab
            col_live = ctile > k

            def seg_c_live(chi):
                return ((chi - 1) // v) * Py + y > k

            # ---- trailing projection C = Qp^T A (first GS sweep) ------- #
            with jax.named_scope("qr_trailing_c"):
                cparts = []
                for clo, chi in col_segs:
                    lm = col_live[clo:chi]
                    cparts.append(lax.cond(
                        seg_c_live(chi),
                        lambda a, m: jnp.matmul(
                            Qp.conj().T,
                            jnp.where(m[None, :], a.astype(cdtype), 0.0),
                            precision=prec),
                        lambda a, m: _vary(jnp.zeros((v, a.shape[1]),
                                                           cdtype)),
                        lax.slice(Aloc, (0, clo), (Ml, chi)), lm,
                    ))
                C = lax.psum(
                    jnp.concatenate(cparts, axis=1) if len(cparts) > 1
                    else cparts[0],
                    (AXIS_X, AXIS_Z))  # (v, Nl)

            # ---- trailing update A -= Qp C on this layer's z-slab ------ #
            Qpp = jnp.pad(Qp.astype(dtype), ((0, 0), (0, v_pad - v)))
            Cp = jnp.pad(C.astype(dtype), ((0, v_pad - v), (0, 0)))
            zoff = (z * nlayr).astype(jnp.int32)
            Qps = lax.dynamic_slice(Qpp, (i0, zoff), (Ml, nlayr))
            Cs = lax.dynamic_slice(Cp, (zoff, i0), (nlayr, Nl))
            with jax.named_scope("qr_trailing_update"):
                Anew = Aloc
                for clo, chi in col_segs:
                    lm = col_live[clo:chi]

                    def seg_update(A, clo=clo, chi=chi, lm=lm):
                        a_seg = lax.slice(A, (0, clo), (Ml, chi))
                        upd = blas.gemm(Qps, Cs[:, clo:chi],
                                        precision=prec, backend=backend)
                        new = a_seg - jnp.where(lm[None, :], upd,
                                                jnp.zeros((), dtype))
                        return lax.dynamic_update_slice(A, new, (0, clo))

                    Anew = lax.cond(seg_c_live(chi), seg_update,
                                    lambda A: A, Anew)

            # ---- Q panel write (z0, column owner) ---------------------- #
            with jax.named_scope("qr_writes"):
                qcol = jnp.where(z0, Qp.astype(dtype), jnp.zeros((), dtype))
                Anew = jnp.where(
                    y == yo, lax.dynamic_update_slice(Anew, qcol, (i0, lj)),
                    Anew)

                # R writes: C into row-tile k (live cols), Rp into the
                # diagonal block, W into column-panel k (done rows)
                rrow_cur = lax.dynamic_slice(Rloc, (lir, i0), (v, Nl))
                rrow_new = jnp.where(
                    col_live[None, :] & z0, C.astype(dtype), rrow_cur)
                rrow_new = jnp.where(
                    (y == yo) & z0,
                    lax.dynamic_update_slice(rrow_new, Rp.astype(dtype),
                                             (i0, lj)),
                    rrow_new)
                Rnew = jnp.where(
                    x == xo, lax.dynamic_update_slice(Rloc, rrow_new,
                                                      (lir, i0)),
                    Rloc)
                # W transpose-exchange: my R rows' corrections live on the
                # y-rank owning that global column; gather + psum over 'y'
                Wr = lax.psum(
                    jnp.where((wsrc_y == y)[:, None]
                              & (grow_r < k * v)[:, None],
                              jnp.take(W, jnp.minimum(wsrc_col, Nl - 1),
                                       axis=0, mode="clip"),
                              jnp.zeros((), cdtype)),
                    AXIS_Y)  # (Nlr, v) complete on every y
                wcol = lax.dynamic_slice(Rnew, (i0, lj), (Nlr, v))
                wcol = wcol + jnp.where(
                    (y == yo) & z0, Wr.astype(dtype), jnp.zeros((), dtype))
                Rnew = lax.dynamic_update_slice(Rnew, wcol, (i0, lj))
            art = dict(Qps=Qps, Cs=Cs, qcol=qcol, lj=lj, yo=yo)
            return Anew, Rnew, art

        def body(k, carry):
            Aloc, Rloc = carry
            W, Qp, Rp = elect(Aloc, k)
            Anew, Rnew, _ = body_core(k, Aloc, Rloc, W, Qp, Rp)
            return Anew, Rnew

        def body_la(k, carry):
            # software-pipelined body: this step's election arrives in
            # the carry; the next step's election is computed from
            # sources with no dataflow edge to the trailing GEMMs so
            # XLA can overlap its collectives with them on a mesh.
            Aloc, Rloc, W, Qp, Rp = carry
            Anew, Rnew, art = body_core(k, Aloc, Rloc, W, Qp, Rp)
            kn = k + 1
            i0 = jnp.zeros((), jnp.int32)

            def compute_next(_):
                # A_q: pre-update matrix + ONLY the Q-column write —
                # value-identical to Anew at every done column (the
                # trailing update touches live columns only; tile k's
                # column is neither: it is overwritten by qcol), but
                # dataflow-independent of the segment GEMMs
                A_q = jnp.where(
                    y == art["yo"],
                    lax.dynamic_update_slice(Aloc, art["qcol"],
                                             (i0, art["lj"])),
                    Aloc)
                # panel slab of tile kn, updated by a GEMM over the same
                # z-slab operands (Qps/Cs) as the segment update — value-
                # equivalent; bitwise only where kernel accumulation is
                # shape-independent (CPU yes; TPU unverified, the slab is
                # width v vs the segment's chi-clo)
                with jax.named_scope("qr_panel_reduce"):
                    lj1 = ((kn // Py) * v).astype(jnp.int32)
                    slab = lax.dynamic_slice(Aloc, (i0, lj1), (Ml, v))
                    upd = blas.gemm(
                        art["Qps"],
                        lax.dynamic_slice(art["Cs"], (i0, lj1),
                                          (nlayr, v)),
                        precision=prec, backend=backend)
                    slab = slab - upd  # tile kn is fully live at step k
                    P_n = lax.psum(
                        jnp.where(y == kn % Py, slab,
                                  jnp.zeros((), dtype)),
                        (AXIS_Y, AXIS_Z)).astype(cdtype)
                with jax.named_scope("qr_reproject"):
                    W_n, P_n = reproject(A_q, P_n, kn)
                with jax.named_scope("qr_panel_tsqr"):
                    Qp_n, Rp_n = tsqr_panel(P_n)
                return W_n, Qp_n, Rp_n

            # the last iteration has no next panel: skip the dangling
            # election (a whole superstep's collectives + TSQR)
            W_n, Qp_n, Rp_n = lax.cond(
                kn < k_end, compute_next, lambda _: (W, Qp, Rp), 0)
            return Anew, Rnew, W_n, Qp_n, Rp_n

        if lookahead:
            W0, Qp0, Rp0 = elect(Aloc, k0)
            Aloc, Rloc, _, _, _ = lax.fori_loop(
                k0, k_end, body_la, (Aloc, Rloc, W0, Qp0, Rp0))
        else:
            Aloc, Rloc = lax.fori_loop(k0, k_end, body, (Aloc, Rloc))
        Qout = lax.psum(Aloc, AXIS_Z)
        Rout = lax.psum(Rloc, AXIS_Z)
        return Qout[None, None], Rout[None, None]

    shard_spec = P(AXIS_X, AXIS_Y, None, None)
    if resumable:
        in_specs = (shard_spec, shard_spec, P(), P())
    else:
        in_specs = shard_spec
    fn = shard_map(qr_factor, mesh=mesh, in_specs=in_specs,
                       out_specs=(shard_spec, shard_spec))
    # resumable mode donates the O(N^2) R state too — unlike LU's O(M)
    # orig map, holding input and output R simultaneously is matrix-sized
    donate_args = ((0, 1) if resumable else (0,)) if donate else ()
    return jax.jit(fn, donate_argnums=donate_args)


def build_program(geom, mesh, precision=None, backend: str | None = None,
                  chunk: int | None = None, donate: bool = False,
                  resumable: bool = False, csegs: int = 8,
                  lookahead: bool = False, dtype=None):
    """The jitted block-cyclic QR program itself (cached per config) —
    the single point resolving trace-time defaults, mirroring
    `lu.distributed.build_program`. Direct use is for callers needing
    the compile artifacts (the miniapp's --profile phase table); such
    callers should pass the input `dtype` they will run with so the
    chunk default resolves with its compute dtype (f64 halves the safe
    TSQR call height) and the built program matches the one the entry
    points cache and time."""
    precision = blas.matmul_precision() if precision is None else precision
    backend = blas.get_backend() if backend is None else backend
    if chunk is None:
        cdtype = blas.compute_dtype(jnp.dtype(dtype)) if dtype is not None \
            else jnp.float32
        chunk = blas.batched_call_rows(geom.v, cdtype)
    if donate and next(iter(mesh.devices.flat)).platform == "cpu":
        donate = False
    if csegs < 1:
        raise ValueError(
            f"csegs must be a positive segment count, got {csegs} "
            "(non-positive counts would silently skip trailing updates)")
    return _build_full(geom, mesh_cache_key(mesh), precision, backend,
                       chunk, donate, resumable, csegs, lookahead)


def qr_factor_distributed(shards, geom, mesh, precision=None,
                          backend: str | None = None,
                          chunk: int | None = None, donate: bool = False,
                          csegs: int = 8, lookahead: bool = False):
    """Blocked QR of block-cyclic (Px, Py, Ml, Nl) shards on the mesh.

    Returns (Q_shards, R_shards): Q thin (M, N) in A's layout, R upper-
    triangular (N, N) block-cyclic over its own geometry (gather it with
    `r_geometry(geom)`). See `_build_full` for the algorithm;
    `lookahead=True` software-pipelines the loop (P8 — next panel's
    election overlaps the trailing update on a mesh; value-equivalent
    results, bitwise-verified on the CPU backend only — see
    `_build_full`'s shape-dependent-accumulation caveat)."""
    from conflux_tpu.geometry import check_shards

    shards = jnp.asarray(shards)
    check_shards(shards, geom)
    # default chunk resolves inside build_program from the compute dtype
    fn = build_program(geom, mesh, precision=precision, backend=backend,
                       chunk=chunk, donate=donate, csegs=csegs,
                       lookahead=lookahead, dtype=shards.dtype)
    return fn(shards)


def qr_factor_steps(shards, geom, mesh, k0: int, k1: int, R=None,
                    precision=None, backend: str | None = None,
                    chunk: int | None = None, donate: bool = False):
    """Factor column panels [k0, k1) only — checkpoint/restart for the QR
    loop (the `lu_factor_steps`/`cholesky_factor_steps` counterpart).

    State = (shards, R): after k panels, columns with tile id < k hold
    finished Q columns, the rest the projected trailing matrix, and R
    holds its first k tile-rows — all plain saveable arrays. Pass R=None
    only when k0 == 0; feed each call's outputs to the next. The step
    bounds are traced scalars: one compiled program serves every segment.
    Same 2.5D caveat as the LU form: the checkpoint consolidates
    z-partial sums, so Pz > 1 resumes are numerically equivalent rather
    than bit-identical; Pz == 1 round-trips exactly."""
    if not (0 <= k0 < k1 <= geom.Nt):
        raise ValueError(f"step range [{k0}, {k1}) outside [0, {geom.Nt})")
    # the default chunk resolves inside build_program with the same
    # compute dtype as qr_factor_distributed's: a resumed run must chunk
    # its panel TSQR like the run it resumes
    if R is None:
        if k0 != 0:
            raise ValueError("resuming at k0 > 0 requires the R state "
                             "returned by the previous qr_factor_steps call")
        # r_geometry's local row count IS the kernel's padded Nlr — one
        # source of truth for the padding rule
        R = jnp.zeros(
            (geom.grid.Px, geom.grid.Py, r_geometry(geom).Ml, geom.Nl),
            jnp.asarray(shards).dtype)
    fn = build_program(geom, mesh, precision=precision, backend=backend,
                       chunk=chunk, donate=donate, resumable=True,
                       dtype=jnp.asarray(shards).dtype)
    return fn(jnp.asarray(shards), jnp.asarray(R), jnp.int32(k0),
              jnp.int32(k1))


def r_geometry(geom):
    """The (N, N) block-cyclic geometry R comes back in."""
    return LUGeometry.create(geom.N, geom.N, geom.v, geom.grid)


def qr_blocked_distributed_host(A: np.ndarray, grid, v: int, mesh=None,
                                precision=None, backend: str | None = None,
                                chunk: int | None = None):
    """Host convenience: scatter, factor, gather. Returns (Q (M, N),
    R (N, N), geom) for the ORIGINAL shape.

    Non-grid-multiple sizes are handled by block-diagonal identity
    extension: QR(blockdiag(A, I)) == blockdiag(Q, I) blockdiag(R, I)
    exactly, so the padded problem's leading (M, N) / (N, N) blocks ARE
    the answer (zero-column padding would instead feed singular panels
    into the TRSM recovery and NaN the trailing matrix). The identity
    lives in padded rows x padded columns, so rows are padded at least
    as far as columns."""
    M, N = A.shape
    if M < N:
        raise ValueError(f"distributed QR needs M >= N, got {A.shape}")
    geom = LUGeometry.create(M, N, v, grid)
    col_pad = geom.N - N
    if geom.M - M < col_pad:
        # need one identity row per pad column: grow the row padding
        geom = LUGeometry.create(M + col_pad, N, v, grid)
    if (geom.M, geom.N) != A.shape:
        Ap = np.zeros((geom.M, geom.N), A.dtype)
        Ap[:M, :N] = A
        Ap[np.arange(M, M + col_pad), np.arange(N, geom.N)] = 1
        A = Ap
    if mesh is None:
        mesh = make_mesh(geom.grid)
    Qs, Rs = qr_factor_distributed(
        jnp.asarray(geom.scatter(A)), geom, mesh, precision=precision,
        backend=backend, chunk=chunk)
    Q = geom.gather(np.asarray(Qs))[:M, :N]
    # r_geometry pads R's rows to a tile multiple of Px; the pad tiles
    # are never written, so slicing restores the (N, N) contract
    R = r_geometry(geom).gather(np.asarray(Rs))[:N, :N]
    return Q, np.triu(R), geom
