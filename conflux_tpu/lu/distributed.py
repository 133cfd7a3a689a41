"""Distributed LU with tournament pivoting over the (x, y, z) mesh.

TPU-native re-design of the reference's `LU_rep` superstep loop
(`conflux_opt.hpp:343-1827`). The reference is host-orchestrated SPMD: each
MPI rank owns block-cyclic tiles, physically compacts pivot rows upward
(`push_pivots_up`, `conflux_opt.hpp:176-218`), and moves panels with
Reduce/Iscatterv/Sendrecv. Here the whole factorization is ONE jitted
`shard_map` program with a `lax.fori_loop` over supersteps and static
shapes throughout:

 - the matrix lives in *currently-pivoted global row order* (LAPACK getrf
   layout): after step k, global positions < k*v hold frozen factor rows
   and positions >= k*v are active. Each step performs LAPACK-style row
   swaps — elected pivot rows move into the step's diagonal block, the
   displaced occupants move to the vacated slots — expressed as two
   (v, Nl) psums plus value-level scatters. This is the TPU answer to the
   reference's `push_pivots_up` row compaction (P6): because eliminated
   rows now occupy a tile-aligned *prefix* of every device's local rows,
   row liveness (like column liveness) is monotone in the local tile
   index, and the hot ops shrink with k instead of paying full-height
   masked work every superstep;
 - rotating owner roles (P5) -> `axis_index` comparisons inside the loop;
 - the z-layer 2.5D replication (P3) -> each device holds a *partial sum*
   shard; sum over the z axis is the true matrix. Panel reads are `psum`s
   over ('y','z'); factor writes land on layer z==0 only;
 - tournament pivoting (P4) -> chunked CALU nomination per x-rank,
   `all_gather` over 'x' + the same chunked reduction tree elects winners
   (every LU call height-bounded by max(chunk, 2v), the role of the
   reference's log-depth butterfly), computed identically on every device
   so the result needs no broadcast;
 - the trailing update (step 6) runs on each device's nlayr = v/Pz slab of
   the panel, so z layers share the O(N^2 v) GEMM flops exactly like the
   reference's 2.5D scheme. The update is cut into row x column segments
   (ragged, tile-aligned); segments with no live rows or columns are
   skipped via `lax.cond`, keeping total GEMM/TRSM work near the true
   2/3 N^3 / P.

Per superstep: 5 collectives (panel psum over (y,z), nominee all_gather
over x, pivot-row psum over (x,z), displaced-row psum over (x,z), small
bookkeeping psums), two chunked tournament factorizations, two TRSMs over
live segments, and the segmented trailing GEMMs.

Factors come back in *pivoted row order* together with `perm` (M,), the
original row index at each global position: A[perm] == L @ U.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from conflux_tpu.geometry import Grid3, LUGeometry, ragged_segments
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
)

_GRI_SENTINEL = np.iinfo(np.int32).max

# The default nomination chunk is blas.single_call_rows(v): unlike the
# batched ceiling (blas.batched_call_rows — batch x height shares one
# scoped VMEM budget), the chunk_live nomination runs each chunk as a
# separate cond'd call, so the full single-call height is VMEM-safe and
# measured faster (10.5 vs 9.8 TFLOP/s at N=32768/v=1024 on a v5e, where
# the derived values pin to 8192/4096).


@functools.lru_cache(maxsize=32)
def _build(geom: LUGeometry, mesh_key, precision, backend: str,
           panel_chunk: int, donate: bool = False, resumable: bool = False,
           lookahead: bool = False, election: str = "gather",
           segs: tuple = (16, 16), tree: str = "pairwise",
           update: str = "segments"):
    """resumable=True builds the checkpoint/restart form: factor supersteps
    [k0, k1) given as TRACED scalars — one compile serves every segment of
    a checkpointed run — with the row-origin state as an explicit
    input/output (`lu_factor_steps`). lookahead=True builds the
    software-pipelined loop (panel + election carried one step ahead; see
    body_la)."""
    mesh = lookup_mesh(mesh_key)
    v = geom.v
    Px, Py, Pz = geom.grid.Px, geom.grid.Py, geom.grid.Pz
    Ml, Nl = geom.Ml, geom.Nl
    nlayr = geom.nlayr
    n_steps = geom.n_steps
    Mcap = geom.M  # positions are < Mcap; sentinel values exceed it
    v_pad = Pz * nlayr  # inner dim padded so every z layer gets a full slab
    # trailing-update segmentation: row and column liveness are both
    # monotone in local tile index (rows because of the LAPACK-order swaps,
    # columns because tile lt has global id lt*P + coord), so the live
    # region is a contiguous (row-suffix x col-suffix) block; ragged
    # segments + lax.cond skip dead blocks, bounding flop overshoot at one
    # segment of width/height per superstep. `segs` = (row, col) segment
    # counts: finer cuts overshoot (avg half a segment of dead rows/cols
    # ride every GEMM) at the cost of more cond/DUS ops per step.
    row_segs = ragged_segments(geom.Mtl, v, segs[0])
    col_segs = ragged_segments(geom.Ntl, v, segs[1])

    def device_fn(blk, orig_blk=None, k0=0, k_end=n_steps):
        x = lax.axis_index(AXIS_X)
        y = lax.axis_index(AXIS_Y)
        z = lax.axis_index(AXIS_Z)
        dtype = blk.dtype
        cdtype = blas.compute_dtype(dtype)

        # z-partial invariant: sum over z == true matrix; data enters on
        # z=0. A resumed state round-trips through the same line: outputs
        # are z-replicated, so taking layer 0 restores the invariant.
        Aloc = jnp.where(z == 0, blk[0, 0], jnp.zeros((), dtype))

        lr = jnp.arange(Ml, dtype=jnp.int32)
        rtile = (lr // v) * Px + x  # global row-tile at each local row
        gp = rtile * v + (lr % v)  # global POSITION of each local row
        lc = jnp.arange(Nl, dtype=jnp.int32)
        ctile = (lc // v) * Py + y  # global col-tile id per local col

        # original row id currently at each local position (rows start in
        # original order, so position == original id at step 0); resumed
        # runs carry it in as explicit state
        orig0 = gp if orig_blk is None else orig_blk[0]

        def loc_of(pos):
            """Local row index of a (v,) vector of global positions; Ml
            (out of range -> scatter/gather drop) when not owned in x or
            when the entry is a sentinel."""
            tile = pos // v
            owned = (tile % Px == x) & (pos < Mcap)
            return jnp.where(owned, (tile // Px) * v + pos % v, Ml)

        def panel_reduce(Aloc, k):
            """Panel column k: z-reduce + y-broadcast in one psum (ref
            step 0)."""
            j_owner = k % Py
            lj = jnp.asarray((k // Py) * v, jnp.int32)  # k may be a py int
            panel_loc = lax.dynamic_slice(
                Aloc, (jnp.zeros((), jnp.int32), lj), (Ml, v))
            return lax.psum(
                jnp.where(y == j_owner, panel_loc, jnp.zeros((), dtype)),
                (AXIS_Y, AXIS_Z),
            ).astype(cdtype)

        def elect(panel, k):
            """Tournament pivoting over x (ref step 1): candidates are
            identified by their global position; the nomination and the
            cross-x election both run the chunked CALU tournament, so every
            LU call is height-bounded by max(panel_chunk, 2v) — the
            reference butterfly's role (`conflux_opt.hpp:220-336`)."""
            live = gp >= k * v
            cand = jnp.where(live[:, None], panel, jnp.zeros((), cdtype))
            pos_m = jnp.where(live, gp, _GRI_SENTINEL)
            # dead rows form a tile-aligned prefix (LAPACK-order layout),
            # so whole chunks die as k advances: a chunk is live iff its
            # last row's position is still active (the position of a local
            # row is a closed form, so this is a scalar compare per chunk,
            # not a gather)
            c_h, nch = blas.chunk_layout(Ml, v, panel_chunk)

            def pos_of_local(r):  # python-int local row -> global position
                return ((r // v) * Px + x) * v + (r % v)

            chunk_live = jnp.stack([
                pos_of_local(min((i + 1) * c_h, Ml) - 1) >= k * v
                for i in range(nch)
            ])
            if Px == 1:
                # single x-rank: the local nomination IS the election
                lu00, top = blas.tournament_winners(
                    cand, chunk=panel_chunk, chunk_live=chunk_live,
                    tree=tree)
                wpos = jnp.take(pos_m, top, mode="fill",
                                fill_value=_GRI_SENTINEL)
                return lu00, wpos
            _, top = blas.tournament_winners(
                cand, chunk=panel_chunk, chunk_live=chunk_live, tree=tree)
            nom = jnp.take(cand, top, axis=0, mode="fill",
                           fill_value=0)
            nid = jnp.take(pos_m, top, mode="fill",
                           fill_value=_GRI_SENTINEL)
            if election == "butterfly":
                # the reference's hypercube exchange
                # (`conflux_opt.hpp:220-336`, partner at
                # `conflux_opt.cpp:59-72`): log2(Px) ppermute rounds,
                # each reducing a pair-ordered (2v, v) stack — only v
                # rows ever cross the interconnect per round, vs the
                # all_gather's Px*v. The ordering/replication invariant
                # lives in `butterfly_allreduce`, which also handles
                # non-power-of-two Px (overflow-rank fold/unfold — the
                # SPMD form of the reference's odd-grid compensating
                # sends, `conflux_opt.hpp:266-280`).
                # lu00 rides the tuple so the final round's packed
                # factor comes out replicated with the winners.
                # ZERO-FILL CONTRACT (butterfly_allreduce): on odd-Px
                # folds every rank runs this reducer, and off-subcube
                # lanes receive ppermute's zero fill — an all-zero
                # stack and ids=0. tournament_winners on zeros is
                # well-defined garbage (getrf of 0 = 0, finite, no
                # NaN/Inf), and the garbage lanes are discarded by the
                # coordinate selects inside butterfly_allreduce. Keep
                # it that way: never gather by the received ids or
                # branch on the values here — only select-by-winner on
                # the local stack (tests/test_ops.py pins this with
                # the real reducers at odd Px).
                def reduce_pair(top, bot):
                    stack = jnp.concatenate([top[0], bot[0]], axis=0)
                    ids = jnp.concatenate([top[1], bot[1]])
                    lu00_, wid = blas.tournament_winners(
                        stack, chunk=min(panel_chunk,
                                         blas.batched_call_rows(v, cdtype)))
                    return (jnp.take(stack, wid, axis=0, mode="fill",
                                     fill_value=0),
                            jnp.take(ids, wid, mode="fill",
                                     fill_value=_GRI_SENTINEL),
                            lu00_)

                nom, nid, lu00 = butterfly_allreduce(
                    (nom, nid, jnp.zeros((v, v), cdtype)), Px, AXIS_X,
                    reduce_pair)
                return lu00, nid
            blks = lax.all_gather(nom, AXIS_X)  # (Px, v, v)
            poss = lax.all_gather(nid, AXIS_X)  # (Px, v)
            flat = blks.reshape(Px * v, v)
            # the election tournament is batched (no liveness
            # structure), so its chunk stays within the batched
            # VMEM-safe bound
            lu00, wid = blas.tournament_winners(
                flat, chunk=min(panel_chunk,
                                blas.batched_call_rows(v, cdtype)),
                tree=tree)
            # winners' positions in pivot order — replicated on
            # every device, no broadcast needed
            wpos = jnp.take(poss.reshape(Px * v), wid, mode="fill",
                            fill_value=_GRI_SENTINEL)
            return lu00, wpos

        def body_core(k, Aloc, orig, panel, lu00, wpos):
            j_owner = k % Py
            lj = ((k // Py) * v).astype(jnp.int32)
            i_owner = k % Px
            li = ((k // Px) * v).astype(jnp.int32)
            i0 = jnp.zeros((), jnp.int32)
            z0 = z == 0
            U00 = jnp.triu(lu00)
            L00 = blas.unit_lower(lu00)

            # ---- LAPACK-style row swaps (ref push_pivots_up, step 2) ----- #
            # winners move into the step's diagonal block (positions
            # k*v..(k+1)*v); the non-winner occupants move to the slots
            # vacated by external winners (i-th displaced occupant -> i-th
            # vacated position, both ascending — a canonical matching).
            # The writes are a (Ml,)-indexed row scatter. XLA lowers it to
            # a serial per-row loop (~10 ms/step at v=1024) — a round-2
            # attempt to fold the writes into the step-6 segments as
            # gather+selects was REVERTED: on a real v5e it was ~30%
            # slower and silently produced garbage factors at N=32768
            # (residual 29 vs 2.9e-05; correct on CPU at every tested
            # size and on TPU at N<=16384, valid perm, bounded factor
            # magnitudes — an XLA TPU miscompile at 4 GiB operands is the
            # best available explanation; see docs/DESIGN.md §14).
            with jax.named_scope("step2_pivotrows"):
                slots = k * v + jnp.arange(v, dtype=jnp.int32)
                occ_is_winner = (wpos[None, :] == slots[:, None]).any(1)
                is_ext = wpos >= (k + 1) * v
                # ascending order of the external winners' positions by
                # comparison ranking — a (v, v) compare + tiny scatter; a
                # jnp.sort here costs ~13 ms/step on TPU (bitonic)
                both = is_ext[None, :] & is_ext[:, None]
                rank = jnp.sum(both & (wpos[None, :] < wpos[:, None]),
                               axis=1).astype(jnp.int32)
                ext_sorted = jnp.full((v,), _GRI_SENTINEL, jnp.int32).at[
                    jnp.where(is_ext, rank, v)
                ].set(wpos, mode="drop")
                disp_rank = jnp.cumsum((~occ_is_winner).astype(jnp.int32)) - 1
                dest_disp = jnp.where(~occ_is_winner, ext_sorted[disp_rank],
                                      _GRI_SENTINEL)

                # winners' full rows + ids, reduced over (x, z) (ref step 3)
                wloc = loc_of(wpos)
                Prows = lax.psum(
                    jnp.take(Aloc, wloc, axis=0, mode="fill", fill_value=0),
                    (AXIS_X, AXIS_Z))  # (v, Nl)
                worig = lax.psum(
                    jnp.take(orig, wloc, mode="fill", fill_value=0), AXIS_X)
                # displaced occupants' full rows + ids + panel rows
                own_d = x == i_owner
                Drows = lax.psum(
                    jnp.where(own_d,
                              lax.dynamic_slice(Aloc, (li, i0), (v, Nl)),
                              jnp.zeros((), dtype)),
                    (AXIS_X, AXIS_Z))  # (v, Nl)
                dorig = lax.psum(
                    jnp.where(own_d, lax.dynamic_slice(orig, (li,), (v,)), 0),
                    AXIS_X)
                diag_panel = lax.psum(
                    jnp.where(own_d,
                              lax.dynamic_slice(panel, (li, i0), (v, v)),
                              jnp.zeros((), cdtype)),
                    AXIS_X)  # (v, v)

                # swap writes: vacated positions get the displaced rows now
                # (they stay active and take the trailing update); diagonal
                # rows are fully rewritten after the GEMM. Swapped rows
                # carry their z-summed value on layer 0, zeros elsewhere.
                didx = loc_of(dest_disp)
                disp_vals = jnp.where(z0, Drows.astype(dtype),
                                      jnp.zeros((), dtype))
                # XLA's per-row scatter loop (~10 ms/step at v=1024,
                # N=32768 — the "other" phase-table bucket). A pipelined
                # Pallas row-DMA alternative existed rounds 3-4 behind
                # swap='dma' but was deleted unadopted per the
                # pre-decided criterion (docs/ROUND3.md #3: hardware A/B
                # or deletion — the chip never recovered to run it); git
                # history has the kernel.
                Aloc = Aloc.at[didx].set(disp_vals, mode="drop")
                orig = jnp.where(
                    own_d, lax.dynamic_update_slice(orig, worig, (li,)), orig)
                orig = orig.at[didx].set(dorig, mode="drop")
                # the panel after the swap, for the L10 solve. Only the
                # displaced rows matter: the diagonal rows (winners) are
                # masked out of the TRSM by row_live, so their panel values
                # are never written back here.
                panel_post = panel.at[didx].set(diag_panel, mode="drop")

            # ---- L10 for the live row suffix (ref step 4 TRSM) ----------- #
            row_live = rtile > k  # whole tiles: diag tile k is done now
            # segment liveness as SCALAR tile-index compares: liveness is
            # monotone in the local tile index (LAPACK-order rows,
            # block-cyclic columns), so "any row/col of the segment live"
            # == "its last row/col's tile is still trailing" — a bool
            # vector .any() here costs ~1 ms/step in reduce fusions
            def seg_r_live(rhi):
                return ((rhi - 1) // v) * Px + x > k

            def seg_c_live(chi):
                return ((chi - 1) // v) * Py + y > k

            with jax.named_scope("step4_dtrsm"):
                pieces = []
                for rlo, rhi in row_segs:
                    rm = row_live[rlo:rhi]
                    pieces.append(lax.cond(
                        seg_r_live(rhi),
                        lambda p, m: blas.trsm_right_upper(
                            U00, jnp.where(m[:, None], p,
                                           jnp.zeros((), cdtype))),
                        lambda p, m: jnp.zeros_like(p),
                        panel_post[rlo:rhi], rm,
                    ))
                L10 = (jnp.concatenate(pieces, axis=0)
                       if len(pieces) > 1 else pieces[0])  # (Ml, v)

            # ---- U01 on the live column suffix (ref step 5 TRSM) --------- #
            col_trail = ctile > k  # (Nl,)
            Prows_c = Prows.astype(cdtype)
            with jax.named_scope("step5_dtrsm"):
                pieces = []
                for clo, chi in col_segs:
                    pieces.append(lax.cond(
                        seg_c_live(chi),
                        lambda p: blas.trsm_left_lower_unit(L00, p),
                        # pvary matches the solve branch's varying axes
                        # (L00 varies over x) for the cond output type
                        lambda p: pvary(p, (AXIS_X,)),
                        Prows_c[:, clo:chi],
                    ))
                U01 = (jnp.concatenate(pieces, axis=1)
                       if len(pieces) > 1 else pieces[0])  # (v, Nl)

            # ---- trailing update on this layer's slab (ref step 6) ------- #
            # GEMM rides the storage dtype (bf16 fast path when selected);
            # the (row-suffix x col-suffix) live block is covered by
            # row x col segments, dead blocks skipped via lax.cond
            L10p = jnp.pad(L10.astype(dtype), ((0, 0), (0, v_pad - v)))
            U01p = jnp.pad(U01.astype(dtype), ((0, v_pad - v), (0, 0)))
            zoff = (z * nlayr).astype(jnp.int32)
            L10s = lax.dynamic_slice(L10p, (i0, zoff), (Ml, nlayr))
            U01s = lax.dynamic_slice(U01p, (zoff, i0), (nlayr, Nl))

            with jax.named_scope("step6_dgemm"):
                if update == "block":
                    # ONE live-suffix block per step instead of the
                    # row x col segment lattice: a lax.switch over the
                    # (row, col) segment-boundary pair containing the
                    # first live row/col selects a branch with STATIC
                    # slice offsets — one slice + one GEMM + one mask +
                    # one DUS, eliminating the per-segment cond/DUS/
                    # select overhead (~9 ms/step of the N=32768 phase
                    # table). Flop overshoot identical to the segment
                    # scheme at the same `segs` (up to one segment of
                    # dead rows/cols rides the GEMM, masked out of the
                    # subtract). Composition note: under lookahead the
                    # carried slab GEMM mirrors operands but not the
                    # wide GEMM's SHAPE, so block+lookahead is value-
                    # equivalent (same pivots, f32-noise factors), not
                    # bitwise like segments+lookahead.
                    def br(args, ri=0, cj=0):
                        A, L10s_, U01s_ = args
                        a = lax.slice(A, (ri, cj), (Ml, Nl))
                        upd = blas.gemm(L10s_[ri:], U01s_[:, cj:],
                                        precision=precision,
                                        backend=backend)
                        keep = (row_live[ri:, None]
                                & col_trail[None, cj:])
                        new = a - jnp.where(keep, upd,
                                            jnp.zeros((), dtype))
                        return lax.dynamic_update_slice(A, new, (ri, cj))

                    branches = [
                        functools.partial(br, ri=rlo, cj=clo)
                        for rlo, _ in row_segs for clo, _ in col_segs
                    ]
                    # first live local row: tiles with rtile <= k are
                    # dead (LAPACK-order prefix)
                    ndead_t = jnp.where(x <= k, (k - x) // Px + 1, 0)
                    first_live = ndead_t * v
                    # first trailing local col: tiles with ctile > k
                    lt0 = jnp.where(y > k, 0, (k - y) // Py + 1)
                    first_col = lt0 * v
                    # index of the segment CONTAINING the boundary =
                    # (# starts <= boundary) - 1; a fully-dead axis
                    # clamps to the last segment (its mask is all-False)
                    ri_idx = sum(
                        (jnp.asarray(rlo) <= first_live).astype(jnp.int32)
                        for rlo, _ in row_segs) - 1
                    cj_idx = sum(
                        (jnp.asarray(clo) <= first_col).astype(jnp.int32)
                        for clo, _ in col_segs) - 1
                    Anew = lax.switch(ri_idx * len(col_segs) + cj_idx,
                                      branches, (Aloc, L10s, U01s))
                else:
                    # in-place cond'd DUS per live segment: a slice->
                    # concat formulation materializes the full local
                    # matrix every step (~26 ms/step of pure copies at
                    # N=32768)
                    Anew = Aloc
                    for rlo, rhi in row_segs:
                        rm = row_live[rlo:rhi]
                        for clo, chi in col_segs:
                            cm = col_trail[clo:chi]

                            def seg_update(A, rlo=rlo, rhi=rhi, clo=clo,
                                           chi=chi, rm=rm, cm=cm):
                                a_seg = lax.slice(A, (rlo, clo), (rhi, chi))
                                upd = blas.gemm(
                                    L10s[rlo:rhi], U01s[:, clo:chi],
                                    precision=precision, backend=backend)
                                keep = rm[:, None] & cm[None, :]
                                new = a_seg - jnp.where(keep, upd,
                                                        jnp.zeros((), dtype))
                                return lax.dynamic_update_slice(A, new,
                                                                (rlo, clo))

                            Anew = lax.cond(
                                seg_r_live(rhi) & seg_c_live(chi),
                                seg_update, lambda A: A, Anew)

            # ---- factor writes (z==0 carries factors, z!=0 zeroed) ------- #
            # diagonal block rows: leading columns keep the winners' frozen
            # L prefix (they ride along in Prows), trailing columns take
            # U01; the panel tile itself is overwritten with packed lu00 by
            # the panel-column write below
            with jax.named_scope("step7_writes"):
                drow_vals = jnp.where(col_trail[None, :], U01.astype(dtype),
                                      Prows.astype(dtype))
                Anew = jnp.where(
                    own_d,
                    lax.dynamic_update_slice(
                        Anew, jnp.where(z0, drow_vals, jnp.zeros((), dtype)),
                        (li, i0)),
                    Anew)
                # panel column: packed LU00 on the diagonal rows, L10 on live
                # rows, untouched on frozen rows; zeroed on z != 0 layers
                pcol_cur = lax.dynamic_slice(Anew, (i0, lj), (Ml, v))
                pcol_new = jnp.where(row_live[:, None], L10.astype(dtype),
                                     pcol_cur)
                pcol_new = jnp.where(
                    own_d,
                    lax.dynamic_update_slice(pcol_new, lu00.astype(dtype),
                                             (li, i0)),
                    pcol_new)
                pcol_new = jnp.where(z0, pcol_new, jnp.zeros((), dtype))
                Anew = jnp.where(
                    y == j_owner,
                    lax.dynamic_update_slice(Anew, pcol_new, (i0, lj)),
                    Anew,
                )
            # A_sw = the post-swap, pre-update matrix: the lookahead body
            # recomputes next step's panel slab from it
            art = dict(A_sw=Aloc, L10s=L10s, U01s=U01s, U01=U01,
                       row_live=row_live, own_d=own_d, li=li, z0=z0)
            return Anew, orig, art

        def body(k, carry):
            Aloc, orig = carry
            with jax.named_scope("step0_reduce"):
                panel = panel_reduce(Aloc, k)
            with jax.named_scope("step1_pivoting"):
                lu00, wpos = elect(panel, k)
            Anew, orig, _ = body_core(k, Aloc, orig, panel, lu00, wpos)
            return Anew, orig

        def body_la(k, carry):
            # software-pipelined (lookahead) body: the panel and election
            # for step k arrive in the carry; step k+1's panel is computed
            # from a separately-updated column slab of the PRE-update
            # matrix, so its election collectives have no data dependence
            # on the trailing GEMMs and XLA's scheduler can overlap them on
            # a mesh (the reference's P8 MPI_Waitany overlap). Slab math
            # mirrors the segment updates operand-for-operand, so carried
            # panels are bitwise identical to recomputed ones.
            Aloc, orig, panel, lu00, wpos = carry
            Anew, orig, art = body_core(k, Aloc, orig, panel, lu00, wpos)
            kn = k + 1
            i0 = jnp.zeros((), jnp.int32)

            def compute_next(_):
                with jax.named_scope("step0_reduce"):
                    j1 = kn % Py
                    lj1 = ((kn // Py) * v).astype(jnp.int32)
                    slab = lax.dynamic_slice(art["A_sw"], (i0, lj1), (Ml, v))
                    upd = blas.gemm(art["L10s"],
                                    lax.dynamic_slice(art["U01s"],
                                                      (i0, lj1),
                                                      (nlayr, v)),
                                    precision=precision, backend=backend)
                    slab = slab - jnp.where(art["row_live"][:, None], upd,
                                            jnp.zeros((), dtype))
                    u01_slab = lax.dynamic_slice(art["U01"], (i0, lj1),
                                                 (v, v)).astype(dtype)
                    slab = jnp.where(
                        art["own_d"],
                        lax.dynamic_update_slice(
                            slab, jnp.where(art["z0"], u01_slab,
                                            jnp.zeros((), dtype)),
                            (art["li"], i0)),
                        slab)
                    panel_next = lax.psum(
                        jnp.where(y == j1, slab, jnp.zeros((), dtype)),
                        (AXIS_Y, AXIS_Z)).astype(cdtype)
                with jax.named_scope("step1_pivoting"):
                    lu00n, wposn = elect(panel_next, kn)
                return panel_next, lu00n, wposn

            # the last iteration has no next step: skip the dangling
            # election (a whole superstep's collectives + tournament)
            panel_next, lu00n, wposn = lax.cond(
                kn < k_end, compute_next, lambda _: (panel, lu00, wpos), 0)
            return Anew, orig, panel_next, lu00n, wposn

        if lookahead:
            with jax.named_scope("step0_reduce"):
                panel0 = panel_reduce(Aloc, k0)
            with jax.named_scope("step1_pivoting"):
                lu000, wpos0 = elect(panel0, k0)
            Aloc, orig, _, _, _ = lax.fori_loop(
                k0, k_end, body_la, (Aloc, orig0, panel0, lu000, wpos0))
        else:
            Aloc, orig = lax.fori_loop(k0, k_end, body, (Aloc, orig0))
        # all factors live on layer 0; psum makes the output z-replicated
        Aout = lax.psum(Aloc, AXIS_Z)
        # assemble the permutation: original row id at every global position
        perm = jnp.zeros((Mcap,), jnp.int32).at[gp].set(orig)
        perm = lax.psum(perm, AXIS_X)
        # identical on every device already; pmax re-establishes replication
        # for the out_spec
        perm = lax.pmax(perm, (AXIS_Y, AXIS_Z))
        if orig_blk is None:
            return Aout[None, None], perm
        # resumable form: the row-origin state rides along (replicated over
        # y/z by pmax — every y/z holds the same x-row's state)
        orig_out = lax.pmax(orig, (AXIS_Y, AXIS_Z))
        return Aout[None, None], orig_out[None], perm

    shard_spec = P(AXIS_X, AXIS_Y, None, None)
    if resumable:
        in_specs = (shard_spec, P(AXIS_X, None), P(), P())
        out_specs = (shard_spec, P(AXIS_X, None), P())
    else:
        in_specs, out_specs = shard_spec, (shard_spec, P())
    fn = shard_map(device_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())



def build_program(geom: LUGeometry, mesh, precision=None,
                  backend: str | None = None, panel_chunk: int | None = None,
                  donate: bool = False, resumable: bool = False,
                  lookahead: bool = False, election: str = "gather",
                  segs: tuple = (16, 16), tree: str = "pairwise",
                  update: str = "segments", dtype=None):
    """The jitted distributed-LU program itself (cached per config).

    The single point resolving the trace-time defaults (precision/backend/
    panel_chunk, CPU donate guard); `lu_factor_distributed` goes through
    here too. Direct use is for callers that need the compile artifacts —
    e.g. the miniapp's `--profile`, which joins an XPlane trace with the
    optimized HLO's named-scope metadata (`profiler.phase_table`) to print
    the per-phase device-time table. Such callers should pass the input
    `dtype` they will run with: the panel_chunk default and the
    tree='flat' VMEM guard resolve chunk ceilings with its compute dtype
    (f64 halves the safe call heights vs the f32 default), so a
    dtype-blind build can cache a different program than the one the
    entry points time — or pass a flat-stack height the chip cannot
    compile.
    """
    precision = blas.matmul_precision() if precision is None else precision
    backend = blas.get_backend() if backend is None else backend
    cdtype = blas.compute_dtype(jnp.dtype(dtype)) if dtype is not None \
        else jnp.float32
    if panel_chunk is None:
        panel_chunk = blas.single_call_rows(geom.v, cdtype)
    if donate and next(iter(mesh.devices.flat)).platform == "cpu":
        donate = False  # CPU PJRT has no buffer donation (warns per call)
    if election not in ("gather", "butterfly"):
        raise ValueError(f"unknown election {election!r} (gather|butterfly)")
    if len(segs) != 2 or segs[0] < 1 or segs[1] < 1:
        raise ValueError(
            f"segs must be two positive segment counts, got {segs!r} "
            "(non-positive counts would silently skip trailing updates)")
    if tree not in ("pairwise", "flat"):
        raise ValueError(f"unknown tree {tree!r} (pairwise|flat)")
    if tree == "flat":
        # the flat election is ONE (nch*v, v) LU custom call per
        # tournament; keep every such stack within the derived
        # single-call VMEM-safe height (blas.single_call_rows — v5e pin
        # 8192 rows at v=1024; 16384 fails to compile). Two tournaments
        # can go flat: the local nomination over Ml rows, and (gather
        # election, Px > 1) the cross-x election over the Px*v nominee
        # panel, whose chunk is additionally capped at the batched bound
        # (the elect() call site). Butterfly's pair reductions are 2v
        # tall — single-chunk at any legal v, never a flat stack.
        v = geom.v
        stacks = []
        _, nch = blas.chunk_layout(geom.Ml, v, panel_chunk)
        if nch > 1:
            stacks.append(nch * v)
        if geom.grid.Px > 1 and election == "gather":
            # same compute-dtype ceiling the traced election uses (the
            # elect() call site) — an f32-blind guard would admit f64
            # flat stacks twice the true single-call-safe height
            _, nch2 = blas.chunk_layout(
                geom.grid.Px * v, v,
                min(panel_chunk, blas.batched_call_rows(v, cdtype)))
            if nch2 > 1:
                stacks.append(nch2 * v)
        if stacks and max(stacks) > blas.single_call_rows(v, cdtype):
            raise ValueError(
                f"tree='flat' would stack {max(stacks)} nominee rows of "
                f"width {v} in one LU call (> the "
                f"{blas.single_call_rows(v, cdtype)}-row VMEM-safe height "
                f"for {jnp.dtype(cdtype).name}); "
                "raise panel_chunk or use tree='pairwise'")
    if update not in ("segments", "block"):
        raise ValueError(f"unknown update {update!r} (segments|block)")
    return _build(geom, mesh_cache_key(mesh), precision, backend,
                  panel_chunk, donate, resumable, lookahead, election,
                  tuple(segs), tree, update)


def lu_factor_distributed(shards, geom: LUGeometry, mesh,
                          precision=None, backend: str | None = None,
                          panel_chunk: int | None = None,
                          donate: bool = False, lookahead: bool = False,
                          election: str = "gather", segs: tuple = (16, 16),
                          tree: str = "pairwise",
                          update: str = "segments"):
    """Factor block-cyclic shards (Px, Py, Ml, Nl) in place on a mesh.

    Returns (shards_out, perm): shards_out holds the packed factors in
    *pivoted row order* (LAPACK getrf layout — global position p holds the
    factor row of original row perm[p], so gathered(shards_out) == the
    packed LU of A[perm]); perm is (M,) int32, replicated. Rows eliminated
    at step k occupy positions k*v..(k+1)*v, so perm[:n_steps*v] reshaped
    to (n_steps, v) is the elimination record (the old `pivots` output).

    Rank-deficient inputs: supersteps whose candidates are exactly zero
    elect no valid rows, leaving that block's perm entries unspecified and
    its factor rows garbage (the getrf `info > 0` situation); everything
    eliminated before the degeneracy is correct and frozen.

    `panel_chunk` bounds the height of every LU call inside the pivot
    election (default: `blas.single_call_rows(v)` — the derived
    single-call VMEM-safe height, 8192 on a v5e at v=1024, safe for the
    unbatched cond'd nomination calls; the batched election stack is
    additionally capped at `blas.batched_call_rows(v)`).
    `donate=True` aliases the input shards into the output (the caller's
    array is invalidated) — at N=32768 f32 on a 16 GB chip this saves the
    4 GB that makes the difference between fitting and OOM.
    `lookahead=True` selects the software-pipelined loop: the next step's
    panel reduce + pivot election are dataflow-independent of the current
    trailing GEMMs, letting XLA overlap the election collectives with
    compute on a mesh (P8; bitwise-identical results, ~one extra
    (Ml, v)-slab GEMM per superstep of redundant work).
    `tree` shapes the election's reduction ('pairwise' binary tree vs
    'flat' single stacked LU — fewer sequential latency-bound custom
    calls; see `ops.blas.tournament_winners`). Both are valid CALU
    elections; pivot choices can differ on ties, so results are
    comparable by residual, not bitwise.
    (An experimental `swap='dma'` Pallas row-DMA alternative to the XLA
    displacement scatter existed rounds 3-4; it was deleted unadopted
    per the pre-decided hardware-A/B-or-delete criterion when the chip
    stayed unreachable. Git history has the kernel and
    its staged probe protocol.)
    """
    from conflux_tpu.geometry import check_shards

    shards = jnp.asarray(shards)
    check_shards(shards, geom)
    # the default panel_chunk resolves inside build_program from the
    # compute dtype — ONE resolution point, so a retune cannot
    # desynchronize the entry paths from the --profile build
    fn = build_program(geom, mesh, precision=precision, backend=backend,
                       panel_chunk=panel_chunk, donate=donate,
                       lookahead=lookahead, election=election,
                       segs=segs, tree=tree, update=update,
                       dtype=shards.dtype)
    return fn(shards)


def lu_factor_steps(shards, geom: LUGeometry, mesh, k0: int, k1: int,
                    orig=None, precision=None, backend: str | None = None,
                    panel_chunk: int | None = None, donate: bool = False,
                    election: str = "gather", segs: tuple = (16, 16),
                    tree: str = "pairwise", update: str = "segments"):
    """Factor supersteps [k0, k1) only — the checkpoint/restart primitive.

    The reference has no notion of resuming a partial factorization
    (SURVEY §5: any rank failure kills the job and the work); here the
    mid-factorization state is first-class because the matrix lives in
    LAPACK-order positions: after k steps, global positions < k*v hold
    frozen factor rows and the rest is the updated trailing problem.

    State = (shards, orig): `orig` is the (Px, Ml) row-origin map
    (original row id at each local position). Pass orig=None when k0 == 0
    (rows start in original order); feed each call's outputs to the next.
    Both arrays are plain host-saveable values (`io.save_matrix` works on
    gathered shards), so a long factorization can checkpoint every few
    supersteps and restart after preemption — run the same call sequence
    with the loaded state.

    Returns (shards_out, orig_out, perm). perm is only the FINAL
    permutation once k1 == geom.n_steps; at intermediate k1 its entries
    beyond position k1*v still name unfactored rows.

    Bitwise caveat: the state output consolidates the 2.5D z-partial sums
    into one z-replicated copy (that is what makes the checkpoint compact
    — one matrix, not Pz layers). With Pz > 1 a resumed run therefore
    re-associates those sums and is numerically equivalent to, but not
    bit-identical with, the uninterrupted factorization (f32 rounding at
    partial-sum granularity). Pz == 1 round-trips exactly.
    """
    if not (0 <= k0 < k1 <= geom.n_steps):
        raise ValueError(
            f"step range [{k0}, {k1}) outside [0, {geom.n_steps})")
    if orig is None:
        if k0 != 0:
            raise ValueError("resuming at k0 > 0 requires the orig state "
                             "returned by the previous lu_factor_steps call")
        # rows start in original order: origin == global row index (the
        # same gri map the geometry exposes)
        orig = jnp.asarray(geom.global_row_index(), jnp.int32)
    # the step bounds are traced scalars: every segment of a checkpointed
    # run reuses ONE compiled program. `segs` rides through so a resumed
    # run keeps the tuned segmentation (math-invariant, perf-only);
    # `tree` rides through because trees may elect different winners on
    # ties — a resume must keep the uninterrupted run's pivot bracket.
    # The default chunk resolves inside build_program with the same
    # compute dtype as lu_factor_distributed's: a dtype-blind default
    # here would chunk a resumed f64 run differently from the run it
    # resumes (different nomination bracket -> different pivots).
    fn = build_program(geom, mesh, precision=precision, backend=backend,
                       panel_chunk=panel_chunk, donate=donate,
                       resumable=True, election=election, segs=segs,
                       tree=tree, update=update,
                       dtype=jnp.asarray(shards).dtype)
    return fn(shards, orig, jnp.int32(k0), jnp.int32(k1))


def lu_distributed_host(A: np.ndarray, grid: Grid3, v: int, mesh=None,
                        precision=None, backend: str | None = None,
                        panel_chunk: int | None = None,
                        segs: tuple = (16, 16), tree: str = "pairwise",
                        update: str = "segments"):
    """Host-level convenience: scatter a global matrix, factor on the mesh,
    gather back. Returns (LU_packed (M, N) in original row order, perm (M,)).

    The role of the reference's `lu_params` + `LU_rep` + validation-gather
    pipeline (`examples/conflux_miniapp.cpp:92-167`) in one call.
    """
    geom = LUGeometry.create(A.shape[0], A.shape[1], v, grid)
    if mesh is None:
        mesh = make_mesh(grid)
    shards = geom.scatter(A)
    # the device shards are a single-use temp: donate them so the jitted
    # program aliases input into output (frees a full matrix of HBM)
    out, perm = lu_factor_distributed(
        jnp.asarray(shards), geom, mesh, precision=precision, backend=backend,
        panel_chunk=panel_chunk, donate=True, segs=segs, tree=tree,
        update=update,
    )
    perm = np.asarray(perm)
    LUp = geom.gather(np.asarray(out))  # factors in pivoted order
    LU = np.empty_like(LUp)
    LU[perm] = LUp  # back to original row order (LU[perm] == L@U packing)
    return LU, perm, geom


def full_permutation(pivots: np.ndarray, M: int) -> np.ndarray:
    """Elimination order -> row permutation of length M.

    pivots is (n_steps, v) global row indices; rows never chosen (only when
    M > N) are appended in ascending order as pure-L rows.
    """
    order = pivots.reshape(-1)
    if order.size < M:
        rest = np.setdiff1d(np.arange(M), order)
        order = np.concatenate([order, rest])
    return order
