"""Pure-NumPy simulation of the distributed LU — the executable spec.

Simulates all Px*Py*Pz devices' local buffers in one process, with every
collective written out as an explicit sum/gather over the simulated device
array — exactly the technique the reference used to develop its algorithm
without a cluster (`python/conflux.py:40`: `A11Buff = np.zeros([P, Nl, Nl])`).

This spec mirrors `conflux_tpu.lu.distributed` step for step (z-partial
shards, value-level pivot masks, layer-0 factor writes), so tests can check
the shard_map implementation against it buffer-for-buffer. Pivoting is
pluggable (reference `python/pivoting.py:14-18`):

  'tournament' — local candidate LU + stacked election (the production path)
  'partial'    — global partial pivoting by |max| column scan (quality oracle)
  'none'       — no pivoting (only safe for diagonally dominant inputs)
"""

from __future__ import annotations

import numpy as np
import scipy.linalg

from conflux_tpu.geometry import Grid3, LUGeometry


def _np_compute_dtype(dtype) -> np.dtype:
    """NumPy mirror of `blas.compute_dtype` (no jax import): panel math
    runs in f32 for narrow types, natively otherwise — the dtype the
    impl resolves its chunk ceilings with."""
    dtype = np.dtype(dtype)
    if dtype == np.float16 or dtype.name == "bfloat16":
        return np.dtype(np.float32)
    return dtype


def _lu_packed(A: np.ndarray):
    """Packed LU with row pivoting: returns (lu, perm) with A[perm] = L@U."""
    P, L, U = scipy.linalg.lu(A)
    # perm from P: A = P L U  =>  A[perm] = L U with perm = argmax over P^T
    perm = np.argmax(P.T, axis=1) if P.shape[0] else np.arange(0)
    lu = np.tril(L[:, : U.shape[0]], -1) + np.pad(
        U, ((0, L.shape[0] - U.shape[0]), (0, 0))
    )
    return lu, perm


_ID_SENTINEL = np.iinfo(np.int64).max


def _take_fill(a: np.ndarray, idx: np.ndarray, fill):
    """NumPy mirror of `jnp.take(..., mode='fill')`: out-of-range ids give
    `fill` instead of clamping (the implementation relies on this to keep
    tournament pad ids from aliasing real rows)."""
    out = np.full((len(idx),) + a.shape[1:], fill, dtype=a.dtype)
    ok = idx < a.shape[0]
    out[ok] = a[idx[ok]]
    return out


def _tournament_winners_np(panel: np.ndarray, v: int, chunk: int):
    """NumPy mirror of `ops/blas.tournament_winners`: chunked nomination +
    binary reduction tree of (2v, v) LUs. Same chunk rounding, same pad-id
    convention, same return contract (packed winner LU, winner row ids)."""
    m = panel.shape[0]
    c = min(chunk, -(-m // v) * v)
    c = max(v, c // v * v)
    nch = -(-m // c)
    mp = nch * c
    if mp != m:
        panel = np.pad(panel, ((0, mp - m), (0, 0)))
    cand = panel.reshape(nch, c, v)
    cid = np.arange(mp).reshape(nch, c)

    win, wid, lu0 = [], [], None
    for i in range(nch):
        lu_c, perm_c = _lu_packed(cand[i])
        if i == 0:
            lu0 = lu_c[:v]
        top = perm_c[:v]
        win.append(cand[i][top])
        wid.append(cid[i][top])
    win, wid = np.stack(win), np.stack(wid)

    n = 1 << (nch - 1).bit_length()
    if n != nch:
        win = np.pad(win, ((0, n - nch), (0, 0), (0, 0)))
        wid = np.pad(wid, ((0, n - nch), (0, 0)), constant_values=mp)
    if n == 1:
        return lu0, wid[0]

    lu_top = None
    while n > 1:
        stacked = win.reshape(n // 2, 2 * v, v)
        sid = wid.reshape(n // 2, 2 * v)
        lus, wins, wids = [], [], []
        for i in range(n // 2):
            lu_r, perm_r = _lu_packed(stacked[i])
            top = perm_r[:v]
            lus.append(lu_r[:v])
            wins.append(stacked[i][top])
            wids.append(sid[i][top])
        lu_top, win, wid = np.stack(lus), np.stack(wins), np.stack(wids)
        n //= 2
    return lu_top[0], wid[0]


def _select_tournament(cand: np.ndarray, gri_m: np.ndarray, Px: int, v: int,
                       chunk: int):
    """Chunked CALU: per-x-rank chunked nomination, then the same chunked
    reduction tree elects winners from the Px*v gathered nominees — mirrors
    the shard_map implementation's step-1 exactly (height-bounded LUs).
    With a single x-rank the nomination IS the election (the implementation
    skips the second tournament; so does the spec, keeping pivot order
    identical)."""
    if Px == 1:
        lu00, top = _tournament_winners_np(cand[0], v, chunk)
        return _take_fill(gri_m[0], top, _ID_SENTINEL), lu00
    noms, nids = [], []
    for px in range(Px):
        _, top = _tournament_winners_np(cand[px], v, chunk)
        noms.append(_take_fill(cand[px], top, 0.0))
        nids.append(_take_fill(gri_m[px], top, _ID_SENTINEL))
    stack = np.concatenate(noms, axis=0)
    sids = np.concatenate(nids, axis=0)
    # the implementation's election tournament is batched, so its chunk is
    # capped at the batched VMEM-safe bound; the helper is imported (not
    # duplicated) so retuning it cannot desynchronize spec and impl
    from conflux_tpu.ops import blas

    # pinned budget, NOT device detection: the spec is pure NumPy and a
    # simulation — its chunking must not depend on which host runs it.
    # dtype is a property of the INPUT (mirrors the impl's compute-dtype
    # resolution), so the spec stays host-independent AND synchronized.
    cap = blas.batched_call_rows(v, _np_compute_dtype(stack.dtype),
                                 budget=blas._MODEL_VMEM_BYTES)
    lu00, wid = _tournament_winners_np(stack, v, min(chunk, cap))
    gpiv = _take_fill(sids, wid, _ID_SENTINEL)
    return gpiv, lu00


def _select_partial(cand: np.ndarray, gri_m: np.ndarray, Px: int, v: int,
                    chunk: int):
    """Global partial pivoting: eliminate column by column over the full
    stacked candidate set (the quality oracle the tournament approximates)."""
    stacked = np.concatenate(list(cand), axis=0).copy()
    sgri = np.concatenate(list(gri_m), axis=0)
    m = stacked.shape[0]
    order = np.arange(m)
    for j in range(v):
        p = j + int(np.argmax(np.abs(stacked[j:, j])))
        stacked[[j, p]] = stacked[[p, j]]
        order[[j, p]] = order[[p, j]]
        piv = stacked[j, j]
        if piv != 0:
            stacked[j + 1 :, j] /= piv
            stacked[j + 1 :, j + 1 :] -= np.outer(stacked[j + 1 :, j], stacked[j, j + 1 :])
    return sgri[order[:v]], stacked[:v]


def _select_none(cand: np.ndarray, gri_m: np.ndarray, Px: int, v: int,
                 chunk: int):
    """Take the v lowest-numbered active rows, in global row order."""
    sgri = np.concatenate(list(gri_m), axis=0)
    stacked = np.concatenate(list(cand), axis=0)
    order = np.argsort(sgri, kind="stable")[:v]
    lu, _ = _lu_packed_nopiv(stacked[order])
    return sgri[order], lu


def _lu_packed_nopiv(A: np.ndarray):
    lu = A.astype(float).copy()
    v = lu.shape[1]
    for j in range(min(v, lu.shape[0])):
        lu[j + 1 :, j] /= lu[j, j]
        lu[j + 1 :, j + 1 :] -= np.outer(lu[j + 1 :, j], lu[j, j + 1 :])
    return lu, np.arange(lu.shape[0])


PIVOTING_STRATEGIES = {
    "tournament": _select_tournament,
    "partial": _select_partial,
    "none": _select_none,
}


def simulate_lu(A: np.ndarray, grid: Grid3, v: int, pivoting: str = "tournament",
                panel_chunk: int | None = None):
    """Run the full distributed algorithm on simulated devices.

    Mirrors the implementation's LAPACK-order layout: rows live at their
    *currently-pivoted global position*; each step swaps the elected pivot
    rows into the step's diagonal block and the displaced occupants into
    the vacated slots (the implementation's value-level answer to the
    reference's `push_pivots_up` compaction, `conflux_opt.hpp:176-218`).

    Returns (LU (M, N) packed factors in original row order, pivots
    (n_steps, v) original global rows in elimination order), matching
    `conflux_tpu.lu.distributed.lu_factor_distributed` (whose shards come
    back pivoted; its `perm[:n_steps*v]` reshaped is this `pivots`).
    `panel_chunk` defaults to the implementation's default
    (`blas.single_call_rows(v)`); pass the same value used there
    for buffer-exact cross-validation in the chunked regime.

    Divergence caveat: the spec pins its chunk ceilings to the 32 MiB
    `blas._MODEL_VMEM_BYTES` so the simulation is host-independent,
    while the implementation honors CONFLUX_TPU_SCOPED_VMEM_BYTES /
    `set_scoped_vmem_bytes` / the device-kind table. When such an
    override is active, default-chunk runs of the two can elect
    different pivots (different nomination brackets). For buffer-exact
    cross-validation either pass an explicit `panel_chunk` to BOTH, or
    assert `blas.scoped_vmem_bytes() == blas._MODEL_VMEM_BYTES`
    first (the spec-vs-impl tests do).
    """
    if panel_chunk is None:
        from conflux_tpu.ops import blas

        # pinned budget (see _select_tournament): host-independent spec;
        # dtype from the input, mirroring lu_factor_distributed
        panel_chunk = blas.single_call_rows(
            v, _np_compute_dtype(np.asarray(A).dtype),
            budget=blas._MODEL_VMEM_BYTES)
    select = PIVOTING_STRATEGIES[pivoting]
    geom = LUGeometry.create(A.shape[0], A.shape[1], v, grid)
    Px, Py, Pz = grid.Px, grid.Py, grid.Pz
    Ml, Nl = geom.Ml, geom.Nl
    nlayr = geom.nlayr

    # per-device state, indexed [x, y, z]
    shards = geom.scatter(A).astype(np.float64)
    Aloc = np.zeros((Px, Py, Pz, Ml, Nl))
    Aloc[:, :, 0] = shards  # data enters on layer z=0

    gp = geom.global_row_index()  # (Px, Ml): global POSITION of local rows
    orig = gp.copy()  # original row id currently at each position
    ctile = np.stack([(np.arange(Nl) // v) * Py + y for y in range(Py)])

    def loc(pos):
        """(x, local row) owning global position pos."""
        t = pos // v
        return t % Px, (t // Px) * v + pos % v

    pivots = np.zeros((geom.n_steps, v), np.int64)

    for k in range(geom.n_steps):
        yo, lj = k % Py, (k // Py) * v
        io, li = k % Px, (k // Px) * v

        # panel = psum over (y, z) of the owner column        [collective]
        panel = Aloc[:, yo, :, :, lj : lj + v].sum(axis=1)  # (Px, Ml, v)

        # pivot selection over the x axis                     [collective]
        live = gp >= k * v
        cand = np.where(live[:, :, None], panel, 0.0)
        pos_m = np.where(live, gp, _ID_SENTINEL)
        wpos, lu00 = select(cand, pos_m, Px, v, panel_chunk)
        U00 = np.triu(lu00)
        L00 = np.tril(lu00, -1) + np.eye(v)

        # swap bookkeeping: winners -> diagonal slots; displaced occupants
        # -> slots vacated by external winners (both ascending)
        slots = k * v + np.arange(v)
        occ_is_winner = np.isin(slots, wpos)
        ext = np.sort(wpos[wpos >= (k + 1) * v])
        disp = np.nonzero(~occ_is_winner)[0]
        assert len(ext) == len(disp)

        # winners' full rows + ids + panel rows (psum over (x, z))
        Prows = np.zeros((Py, v, Nl))
        worig = np.zeros(v, np.int64)
        for j, p in enumerate(wpos):
            xw, lw = loc(p)
            Prows[:, j, :] = Aloc[xw, :, :, lw, :].sum(axis=1)
            worig[j] = orig[xw, lw]
        pivots[k] = worig
        # displaced occupants' rows + ids
        Drows = Aloc[io, :, :, li : li + v, :].sum(axis=1)  # (Py, v, Nl)
        dorig = orig[io, li : li + v].copy()
        diag_panel = panel[io, li : li + v].copy()  # (v, v)

        # swap writes: vacated slots take the displaced rows (full value on
        # z0, zero elsewhere); diagonal rows are rewritten after the GEMM
        panel_post = panel.copy()
        for i, j in enumerate(disp):
            xd, ld = loc(ext[i])
            for y in range(Py):
                Aloc[xd, y, 0, ld] = Drows[y, j]
                Aloc[xd, y, 1:, ld] = 0.0
            orig[xd, ld] = dorig[j]
            panel_post[xd, ld] = diag_panel[j]
        orig[io, li : li + v] = worig

        # L10 on the live row suffix (duplicated compute)
        row_live = gp >= (k + 1) * v
        act = np.where(row_live[:, :, None], panel_post, 0.0)
        # X U00 = act  =>  U00^T X^T = act^T
        L10 = scipy.linalg.solve_triangular(
            U00, act.reshape(-1, v).T, trans="T", lower=False
        ).T.reshape(Px, Ml, v)

        U01 = np.stack(
            [scipy.linalg.solve_triangular(L00, Prows[y], lower=True, unit_diagonal=True)
             for y in range(Py)]
        )  # (Py, v, Nl)

        # trailing update on the (row-suffix x col-suffix) live block
        for x in range(Px):
            rl = row_live[x]
            for y in range(Py):
                trail = ctile[y] > k
                for z in range(Pz):
                    s0, s1 = z * nlayr, min((z + 1) * nlayr, v)
                    upd = L10[x][:, s0:s1] @ U01[y][s0:s1, :]
                    Aloc[x, y, z][np.ix_(rl, trail)] -= upd[np.ix_(rl, trail)]

        # factor writes on layer 0: diagonal rows keep the winners' frozen
        # L prefix (leading cols), take packed lu00 (panel tile) and U01
        # (trailing cols)
        for y in range(Py):
            trail = ctile[y] > k
            Aloc[io, y, 0, li : li + v] = np.where(trail[None, :], U01[y], Prows[y])
            Aloc[io, y, 1:, li : li + v] = 0.0
        # panel column on the owner y
        for x in range(Px):
            for z in range(Pz):
                col = Aloc[x, yo, z][:, lj : lj + v]
                if z == 0:
                    col[row_live[x]] = L10[x][row_live[x]]
                    if x == io:
                        col[li : li + v] = lu00
                else:
                    col[:] = 0.0
                Aloc[x, yo, z][:, lj : lj + v] = col

    LUp = geom.gather(Aloc.sum(axis=2))  # factors in pivoted order
    # permutation: original row id at each global position
    perm = np.empty(geom.M, np.int64)
    for x in range(Px):
        perm[gp[x]] = orig[x]
    LU = np.empty_like(LUp)
    LU[perm] = LUp  # original row order, matching the host wrapper
    return LU, pivots
