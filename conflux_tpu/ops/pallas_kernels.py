"""Hand-written Pallas TPU kernels for the hot tile ops.

The trailing-matrix GEMM is where ~2/3 N^3 of the factorization's flops live
(reference `conflux_opt.hpp:1626-1634`); this module provides an MXU-tiled
Pallas implementation behind the `conflux_tpu.ops.blas` backend registry.
`row_scatter` writes the LU's step-2 row swaps in place on TPU.
Off-TPU (CPU simulation in tests) the kernels run in interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, precision):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(
        a_ref[:], b_ref[:], preferred_element_type=jnp.float32,
        precision=precision,
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _store():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "precision"))
def _gemm(a, b, bm: int, bn: int, bk: int, interpret: bool, precision=None):
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    if (Mp, Kp) != (M, K):
        a = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        b = jnp.pad(b, ((0, Kp - K), (0, Np - N)))
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, precision=precision),
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * Mp * Np * Kp,
            bytes_accessed=(Mp * Kp + Kp * Np + Mp * Np) * a.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(a, b)
    if (Mp, Np) != (M, N):
        out = out[:M, :N]
    return out


def gemm(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
         bk: int = 512, precision=None) -> jax.Array:
    """a @ b via an MXU-tiled Pallas kernel with float32 accumulation.
    `precision` as for `jnp.dot`: on TPU the default runs f32 operands
    through one bf16 MXU pass."""
    M, K = a.shape
    _, N = b.shape
    # clamp blocks for small operands; keep MXU/VPU-aligned minima
    bm = min(bm, _round_up(M, 128))
    bn = min(bn, _round_up(N, 128))
    bk = min(bk, _round_up(K, 128))
    interpret = jax.default_backend() != "tpu"
    return _gemm(a, b, bm, bn, bk, interpret, precision)


# --------------------------------------------------------------------------- #
# Panel LU: masked Gaussian elimination on a VMEM-resident column block
# --------------------------------------------------------------------------- #
#
# The role of the reference's per-rank `LAPACKE_dgetrf` panel kernel (`LUP`,
# `conflux_opt.hpp:143-166`), redesigned for the TPU vector unit: rows never
# move (XLA's LU custom call swaps rows serially per column and overflows its
# scoped VMEM on tall panels). Instead the whole (m, w) block lives in VMEM
# and each of the w elimination steps is a handful of full-array masked VPU
# ops: select pivot by masked argmax, record it, write multipliers in place,
# rank-1-update the live rows. Pivot rows keep their (now U-row) values in
# their original positions; `alive` marks rows not yet chosen. The caller
# gathers rows into LAPACK order once at the end of the full panel.

_PANEL_W = 128  # column-block width == one lane tile


def _lu_block_kernel(a_ref, alive_ref, out_ref, alive_out_ref, piv_ref):
    m, w = a_ref.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, w), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (m, w), 1)
    cols1 = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)

    out_ref[:] = a_ref[:]
    alive_out_ref[:] = alive_ref[:]
    piv_ref[:] = jnp.zeros((1, w), jnp.int32)

    # Mutate the output refs per step; the loop carry stays scalar (Mosaic
    # cannot legalize scf.for with large value carries). Two more Mosaic
    # constraints shape the body: there is no (m, 1) -> (m, w) lane
    # broadcast (the pivot column/row are spread with small MXU matmuls
    # instead), and boolean ops between lane-iota-derived masks (sublane-
    # replicated i1 layout) and data-derived masks trigger invalid i1
    # relayouts — so every mask is cast to f32 and combined arithmetically.
    def body(j, carry):
        A = out_ref[:]
        alive_f = (alive_out_ref[:] != 0).astype(jnp.float32)
        # broadcast column j across lanes with a roll-reduction tree: the
        # masked array has a single nonzero per row, so the cyclic tree sum
        # is EXACT in f32 (an MXU broadcast would truncate to bf16 passes)
        colj = jnp.where(cols == j, A, 0.0)
        s = 1
        while s < w:
            colj = colj + pltpu.roll(colj, s, 1)
            s *= 2
        cand = jnp.abs(colj) * alive_f - (1.0 - alive_f)  # dead rows -> -1
        # masked argmax as reductions to scalar (lowest row wins ties)
        p = jnp.min(jnp.where(cand == jnp.max(cand), rows, m)).astype(jnp.int32)
        isp_f = (rows == p).astype(jnp.float32)
        # pivot row: dynamic sublane read (supported, unlike lane indexing),
        # then an exact sublane broadcast
        rowp_bc = jnp.broadcast_to(out_ref[pl.ds(p, 1), :], (m, w))
        colmask_f = (cols == j).astype(jnp.float32)
        gtmask_f = (cols > j).astype(jnp.float32)
        pivval = jnp.sum(isp_f * colmask_f * A)
        live_f = alive_f * (1.0 - isp_f)
        l = colj / pivval * live_f  # (m, w) multipliers, 0 on dead/pivot rows
        # rank-1 update of live rows, trailing columns only; multipliers into
        # column j of live non-pivot rows
        A = A - gtmask_f * (l * rowp_bc)
        maskf = colmask_f * live_f
        A = A * (1.0 - maskf) + l * maskf
        out_ref[:] = A
        alive_out_ref[:] = live_f.astype(jnp.int8)
        piv_ref[:] = jnp.where(cols1 == j, p, piv_ref[:])
        return carry

    jax.lax.fori_loop(0, w, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lu_block(a, alive, interpret: bool):
    m, w = a.shape
    return pl.pallas_call(
        _lu_block_kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((m, w), a.dtype),
            jax.ShapeDtypeStruct((m, w), jnp.int8),
            jax.ShapeDtypeStruct((1, w), jnp.int32),
        ),
        interpret=interpret,
    )(a, alive)


# --------------------------------------------------------------------------- #
# Row scatter: a[idx[i]] = rows[i], in place, by aligned row groups
# --------------------------------------------------------------------------- #
#
# XLA lowers a row scatter into an (M, W) matrix to a serial loop, one row
# per trip with a select and a bounds check around each write (~10 us a
# row at W=32768 on a v5e). A DMA cannot write a single row either: the
# HBM layout is tiled (8, 128) for 4-byte types, and Mosaic refuses a
# 1-row slice of a tiled memref. So each destination's aligned 8-row group
# is read into VMEM, the row is stored into it there (a dynamic 1-row load
# and store are fine, a 1-row memref slice is not), and the group is
# written back. Kept indices ascend (the caller's precondition), so the
# destinations that share a group come one after another and ride one
# read-modify-write, and every group in flight is distinct: several reads
# can run ahead of the writes without a hazard. The source rows stream
# through VMEM in aligned 8-row blocks, double-buffered. The matrix is
# worked in column stripes, one grid step each, so the VMEM the kernel
# takes is bounded whatever W is; a (32768, 32768) matrix is one stripe.
# On a v5e the kernel is bound by HBM: at N=32768 it moves each displaced
# row's 1 MiB group in and out at ~570 GB/s, with 4 to 10 slots alike.
# Copies that name a column range are slower than whole-row ones (4.43 to
# 4.58 ms, with 2, 3 or 6 slots, against 3.83 for ~985 rows into a
# (32768, 32768) matrix), so a matrix of one stripe is copied by whole rows.

_ROW_GROUP = 8       # sublanes of one (8, 128) tile of a 4-byte type
_SCATTER_SLOTS = 6   # VMEM group buffers: reads run up to 5 groups ahead
_SCATTER_LANES = 2048  # columns per row copy (16 vregs of f32)
_SCATTER_VMEM = 8 << 20  # bytes of group and source buffers, under the
                         # 16 MiB of scoped VMEM a v5e gives a kernel


def _stripe_cols(w: int, itemsize: int) -> int:
    """Columns of one stripe: the widest multiple of 128 that divides W and
    keeps the (slots + 2) 8-row buffers within `_SCATTER_VMEM`."""
    cap = _SCATTER_VMEM // ((_SCATTER_SLOTS + 2) * _ROW_GROUP * itemsize)
    k = w // 128
    return 128 * max(d for d in range(1, k + 1)
                     if k % d == 0 and 128 * d <= cap)


def _row_scatter_kernel(idx_ref, a_hbm, rows_hbm, o_hbm, grp, src, starts,
                        gsem, ssem):
    del a_hbm  # aliased with o_hbm: every read and write goes through o_hbm
    m = o_hbm.shape[0]
    n = rows_hbm.shape[0]
    g8 = _ROW_GROUP
    slots, _, w = grp.shape  # w: the stripe's columns
    if o_hbm.shape[1] != w:
        stripe = pl.ds(pl.multiple_of(pl.program_id(0) * w, 128), w)
        o_hbm, rows_hbm = o_hbm.at[:, stripe], rows_hbm.at[:, stripe]
    nblk = n // g8
    lanes = math.gcd(w, _SCATTER_LANES)

    # runs: maximal stretches of i whose kept destinations share a group;
    # starts[r] is the first kept i of run r, starts[nrun] == n
    def scan(i, carry):
        nrun, gprev = carry
        d = idx_ref[i]
        new = (d < m) & (d // g8 != gprev)

        @pl.when(new)
        def _():
            starts[nrun] = i

        return nrun + new.astype(jnp.int32), jnp.where(d < m, d // g8, gprev)

    nrun, _ = jax.lax.fori_loop(0, n, scan, (jnp.int32(0), jnp.int32(-1)))
    starts[nrun] = n

    def group(r):
        return pl.ds(pl.multiple_of(idx_ref[starts[r]] // g8 * g8, g8), g8)

    def read(r, slot):
        return pltpu.make_async_copy(o_hbm.at[group(r)], grp.at[slot],
                                     gsem.at[0, slot])

    def write(r, slot):
        return pltpu.make_async_copy(grp.at[slot], o_hbm.at[group(r)],
                                     gsem.at[1, slot])

    def fetch(j):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(j * g8, g8), g8)],
            src.at[j % 2], ssem.at[j % 2])

    def advance(jcur, j):
        """Wait for source blocks up to j, prefetching the one after."""
        def step(jc):
            jc = jc + 1
            fetch(jc).wait()

            @pl.when(jc + 1 < nblk)
            def _():
                fetch(jc + 1).start()

            return jc

        return jax.lax.while_loop(lambda jc: jc < j, step, jcur)

    def put(i, slot, jcur):
        d = idx_ref[i]
        jcur = advance(jcur, i // g8)

        @pl.when(d < m)
        def _():
            for c in range(0, w, lanes):
                cols = pl.ds(c, lanes)
                grp[slot, pl.ds(d % g8, 1), cols] = src[
                    (i // g8) % 2, pl.ds(i % g8, 1), cols]

        return jcur

    def run(r, jcur):
        slot = r % slots
        read(r, slot).wait()
        jcur = jax.lax.fori_loop(starts[r], starts[r + 1],
                                 lambda i, jc: put(i, slot, jc), jcur)
        write(r, slot).start()
        # the slot written one run ago takes the read `slots - 1` runs ahead
        prev = (r + slots - 1) % slots

        @pl.when(r >= 1)
        def _():
            write(r - 1, prev).wait()

        @pl.when(r + slots - 1 < nrun)
        def _():
            read(r + slots - 1, prev).start()

        return jcur

    fetch(0).start()
    for r in range(slots - 1):
        @pl.when(r < nrun)
        def _(r=r):
            read(r, r).start()

    jcur = jax.lax.fori_loop(0, nrun, run, jnp.int32(-1))

    @pl.when(nrun >= 1)
    def _():
        write(nrun - 1, (nrun - 1) % slots).wait()

    advance(jcur, nblk - 1)  # drain the source stream


@functools.partial(jax.jit, static_argnames=("cols", "interpret"))
def _row_scatter(a, idx, rows, cols, interpret=False):
    m, w = a.shape
    n = rows.shape[0]
    slots = _SCATTER_SLOTS
    return pl.pallas_call(
        _row_scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(w // cols,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((slots, _ROW_GROUP, cols), a.dtype),
                pltpu.VMEM((2, _ROW_GROUP, cols), a.dtype),
                pltpu.SMEM((n + 1,), jnp.int32),
                pltpu.SemaphoreType.DMA((2, slots)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m, w), a.dtype,
                                       vma=jax.typeof(a).vma),
        input_output_aliases={1: 0},
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=(2 * _ROW_GROUP + 1) * n * w * a.dtype.itemsize),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(idx, a, rows)


def row_scatter(a: jax.Array, idx: jax.Array, rows: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """`a.at[idx].set(rows, mode="drop")`, written in place by a Pallas TPU
    kernel: a (M, W), idx (n,) int32, rows (n, W).

    Precondition: the kept indices (those < M) ascend strictly; indices
    >= M are dropped and may sit anywhere; none is negative. A 4-byte dtype
    (the (8, 128) tiling), M and n multiples of 8, W a multiple of 128.
    Pass `a` where the caller can give it up (a donated or loop-carried
    buffer): the output aliases it. `interpret` runs the TPU interpreter,
    which simulates every DMA (tests only: slow at real sizes).
    """
    m, w = a.shape
    n = rows.shape[0]
    if (a.dtype.itemsize != 4 or m % _ROW_GROUP or n % _ROW_GROUP
            or w % 128 or rows.shape[1] != w or idx.shape != (n,)):
        raise ValueError(
            f"row_scatter needs a 4-byte (M, W) matrix with M, n multiples "
            f"of {_ROW_GROUP} and W of 128, idx (n,), rows (n, W); got a "
            f"{a.dtype} {a.shape}, idx {idx.shape}, rows {rows.shape}")
    return _row_scatter(a, idx.astype(jnp.int32), rows.astype(a.dtype),
                        _stripe_cols(w, a.dtype.itemsize),
                        interpret=interpret)


def lu_block(a: jax.Array, alive: jax.Array):
    """Eliminate one (m, 128) column block in place (no row movement).

    `alive` is an (m, 1) mask of rows still eligible as pivots. Returns
    (out, alive_out, piv): `out` has U-row values sitting at the pivot rows'
    original positions and L multipliers at live rows; `piv` (1, 128) gives
    the chosen pivot row per elimination step. VMEM bound: the f32 block, the
    int8 mask and the (m, w) f32 temporaries must fit the 16 MB scoped VMEM
    — m <= 2048 (`blas._PALLAS_MAX_ROWS`; a described-v5e compile refuses
    m=4096).
    """
    m, w = a.shape
    interpret = jax.default_backend() != "tpu"
    alive_mw = jnp.broadcast_to(alive.astype(jnp.int8), (m, w))
    out, alive_out, piv = _lu_block(a, alive_mw, interpret)
    return out, alive_out[:, :1].astype(jnp.int32), piv
