"""Hand-written Pallas TPU kernels for the hot tile ops.

The trailing-matrix GEMM is where ~2/3 N^3 of the factorization's flops live
(reference `conflux_opt.hpp:1626-1634`); this module provides an MXU-tiled
Pallas implementation behind the `conflux_tpu.ops.blas` backend registry.
Off-TPU (CPU simulation in tests) the kernels run in interpret mode.
"""

from __future__ import annotations

import functools

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
# Row scatter (REMOVED, round 4)
# --------------------------------------------------------------------------- #
#
# An experimental pipelined row-DMA scatter (HBM -> VMEM -> HBM with
# scalar-prefetched destination indices, in-place aliasing) lived here in
# rounds 3-4 as the `swap='dma'` alternative to XLA's serial per-row
# scatter loop (~10 ms/step at v=1024, N=32768). The pre-decided adoption
# criterion (docs/ROUND3.md #3) required a staged hardware A/B with a
# full-scale residual gate; the remote chip of that time never came back
# to run it, so the kernel was deleted unadopted per VERDICT r3 item 3
# ("no third state"). Git history (rounds 3-4) has the kernel,
# its bring-up protocol (scripts/swap_probe.py), and the lesson that
# direct HBM->HBM local DMA wedges the chip (local copies want a VMEM
# side).


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
