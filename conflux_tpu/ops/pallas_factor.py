"""Batched Pallas factor kernels — the factor lane's batch-blocked core.

The serving stack's remaining vmap cliff (DESIGN §29): every coalesced
cold start, gang refactor and revival storm factors its stack through
`jax.vmap` over the SINGLE-system blocked bodies (`lu/single.py`,
`cholesky/single.py`), so the panel factorization — the latency-critical
serialized path, CONFLUX's core thesis — serializes per slot across the
batch. On TPU the batch axis belongs in the Pallas grid instead: grid
``(batch, panel-step)`` with the running matrix in persistent VMEM
scratch (the `_matmul_kernel` accumulator discipline), so every slot's
panel elimination is the same masked VPU program and the batch is pure
grid parallelism, not a vmapped loop.

Two kernels share the layout:

- :func:`pallas_lu_factor_batched` — partial-pivot LU. The elimination
  body is `pallas_kernels._lu_block_kernel`'s masked-winner pattern
  (rows never move; per column: masked-argmax pivot election, record,
  multipliers in place, rank-1 update) extended with the leading batch
  grid axis and FULL-width trailing updates: eliminating column j
  updates every trailing column of the live rows, so each pivot row
  already carries its finished U row when it is frozen — the blocked
  trailing update needs no in-kernel row gather and no triangular
  solve. The caller gathers rows into LAPACK order once, outside the
  kernel (one batched `take_along_axis`).
- :func:`pallas_cholesky_factor_batched` — the SPD counterpart, no
  pivot election; the trailing update keeps BOTH triangles of the
  running matrix symmetric so the update's row factor is a cheap
  sublane broadcast of row j (there is no (m, 1) -> (m, m) lane
  broadcast on the VPU — the column factor takes the roll-reduction
  tree, same as LU).

Mosaic constraints (documented at `_lu_block_kernel`) shape both
bodies: scalar-only `fori_loop` carries (the matrix mutates VMEM
scratch refs), masks cast to the accumulator dtype and combined
arithmetically (no i1 relayouts), lane broadcasts via the exact cyclic
roll-reduction tree (power-of-two width — the wrapper identity-pads N
up), pivot rows via dynamic sublane reads.

The factor epilogue fuses here too: the kernels accumulate the §21
Freivalds probe row ``wA = w^T A`` at step 0 while the pristine input
block is VMEM-resident (`probe_w=`), and the jitted wrappers the serve
layer traces (`FactorPlan._stacked_factor_body`) append the
``substitution='blocked'`` diagonal-block inverses and the probe solve
in the SAME program — a checked coalesced factor is one dispatch, with
no second factor-time pass re-reading A from HBM for the probe row.

Per-slot outputs are bitwise invariant to the batch size and the pad
contents — grid slots never interact — which preserves the bucket/pad
contract that gives bitwise parity between ``plan.factor`` (bucket 1)
and the coalesced factor lane. Off-TPU the kernels run in interpret
mode (the correctness-test path, like `pallas_blocked_trsm`); f64 is
interpret-only (Mosaic has no f64), and the VMEM working set bounds
the padded size on hardware: a described-v5e compile fits Np=512 in the
16 MiB scoped VMEM and refuses Np=1024, so larger sizes raise on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from conflux_tpu.ops.batched_trsm import _pad_identity

_PANEL_W = 128  # elimination-step chunk == one lane tile (grid dim 1)
_MAX_TPU_N = 512  # largest padded N whose described-v5e compile fits VMEM


def _pow2(n: int) -> int:
    """Next power of two >= n — the kernel's padded width: the exact
    lane broadcast is a cyclic roll-reduction tree, which double-counts
    wrapped shifts unless the lane width is a power of two."""
    return 1 << (max(1, int(n)) - 1).bit_length()


def _check_batched_square(A) -> None:
    if A.ndim != 3 or A.shape[-1] != A.shape[-2]:
        raise ValueError(
            f"batched factor kernels take (B, N, N), got {A.shape}")


def _interpret(m: int) -> bool:
    """Interpret mode off-TPU only; on TPU a width the kernel's VMEM
    working set cannot hold is refused, never run some other way."""
    interpret = jax.default_backend() != "tpu"
    if not interpret and m > _MAX_TPU_N:
        raise ValueError(
            f"pallas factor kernels: padded N={m} does not fit VMEM on TPU "
            f"(at most {_MAX_TPU_N}); use backend='xla'")
    return interpret


# --------------------------------------------------------------------------- #
# kernels: grid (batch, panel-step), persistent VMEM running matrix
# --------------------------------------------------------------------------- #


def _blu_kernel(a_ref, w_ref, o_ref, piv_ref, wa_ref, acc_ref, alive_ref,
                *, bw: int):
    """Batch-blocked partial-pivot LU, one (batch, panel-step) grid
    cell: eliminate columns [i*bw, (i+1)*bw) of this slot's running
    matrix (VMEM scratch, initialized from the input block at step 0).
    Masked-winner election per column; full-width rank-1 updates, so
    frozen pivot rows hold finished U rows in place."""
    i = pl.program_id(1)
    m = acc_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = a_ref[0].astype(acc_ref.dtype)
        alive_ref[:] = jnp.ones_like(alive_ref)
        # fused probe row: wA = w^T A off the pristine VMEM-resident
        # input block — no second factor-time pass re-reading A
        wa_ref[0] = jnp.dot(
            w_ref[:].astype(acc_ref.dtype), a_ref[0].astype(acc_ref.dtype),
            preferred_element_type=acc_ref.dtype,
            precision=lax.Precision.HIGHEST).astype(wa_ref.dtype)

    rows = lax.broadcasted_iota(jnp.int32, (m, m), 0)
    cols = lax.broadcasted_iota(jnp.int32, (m, m), 1)
    colsp = lax.broadcasted_iota(jnp.int32, (1, 1, m), 2)
    base = i * bw

    def body(jj, carry):
        j = base + jj
        A = acc_ref[:]
        alive_f = alive_ref[:]
        # lane-broadcast column j: one nonzero per row, so the cyclic
        # roll-reduction tree sum is EXACT (power-of-two m)
        colj = jnp.where(cols == j, A, 0.0)
        s = 1
        while s < m:
            colj = colj + pltpu.roll(colj, s, 1)
            s *= 2
        cand = jnp.abs(colj) * alive_f - (1.0 - alive_f)  # dead rows -> -1
        p = jnp.min(
            jnp.where(cand == jnp.max(cand), rows, m)).astype(jnp.int32)
        isp_f = (rows == p).astype(acc_ref.dtype)
        rowp_bc = jnp.broadcast_to(acc_ref[pl.ds(p, 1), :], (m, m))
        colmask_f = (cols == j).astype(acc_ref.dtype)
        gtmask_f = (cols > j).astype(acc_ref.dtype)
        pivval = jnp.sum(isp_f * colmask_f * A)
        live_f = alive_f * (1.0 - isp_f)
        lmul = colj / pivval * live_f  # multipliers; 0 on dead/pivot rows
        # FULL-width rank-1 update of live rows (every trailing column,
        # future panels included) — what lets frozen pivot rows carry
        # finished U rows with no in-kernel gather/trsm; multipliers
        # land in column j of the live rows
        A = A - gtmask_f * (lmul * rowp_bc)
        maskf = colmask_f * live_f
        A = A * (1.0 - maskf) + lmul * maskf
        acc_ref[:] = A
        alive_ref[:] = live_f
        piv_ref[:] = jnp.where(colsp == j, p, piv_ref[:])
        return carry

    jax.lax.fori_loop(0, bw, body, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _store():
        o_ref[0] = acc_ref[:].astype(o_ref.dtype)


def _bchol_kernel(a_ref, w_ref, o_ref, wa_ref, acc_ref, *, bw: int):
    """Batch-blocked Cholesky, one (batch, panel-step) grid cell: no
    pivot election; the trailing update keeps BOTH triangles of the
    running matrix symmetric, so the rank-1 row factor is row j itself
    (an exact sublane broadcast) while the column factor rides the
    roll-reduction tree."""
    i = pl.program_id(1)
    m = acc_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = a_ref[0].astype(acc_ref.dtype)
        wa_ref[0] = jnp.dot(
            w_ref[:].astype(acc_ref.dtype), a_ref[0].astype(acc_ref.dtype),
            preferred_element_type=acc_ref.dtype,
            precision=lax.Precision.HIGHEST).astype(wa_ref.dtype)

    rows = lax.broadcasted_iota(jnp.int32, (m, m), 0)
    cols = lax.broadcasted_iota(jnp.int32, (m, m), 1)
    base = i * bw

    def body(jj, carry):
        j = base + jj
        A = acc_ref[:]
        eqrow_f = (rows == j).astype(acc_ref.dtype)
        eqcol_f = (cols == j).astype(acc_ref.dtype)
        gtrow_f = (rows > j).astype(acc_ref.dtype)
        gtcol_f = (cols > j).astype(acc_ref.dtype)
        colj = jnp.where(cols == j, A, 0.0)
        s = 1
        while s < m:
            colj = colj + pltpu.roll(colj, s, 1)
            s *= 2
        ajj = jnp.sum(colj * eqrow_f * eqcol_f)
        ljj = jnp.sqrt(ajj)
        rowj_bc = jnp.broadcast_to(acc_ref[pl.ds(j, 1), :], (m, m))
        # symmetric trailing update (both triangles stay current so
        # future steps' rowj_bc reads are valid)
        A = A - (gtrow_f * gtcol_f) * (colj * rowj_bc) / ajj
        # scale column j below (and on) the diagonal into L values
        sel = eqcol_f * (gtrow_f + eqrow_f)
        A = A * (1.0 - sel) + (colj / ljj) * sel
        acc_ref[:] = A
        return carry

    jax.lax.fori_loop(0, bw, body, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _store():
        trilf = (rows >= cols).astype(acc_ref.dtype)
        o_ref[0] = (acc_ref[:] * trilf).astype(o_ref.dtype)


# --------------------------------------------------------------------------- #
# jitted pallas_call wrappers
# --------------------------------------------------------------------------- #


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_blu(A, w, interpret: bool):
    B, m, _ = A.shape
    bw = min(_PANEL_W, m)
    nsteps = m // bw
    acc_dt = jnp.promote_types(A.dtype, jnp.float32)
    kern = functools.partial(_blu_kernel, bw=bw)
    out, piv, wa = pl.pallas_call(
        kern,
        grid=(B, nsteps),
        in_specs=[
            pl.BlockSpec((1, m, m), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, m), lambda b, i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, m, m), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, m), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, m), lambda b, i: (b, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, m, m), A.dtype),
            jax.ShapeDtypeStruct((B, 1, m), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, m), acc_dt),
        ),
        scratch_shapes=[
            pltpu.VMEM((m, m), acc_dt),
            pltpu.VMEM((m, m), acc_dt),
        ],
        cost_estimate=pl.CostEstimate(
            flops=B * (2 * m * m * m // 3 + 2 * m * m),
            bytes_accessed=B * (2 * m * m + 2 * m) * A.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(A, w)
    # rows into LAPACK order: position k's row is the step-k pivot
    # winner (square elimination freezes every row exactly once)
    gpiv = piv[:, 0]
    LU = jnp.take_along_axis(out, gpiv[..., None], axis=1)
    return LU, gpiv, wa[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_bchol(A, w, interpret: bool):
    B, m, _ = A.shape
    bw = min(_PANEL_W, m)
    nsteps = m // bw
    acc_dt = jnp.promote_types(A.dtype, jnp.float32)
    kern = functools.partial(_bchol_kernel, bw=bw)
    L, wa = pl.pallas_call(
        kern,
        grid=(B, nsteps),
        in_specs=[
            pl.BlockSpec((1, m, m), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, m), lambda b, i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, m, m), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, m), lambda b, i: (b, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, m, m), A.dtype),
            jax.ShapeDtypeStruct((B, 1, m), acc_dt),
        ),
        scratch_shapes=[pltpu.VMEM((m, m), acc_dt)],
        cost_estimate=pl.CostEstimate(
            flops=B * (m * m * m // 3 + 2 * m * m),
            bytes_accessed=B * (2 * m * m + 2 * m) * A.dtype.itemsize,
            transcendentals=B * m,
        ),
        interpret=interpret,
    )(A, w)
    return L, wa[:, 0]


def _pad_batch_floor(Ap):
    """Floor the kernel batch at 2 with one identity pad slot. The
    bucket/pad contract wants per-slot bits invariant to the batch
    size; on TPU one Mosaic body serves every grid size, but in
    interpret mode a trip-count-1 grid loop gets INLINED by XLA and
    fuses differently from the retained loop at trip >= 2 (measured:
    low-bit drift at B=1 only, B in [2, 32] all bitwise identical).
    One wasted identity factor at bucket 1 buys the contract back."""
    if Ap.shape[0] >= 2:
        return Ap
    eye = jnp.eye(Ap.shape[-1], dtype=Ap.dtype)
    return jnp.concatenate([Ap, eye[None]])


def _probe_input(probe_w, n: int, m: int, acc_dt):
    """The (1, m) probe-row input: the caller's w zero-extended over the
    identity tail, or all-zero when no probe is wanted (the kernel's
    elimination program is identical either way — the probe is one dot
    at step 0 whose output the caller then drops)."""
    w = jnp.zeros((1, m), acc_dt)
    if probe_w is not None:
        w = w.at[0, :n].set(jnp.asarray(probe_w).astype(acc_dt))
    return w


def pallas_lu_factor_batched(A, *, probe_w=None):
    """Pivoted LU of a (B, N, N) batch through the batch-blocked Pallas
    kernel: returns ``(LU, perm)`` — packed factors in LAPACK order and
    the permutation, with ``A[i][perm[i]] == L_i @ U_i`` (the
    `lu_factor_blocked` contract per slot). With ``probe_w`` (length-N
    probe vector) also returns ``wA`` (B, N) = ``w^T A_i`` accumulated
    in-kernel at step 0 — the §21 Freivalds probe rows, free with the
    factor. Ragged N identity-pads to the next power of two and slices
    back bitwise (pad slots/rows never couple into real ones). Runs in
    interpret mode off-TPU; f64 is interpret-only."""
    A = jnp.asarray(A)
    _check_batched_square(A)
    B, n = A.shape[0], A.shape[-1]
    m = _pow2(n)
    acc_dt = jnp.promote_types(A.dtype, jnp.float32)
    Ap = _pad_batch_floor(_pad_identity(A, m))
    w = _probe_input(probe_w, n, m, acc_dt)
    interpret = _interpret(m)
    LU, perm, wa = _pallas_blu(Ap, w, interpret)
    LU, perm = LU[:B, :n, :n], perm[:B, :n]
    if probe_w is None:
        return LU, perm
    return LU, perm, wa[:B, :n]


def pallas_cholesky_factor_batched(A, *, probe_w=None):
    """Lower Cholesky factors of a (B, N, N) SPD batch through the
    batch-blocked Pallas kernel: returns L (B, N, N), strictly-upper
    parts zeroed (the `cholesky_blocked` contract per slot); with
    ``probe_w`` also the in-kernel probe rows wA (B, N). Ragged N
    identity-pads to the next power of two, bitwise. Interpret mode
    off-TPU; f64 interpret-only."""
    A = jnp.asarray(A)
    _check_batched_square(A)
    B, n = A.shape[0], A.shape[-1]
    m = _pow2(n)
    acc_dt = jnp.promote_types(A.dtype, jnp.float32)
    Ap = _pad_batch_floor(_pad_identity(A, m))
    w = _probe_input(probe_w, n, m, acc_dt)
    interpret = _interpret(m)
    L, wa = _pallas_bchol(Ap, w, interpret)
    L = L[:B, :n, :n]
    if probe_w is None:
        return L
    return L, wa[:B, :n]
