"""Blocked batched triangular solves — the vmapped substitution engine.

XLA's *batched* small-RHS TriangularSolve is the serving stack's oldest
measured cliff: it substitutes serially per row (~70x slower than GEMM
form at B=32, N=256 on CPU — DESIGN §17, re-measured in §26), which is
why every vmapped serving program was forced onto the ``'inv'``
substitution engine (explicit full triangular inverses, error growth ~
cond(L) cond(U)). The reference CONFLUX never pays that path either: its
communication-optimal flops come from *blocked* triangular updates whose
inner work is GEMM (`conflux_opt.hpp` trailing-matrix update).

This module is that cure, generalized to the batched layout (DESIGN
§27): split the triangular axis into ``bs``-wide blocks, invert ONLY the
(bs, bs) diagonal blocks (once, at factor time — O(N bs^2) work next to
the O(N^3) factorization, error growth ~ max cond of a diagonal block
instead of cond(L) cond(U)), and substitute block-by-block so each of
the O(N/bs) steps is one (bs, bs) GEMM against the diagonal inverse plus
one trailing-panel GEMM — all MXU/BLAS3-shaped, all trivially vmappable
over a batch/stack axis. N serial 1-column substitutions become
O(N/bs) batched GEMMs.

Two implementations share the contract:

- the portable pure-XLA path (:func:`blocked_solve` /
  :func:`blocked_trsm`) — an unrolled static block loop of jnp matmuls,
  safe inside jit/vmap, what the serve programs trace;
- a Pallas TPU kernel (:func:`pallas_blocked_trsm`) — grid over
  (batch, block step) with the running right-hand side held in a VMEM
  accumulator (the `_matmul_kernel` discipline from
  `pallas_kernels.py`), registered behind the `ops.blas` backend
  registry (``blas.blocked_trsm(..., backend='pallas')``) and running in
  interpret mode off-TPU so correctness tests cover it on CPU.

The final block step optionally fuses the §20/§21 Freivalds probe
epilogue (:func:`blocked_solve_probe`): as each solution block is
produced, the finite-check accumulator (sum of x) and the probe
projection (wA . x[:, 0]) accumulate in the same loop, so a checked
solve's verdict costs no separate pass over x after the substitution.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = lax.Precision.HIGHEST


def default_block_size(n: int) -> int:
    """The deterministic block width for an (n, n) triangle: 32, shrunk
    to the next power of two >= n for tiny systems. Derived from n ONLY
    — the diagonal-inverse stack's shape is part of a blocked plan's
    factor pytree, so it must be reproducible across processes
    (checkpoint/restore bitwise contract, DESIGN §23). 32 keeps the
    diagonal inverses well-conditioned (a (32, 32) triangle, not the
    whole factor), the per-step GEMMs MXU-tileable, and the step count
    N/32 small enough that XLA-CPU's fixed per-op overhead stays
    amortized (8 steps at the production N=256)."""
    if n < 1:
        raise ValueError(f"triangular solve needs n >= 1, got {n}")
    return min(32, 1 << (int(n) - 1).bit_length())


def _nblocks(n: int, bs: int) -> int:
    return -(-n // bs)


def _pad_identity(T, np_: int):
    """Extend an (..., n, n) triangle to (..., np_, np_) with an
    identity tail: the pad rows solve to exactly zero against a zero
    RHS pad and contribute nothing to real rows (their off-diagonal
    couplings are zero), so padded answers slice back bitwise."""
    n = T.shape[-1]
    if np_ == n:
        return T
    pad = [(0, 0)] * (T.ndim - 2) + [(0, np_ - n), (0, np_ - n)]
    Tp = jnp.pad(T, pad)
    idx = jnp.arange(n, np_)
    return Tp.at[..., idx, idx].set(jnp.ones((), T.dtype))


def diag_block_inverses(T, *, lower: bool = True,
                        unit_diagonal: bool = False,
                        block_size: int | None = None):
    """Invert the (bs, bs) diagonal blocks of an (n, n) triangle —
    the factor-time half of the blocked engine. Returns an
    (nb, bs, bs) stack of triangular inverses (nb = ceil(n / bs), the
    tail block identity-extended when bs does not divide n).

    `T` may be a PACKED factor (garbage on the other triangle — e.g.
    the U values a packed LU carries above L's diagonal): the block is
    masked to its triangle before inversion, and `unit_diagonal=True`
    rebuilds the implicit unit diagonal. One batched (nb, bs, bs)
    TriangularSolve against the identity — a bs-wide RHS, nowhere near
    the small-RHS cliff — runs at factor time and is amortized into
    the session open exactly like the 'inv' engine's full inverses,
    at 1/nb-th the inversion flops and far better conditioning.
    Traceable (jit/vmap-safe)."""
    n = T.shape[-1]
    bs = default_block_size(n) if block_size is None else int(block_size)
    nb = _nblocks(n, bs)
    Tp = _pad_identity(T, nb * bs)
    D = jnp.stack([Tp[i * bs:(i + 1) * bs, i * bs:(i + 1) * bs]
                   for i in range(nb)])
    if unit_diagonal:
        strict = jnp.tril(D, -1) if lower else jnp.triu(D, 1)
        D = strict + jnp.eye(bs, dtype=D.dtype)
    else:
        D = jnp.tril(D) if lower else jnp.triu(D)
    eye = jnp.broadcast_to(jnp.eye(bs, dtype=D.dtype), D.shape)
    return lax.linalg.triangular_solve(D, eye, left_side=True,
                                       lower=lower)


def _blocked_core(T, dinv, b, lower: bool, precision,
                  wA=None, stats_dtype=None):
    """The 2D block-substitution loop: solve T x = b through the
    precomputed diagonal-block inverses. Per step: one (bs, bs) x
    (bs, k) GEMM against the diagonal inverse, one trailing-panel GEMM
    updating the not-yet-solved rows. The loop is unrolled over a
    STATIC block count, so under vmap every step is a batched GEMM —
    the whole point. Off-triangle panels of a packed factor are never
    read (a lower solve reads strictly-below-diagonal panels only, an
    upper solve strictly-above), so packed LU storage needs no masking
    here. With `wA` (the probe row, length n) the Freivalds epilogue
    accumulates sum(x) and wA . x[:, 0] per block IN the loop — see
    :func:`blocked_solve_probe`."""
    n = T.shape[-1]
    nb, bs = dinv.shape[0], dinv.shape[-1]
    np_ = nb * bs
    if np_ != n:
        T = _pad_identity(T, np_)
        b = jnp.pad(b, ((0, np_ - n), (0, 0)))
    dt = jnp.result_type(T.dtype, b.dtype)

    def mm(a, x):
        return jnp.matmul(a.astype(dt), x.astype(dt),
                          precision=precision)

    probe = wA is not None
    if probe:
        sdt = dt if stats_dtype is None else jnp.dtype(stats_dtype)
        wAp = jnp.pad(wA.astype(sdt), (0, np_ - wA.shape[-1]))
        xsum = jnp.zeros((), sdt)
        wAx = jnp.zeros((), sdt)
    xs = []
    rest = b
    order = range(nb) if lower else range(nb - 1, -1, -1)
    for i in order:
        if lower:
            ri, rest = rest[:bs], rest[bs:]
        else:
            m = rest.shape[0]
            ri, rest = rest[m - bs:], rest[:m - bs]
        xi = mm(dinv[i], ri)
        xs.append(xi)
        if probe:
            xc = xi.astype(sdt)
            xsum = xsum + jnp.sum(xc)
            wAx = wAx + jnp.sum(wAp[i * bs:(i + 1) * bs] * xc[:, 0])
        if rest.shape[0]:
            if lower:
                panel = T[(i + 1) * bs:, i * bs:(i + 1) * bs]
            else:
                panel = T[:i * bs, i * bs:(i + 1) * bs]
            rest = rest - mm(panel, xi)
    x = jnp.concatenate(xs if lower else xs[::-1], axis=0)[:n]
    if probe:
        return x, xsum, wAx
    return x


def blocked_solve(T, dinv, b, *, lower: bool = True, precision=None):
    """Per-system blocked substitution with PRECOMPUTED diagonal-block
    inverses (`dinv` from :func:`diag_block_inverses`, resident in a
    blocked plan's factor pytree) — the serve hot path's primitive.
    T is (n, n) (packed factors fine), b is (n, k); traceable and
    vmap-safe (vmapping yields exactly the batched GEMM schedule)."""
    return _blocked_core(T, dinv, b, lower,
                         _HI if precision is None else precision)


def blocked_solve_probe(T, dinv, b, wA, *, lower: bool = False,
                        precision=None, stats_dtype=None):
    """:func:`blocked_solve` with the Freivalds probe epilogue fused
    into the block loop: returns (x, xsum, wAx) where xsum = sum(x)
    (the finite-check accumulator — NaN/Inf anywhere in x poisons it)
    and wAx = wA . x[:, 0] (the probe projection), both accumulated in
    `stats_dtype` as each block of x is produced. A checked blocked
    solve's verdict (`update.health_verdict_from_stats`) is assembled
    from these plus two O(N) dots on b — no separate pass re-reading x
    after the substitution (DESIGN §27). Defaults to the BACK solve
    (`lower=False`): the final block step of every factorization's
    substitution chain, where x is final."""
    return _blocked_core(T, dinv, b, lower,
                         _HI if precision is None else precision,
                         wA=wA, stats_dtype=stats_dtype)


def blocked_trsm(T, b, *, lower: bool = True,
                 unit_diagonal: bool = False, dinv=None,
                 block_size: int | None = None, precision=None,
                 backend: str | None = None):
    """Solve T x = b for a triangle or a batch of triangles — the
    public blocked-trsm entry (also surfaced as `blas.blocked_trsm`,
    behind the backend registry).

    T is (n, n) or (B, n, n); b matches with an optional trailing RHS
    axis ((n,), (n, k), (B, n), (B, n, k)); x comes back in b's shape.
    `dinv` passes precomputed diagonal-block inverses (per system, or
    stacked (B, nb, bs, bs) for batched input) — computed here when
    omitted. `backend='pallas'` routes BATCHED input through the Pallas
    kernel (interpret mode off-TPU); everything else takes the
    pure-XLA block loop, vmapped over the batch axis."""
    T = jnp.asarray(T)
    b = jnp.asarray(b)
    if T.ndim not in (2, 3) or T.shape[-1] != T.shape[-2]:
        raise ValueError(f"T must be (n, n) or (B, n, n), got {T.shape}")
    batched = T.ndim == 3
    squeeze = b.ndim == T.ndim - 1
    if squeeze:
        b = b[..., None]
    if b.ndim != T.ndim or b.shape[:-1] != T.shape[:-1]:
        raise ValueError(f"rhs {b.shape} does not match T {T.shape}")
    precision = _HI if precision is None else precision
    if backend is None:
        from conflux_tpu.ops import blas

        backend = blas.get_backend()

    def one_dinv(t):
        return diag_block_inverses(t, lower=lower,
                                   unit_diagonal=unit_diagonal,
                                   block_size=block_size)

    if dinv is None:
        dinv = jax.vmap(one_dinv)(T) if batched else one_dinv(T)
    else:
        dinv = jnp.asarray(dinv)
    if not batched:
        x = _blocked_core(T, dinv, b, lower, precision)
        return x[..., 0] if squeeze else x
    if backend == "pallas":
        x = pallas_blocked_trsm(T, dinv, b, lower=lower)
    else:
        x = jax.vmap(lambda t, d, r: _blocked_core(t, d, r, lower,
                                                   precision))(T, dinv, b)
    return x[..., 0] if squeeze else x


# --------------------------------------------------------------------------- #
# Pallas TPU kernel: one grid cell per batch element, VMEM accumulator
# --------------------------------------------------------------------------- #
#
# Grid (B,): each cell brings in its whole (np, np) triangle, its (nb, bs,
# bs) diagonal inverses and its (np, k) right-hand side, and runs the nb
# block steps as a STATIC loop against a VMEM scratch holding the running
# right-hand side (the `_matmul_kernel` accumulator discipline). Per step
# one (bs, k) x block comes off the MXU and the not-yet-solved rows are
# downdated with one (np, bs) panel GEMM — masked arithmetically (f32
# row-iota compare), never via i1 relayouts (the Mosaic constraint
# `_lu_block_kernel` documents). Whole-array blocks keep every block shape
# legal for Mosaic (a (np, bs) column-panel block is not: bs=32 is neither
# a lane multiple nor the array's width), and static panel slices need no
# dynamic lane offsets. The triangle must fit VMEM: `pallas_blocked_trsm`
# refuses larger ones on TPU.

_MAX_TPU_N = 1024  # largest padded n whose described-v5e compile fits VMEM


def _btrsm_kernel(t_ref, d_ref, b_ref, o_ref, acc_ref, *, nb: int,
                  bs: int, lower: bool):
    acc_ref[:] = b_ref[0].astype(acc_ref.dtype)
    rows = lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
    for j in (range(nb) if lower else range(nb - 1, -1, -1)):
        ri = acc_ref[j * bs:(j + 1) * bs, :]
        xi = jnp.dot(d_ref[0, j].astype(acc_ref.dtype), ri,
                     preferred_element_type=acc_ref.dtype, precision=_HI)
        o_ref[0, j * bs:(j + 1) * bs, :] = xi.astype(o_ref.dtype)
        # downdate rows not yet solved: below the block for a lower
        # solve, above it for an upper one; the masked rows also null
        # out the packed factor's other-triangle garbage in the panel
        upd = jnp.dot(t_ref[0, :, j * bs:(j + 1) * bs].astype(acc_ref.dtype),
                      xi, preferred_element_type=acc_ref.dtype,
                      precision=_HI)
        if lower:
            maskf = (rows >= (j + 1) * bs).astype(acc_ref.dtype)
        else:
            maskf = (rows < j * bs).astype(acc_ref.dtype)
        acc_ref[:] = acc_ref[:] - maskf * upd


@functools.partial(jax.jit,
                   static_argnames=("lower", "interpret"))
def _pallas_btrsm(T, dinv, b, lower: bool, interpret: bool):
    B, np_, _ = T.shape
    nb, bs = dinv.shape[1], dinv.shape[-1]
    k = b.shape[-1]
    acc_dtype = jnp.promote_types(T.dtype, jnp.float32)
    kern = functools.partial(_btrsm_kernel, nb=nb, bs=bs, lower=lower)
    return pl.pallas_call(
        kern,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, np_, np_), lambda bi: (bi, 0, 0)),
            pl.BlockSpec((1, nb, bs, bs), lambda bi: (bi, 0, 0, 0)),
            pl.BlockSpec((1, np_, k), lambda bi: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, np_, k), lambda bi: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, np_, k), b.dtype),
        scratch_shapes=[pltpu.VMEM((np_, k), acc_dtype)],
        cost_estimate=pl.CostEstimate(
            flops=B * (np_ * np_ * k + np_ * bs * k),
            bytes_accessed=B * (np_ * np_ + np_ * k * 2
                                + nb * bs * bs) * T.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(T, dinv, b)


def pallas_blocked_trsm(T, dinv, b, *, lower: bool = True):
    """Batched blocked trsm through the Pallas kernel: T (B, n, n)
    (packed factors fine), dinv (B, nb, bs, bs) from
    :func:`diag_block_inverses` per system, b (B, n, k). Runs in
    interpret mode off-TPU (the correctness-test path, same as the §7
    kernels); on TPU the accumulator lives in VMEM and both per-step
    GEMMs hit the MXU. Returns x (B, n, k)."""
    T = jnp.asarray(T)
    dinv = jnp.asarray(dinv)
    b = jnp.asarray(b)
    n = T.shape[-1]
    nb, bs = dinv.shape[1], dinv.shape[-1]
    np_ = nb * bs
    if np_ != n:
        T = _pad_identity(T, np_)
        b = jnp.pad(b, ((0, 0), (0, np_ - n), (0, 0)))
    interpret = jax.default_backend() != "tpu"
    if not interpret and np_ > _MAX_TPU_N:
        raise ValueError(
            f"pallas blocked trsm: a padded n={np_} triangle does not fit "
            f"VMEM on TPU (at most {_MAX_TPU_N}); use backend='xla'")
    x = _pallas_btrsm(T, dinv, b, lower, interpret)
    return x[:, :n]
