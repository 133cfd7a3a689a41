"""Incremental low-rank factor refresh: Sherman-Morrison-Woodbury solves.

PR 1 made repeated solves cheap (device-resident factors, one compiled
program per traffic shape), but any CHANGE to a served matrix still cost a
full O(N^3) refactorization. Serving traffic whose systems drift by a
rank-k correction between requests (streaming updates, a few changed
rows/columns, trust-region model tweaks) wants the Woodbury identity
instead: with A1 = A0 + U V^H (U, V of shape (N, k), k << N),

    A1^{-1} b = A0^{-1} b - A0^{-1} U (I_k + V^H A0^{-1} U)^{-1} V^H A0^{-1} b

so a refreshed solve is the BASE substitution (already compiled and
device-resident in the session) plus O(N k) extra GEMM work through the
k x k *capacitance* matrix C = I + V^H A0^{-1} U — an O(N^2 k) refresh +
O(N^2) solves where the refactor path pays O(N^3) per drift.

This module holds the traceable math (capacitance assembly, the corrected
apply, the one-shot functional solve) and the host-side
:class:`DriftPolicy` that decides when the correction has stopped paying
for itself and the session should pay for one true refactorization through
the existing `FactorPlan` factor program instead. The serving surface —
``SolveSession.update(U, V)`` / bucketed compiled programs / refactor
plumbing — lives in `conflux_tpu.serve`; one-shot entry points are
`solvers.solve_updated` and `batched.solve_updated_batched`.

When NOT to use the refresh path (also DESIGN.md §18): accumulated rank k
growing toward N (the correction costs O(N^2 k) per solve — past k ~ N/8
a refactor is cheaper and more accurate), and ill-conditioned capacitance
(cond(C) large means A0 + U V^H is near-singular *relative to the base
factors* and the correction amplifies rounding; the policy refactors on
both triggers, and `refine` backstop sweeps hold the residual in between).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
from jax import lax

from conflux_tpu.ops import blas

_HI = lax.Precision.HIGHEST


def rank_bucket(k: int) -> int:
    """Next power of two >= k: the compiled-program bucket for update rank
    (and RHS width — `serve` pads to the bucket and slices back), so a
    traffic mix of ranks/widths compiles O(log) programs, not O(distinct)."""
    if k < 1:
        raise ValueError(f"bucket needs a positive size, got {k}")
    return 1 << (int(k) - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class DriftPolicy:
    """When does the Woodbury correction stop paying for itself?

    max_rank: accumulated-rank cap; once total update rank exceeds it the
        session refactors (None -> max(8, N // 8): past ~N/8 the O(N^2 k)
        correction approaches the amortized O(N^3) refactor and accuracy
        degrades with every stacked correction).
    cond_limit: 1-norm condition cap on the k x k capacitance matrix; a
        large cond(C) means the drifted system is near-singular relative
        to the base factors and the correction amplifies rounding —
        refactor instead (non-finite estimates also trigger).
    refine: iterative-refinement backstop sweeps ADDED to the plan's own
        `refine` on updated solves only — the residual r = b - A1 x is
        computed against the *drifted* matrix (A0 x + U (V^H x)) and the
        correction rides the same Woodbury apply, the serve layer's
        existing refinement-loop discipline.
    """

    max_rank: int | None = None
    cond_limit: float = 1e6
    refine: int = 0

    def resolved_max_rank(self, n: int) -> int:
        if self.max_rank is not None:
            return int(self.max_rank)
        return max(8, n // 8)


def capacitance(base_apply, U, V):
    """Assemble the Woodbury correction state against the base factors.

    base_apply(r) must apply A0^{-1} (the session's substitution); U, V are
    (N, k) — zero-padded columns are harmless (they contribute an identity
    block to C, see below). Returns (Y, Cinv, cond1):

      Y    = A0^{-1} U                      (N, k)
      C    = I_k + V^H Y                    (k, k) capacitance
      Cinv = C^{-1}                         (k, k), dense — k is small, and
             an explicit inverse makes every later solve two GEMMs (the
             same trade as the serve layer's 'inv' substitution engine)
      cond1 = ||C||_1 ||C^{-1}||_1          the drift policy's trigger

    Traceable (jit/vmap-safe): the policy decision on cond1 happens on the
    host in the serve layer, not here.
    """
    Y = base_apply(U.astype(jnp.result_type(U.dtype, jnp.float32)))
    cdtype = Y.dtype
    Vc = V.astype(cdtype)
    k = U.shape[-1]
    C = jnp.eye(k, dtype=cdtype) + jnp.matmul(Vc.conj().T, Y, precision=_HI)
    Cinv = jnp.linalg.inv(C)
    norm1 = lambda M: jnp.max(jnp.sum(jnp.abs(M), axis=-2), axis=-1)
    cond1 = norm1(C) * norm1(Cinv)
    return Y, Cinv, cond1


def woodbury_apply(base_apply, Y, Cinv, V, b):
    """A1^{-1} b through the base factors + capacitance state:
    z - Y (Cinv (V^H z)) with z = A0^{-1} b. b is (N, nrhs)."""
    z = base_apply(b)
    Vc = V.astype(z.dtype)
    w = jnp.matmul(Vc.conj().T, z, precision=_HI)
    return z - jnp.matmul(Y.astype(z.dtype),
                          jnp.matmul(Cinv.astype(z.dtype), w, precision=_HI),
                          precision=_HI)


def updated_matvec(A0, U, V, x):
    """(A0 + U V^H) x without materializing the drifted matrix — the
    residual matvec of the refinement backstop, O(N^2 + N k) per column."""
    cdtype = x.dtype
    ax = jnp.matmul(A0.astype(cdtype), x, precision=_HI)
    w = jnp.matmul(V.astype(cdtype).conj().T, x, precision=_HI)
    return ax + jnp.matmul(U.astype(cdtype), w, precision=_HI)


def woodbury_solve(base_apply, A0, U, V, b, refine: int = 0):
    """One-shot functional form: solve (A0 + U V^H) x = b given a base
    substitution `base_apply` (r -> A0^{-1} r). `refine` sweeps compute the
    residual against the DRIFTED matrix and correct through the same
    Woodbury apply. A0 is only consumed when refine > 0 (pass None
    otherwise). Traceable; b is (N, nrhs)."""
    Y, Cinv, _ = capacitance(base_apply, U, V)
    x = woodbury_apply(base_apply, Y, Cinv, V, b)
    cdtype = x.dtype
    bc = b.astype(cdtype)
    for _ in range(refine):
        r = bc - updated_matvec(A0, U, V, x)
        x = x + woodbury_apply(base_apply, Y, Cinv, V, r).astype(cdtype)
    return x


def probe_vector(n: int):
    """The resilience layer's fixed Rademacher probe w (host numpy,
    float32 +-1, deterministic): E[(w . r)^2] = ||r||^2 exactly, so the
    projected residual below estimates the true one at the same relative
    scale. One fixed w per size keeps every checked program and every
    cached wA consistent."""
    import numpy as _np

    rng = _np.random.default_rng(0xC0FFEE)
    return rng.choice(_np.float32([-1.0, 1.0]), size=n)


def probe_row(w, A0):
    """wA = w^T A0 — the session-resident half of the Freivalds-style
    residual check, paid ONCE per base matrix (O(N^2), amortized like
    the factors; `SolveSession` caches it and invalidates on refactor).
    Traceable; per-system."""
    cdtype = blas.compute_dtype(A0.dtype)
    return jnp.matmul(w.astype(cdtype), A0.astype(cdtype), precision=_HI)


def probe_lstsq(w, A0):
    """(u, uA) — the least-squares analog of :func:`probe_row`, paid
    once per base matrix of a QR-backed session (`serve` kind='qr').

    A square session's Freivalds check projects the residual b - A x
    through a fixed Rademacher w. For min||Ax - b|| that residual is
    NOT small — it is the orthogonal complement of b — so the probe
    must live in range(A0) instead: u = A0 w (normalized to the
    Rademacher scale ||u|| = sqrt(M)) and uA = u^T A0. At the true LS
    solution the residual is orthogonal to range(A0), so
    u . (b - A0 x) = u . b - uA . x vanishes, and
    :func:`health_spot_check` works VERBATIM with (u, uA) in the
    (w, wA) slots — same formula, same (2,) verdict, same escalation
    plumbing. Systemic garbage (corrupt R, a non-orthogonal Q) shows
    up as an O(1) relative error in uA . x. Traceable; per-system."""
    cdtype = blas.compute_dtype(A0.dtype)
    u = jnp.matmul(A0.astype(cdtype), w.astype(cdtype), precision=_HI)
    m = A0.shape[-2]
    scale = jnp.sqrt(jnp.asarray(float(m), cdtype))
    u = u * (scale / (jnp.sqrt(jnp.sum(jnp.abs(u) ** 2))
                      + jnp.finfo(cdtype).tiny))
    uA = jnp.matmul(u, A0.astype(cdtype), precision=_HI)
    return u, uA


def health_spot_check(w, wA, x, b, Up=None, Vp=None):
    """Fused finite/projected-residual health verdict for one solve —
    the resilience layer's output guard (`conflux_tpu.resilience`),
    fused into the checked solve programs so the clean path pays no
    extra dispatch. Returns a (2,) float32 verdict
    [finite_flag, residual]:

      finite_flag — 1.0 iff EVERY element of x is finite. RHS columns
          are independent through the substitution, so a NaN/Inf column
          corrupts only its own answer column: the all-element finite
          check IS the per-column guard.
      residual    — |w . (b0 - A x0)| / ||b0|| on column 0, computed
          Freivalds-style through the precomputed probe row wA = w^T A0
          (:func:`probe_row`): w.b0 - wA.x0 costs two O(N) dots where
          the true residual matvec costs O(N^2) — which is comparable to
          the solve itself, and a <5% clean-path overhead budget
          forbids that (XLA CPU also runs skinny
          batched matvecs far off peak, the §17 trsm lesson). With the
          Rademacher w the projection estimates ||r||/||b|| at the same
          relative scale; systemic garbage (factor corruption, an
          ill-conditioned SMW correction) is an O(1) relative error in
          essentially every component, so it cannot hide from the
          projection except on a measure-zero set. A tripwire for
          catastrophic failures, not an accuracy certificate — `refine`
          sweeps are the accuracy tool.

    Up/Vp (the session's padded drift factors) extend the projection to
    the DRIFTED matrix: w^T A1 = wA + (w^T Up) Vp^H, two more O(N k)
    dots; zero-padded columns are inert.

    Batch-generic and deliberately op-lean: XLA CPU charges microseconds
    of fixed overhead PER OP next to tiny dispatches, so the verdict is
    built from a handful of batched reductions on the whole (B, N, w)
    block — never per-vmap-lane — and the finite flag rides one
    summation (NaN/Inf poisons the accumulator; an overflow false
    positive merely triggers one escalation whose exact re-check then
    passes). Traceable; call OUTSIDE any vmap."""
    cdtype = x[..., 0].dtype
    finite = jnp.isfinite(jnp.sum(x))
    x0 = x[..., 0].astype(cdtype)                       # (..., N)
    b0 = b[..., 0].astype(cdtype)
    wc = w.astype(cdtype)
    ax = jnp.sum(wA.astype(cdtype) * x0, axis=-1)       # (...,)
    if Up is not None:
        wU = jnp.sum(wc[:, None] * Up.astype(cdtype), axis=-2)
        vx = jnp.sum(Vp.astype(cdtype).conj()
                     * x0[..., :, None], axis=-2)       # (..., k)
        ax = ax + jnp.sum(wU * vx, axis=-1)
    num = jnp.abs(jnp.sum(wc * b0, axis=-1) - ax)
    den = (jnp.sqrt(jnp.sum(jnp.abs(b0) ** 2, axis=-1))
           + jnp.finfo(cdtype).tiny)
    return jnp.stack([finite.astype(jnp.float32),
                      jnp.max(num / den).astype(jnp.float32)])


def health_spot_check_slots(w, wA, x, b, Up=None, Vp=None):
    """Per-slot fused health verdict for a STACKED (gang) solve — the
    cross-session analog of :func:`health_spot_check`, returning a
    (2, S) float32 block instead of a (2,) scalar pair: row 0 the
    per-slot finite flags, row 1 the per-slot projected residuals.
    Slot i's verdict depends only on slot i's factors/RHS (the vmapped
    solve never mixes slots), so one sick session can never contaminate
    its gang-mates' evidence — the same blast-radius-isolation shape as
    the factor lane's per-slot verdict (`FactorPlan._factor_health_fn`),
    read host-side by the same `resilience.evaluate_slots`.

    x is (S, N, w), wA is (S, N), b is (S, N, w); Up/Vp (S, N, kb)
    extend the projection to each slot's drifted matrix (zero-padded
    columns inert — a clean slot carries zero U/V). Idle gang slots
    (zero RHS columns) evaluate finite with residual 0. Deliberately
    op-lean: a handful of batched reductions OUTSIDE the vmap (the
    XLA-CPU fixed-op-cost rule, §20) — per-slot sums, never
    per-element ops. Traceable; single-system plans only."""
    cdtype = x[..., 0].dtype
    xs = jnp.sum(x, axis=tuple(range(1, x.ndim)))            # (S,)
    finite = jnp.isfinite(xs)
    x0 = x[..., 0].astype(cdtype)                            # (S, N)
    b0 = b[..., 0].astype(cdtype)
    wc = w.astype(cdtype)
    ax = jnp.sum(wA.astype(cdtype) * x0, axis=-1)            # (S,)
    if Up is not None:
        wU = jnp.sum(wc[None, :, None] * Up.astype(cdtype),
                     axis=-2)                                # (S, kb)
        vx = jnp.sum(Vp.astype(cdtype).conj()
                     * x0[..., :, None], axis=-2)            # (S, kb)
        ax = ax + jnp.sum(wU * vx, axis=-1)
    num = jnp.abs(jnp.sum(wc * b0, axis=-1) - ax)
    den = (jnp.sqrt(jnp.sum(jnp.abs(b0) ** 2, axis=-1))
           + jnp.finfo(cdtype).tiny)
    return jnp.stack([finite.astype(jnp.float32),
                      (num / den).astype(jnp.float32)])


def health_verdict_from_stats(w, xsum, wAx, b):
    """Assemble the :func:`health_spot_check` verdict from IN-LOOP
    accumulators instead of a pass over x — the blocked substitution
    engine's fused probe epilogue (DESIGN §27): `xsum` is sum(x) (the
    finite accumulator) and `wAx` is wA . x[:, 0], both accumulated per
    block inside `ops.batched_trsm.blocked_solve_probe`'s final solve,
    so the verdict here costs only the two O(N) b-side dots. Leading
    batch axes of xsum/wAx/b (batched plans) max-reduce like the
    unfused check. Returns the same (2,) float32
    [finite_flag, residual] verdict; traceable, call OUTSIDE vmap."""
    cdtype = wAx.dtype
    finite = jnp.isfinite(jnp.sum(xsum))
    b0 = b[..., 0].astype(cdtype)
    wc = w.astype(cdtype)
    num = jnp.abs(jnp.sum(wc * b0, axis=-1) - wAx)
    den = (jnp.sqrt(jnp.sum(jnp.abs(b0) ** 2, axis=-1))
           + jnp.finfo(cdtype).tiny)
    return jnp.stack([finite.astype(jnp.float32),
                      jnp.max(num / den).astype(jnp.float32)])


def health_verdict_from_stats_slots(w, xsum, wAx, b):
    """Per-slot fused verdict from in-loop accumulators — the stacked
    (gang) analog of :func:`health_verdict_from_stats`, mirroring
    :func:`health_spot_check_slots`'s (2, S) contract: xsum/wAx are
    (S,) per-slot accumulators out of the vmapped blocked probe solve,
    b is (S, N, w). Slot i's verdict still depends only on slot i's
    accumulators and RHS (blast-radius isolation); idle pad slots
    (zero RHS) evaluate finite with residual 0. Traceable."""
    cdtype = wAx.dtype
    finite = jnp.isfinite(xsum)
    b0 = b[..., 0].astype(cdtype)
    wc = w.astype(cdtype)
    num = jnp.abs(jnp.sum(wc * b0, axis=-1) - wAx)
    den = (jnp.sqrt(jnp.sum(jnp.abs(b0) ** 2, axis=-1))
           + jnp.finfo(cdtype).tiny)
    return jnp.stack([finite.astype(jnp.float32),
                      (num / den).astype(jnp.float32)])


def pad_update_state(Up, Vp, Y, Cinv, kb: int):
    """Zero-pad one session's Woodbury state from its own rank bucket
    k0 = Up.shape[-1] up to the gang bucket `kb` — what lets sessions
    at DIFFERENT drift ranks share one stacked rank-bucketed Woodbury
    dispatch. U/V/Y gain zero columns (inert: a zero column contributes
    nothing to V^H z or to Y @ (...)); Cinv extends block-diagonally
    with the identity — exactly the capacitance :func:`capacitance`
    would have produced from the zero-padded U/V (C = I + V^H Y is
    block-diag [C_k0, I], so its inverse is [Cinv_k0, I]), built here
    by construction instead of re-inverting. The padded slot's
    correction therefore equals the unpadded one up to reduction
    order (allclose, the gang contract for drifted slots)."""
    k0 = Up.shape[-1]
    if k0 == kb:
        return Up, Vp, Y, Cinv
    if k0 > kb:
        raise ValueError(f"cannot pad rank {k0} down to bucket {kb}")
    pad = [(0, 0)] * (Up.ndim - 1) + [(0, kb - k0)]
    Up2 = jnp.pad(Up, pad)
    Vp2 = jnp.pad(Vp, pad)
    Y2 = jnp.pad(Y, pad)
    C2 = jnp.eye(kb, dtype=Cinv.dtype).at[:k0, :k0].set(Cinv)
    return Up2, Vp2, Y2, C2


def zero_update_state(n: int, kb: int, dtype, factor_dtype=None):
    """The Woodbury state of an UNdrifted gang slot at rank bucket kb:
    zero U/V/Y and an identity capacitance inverse. Riding the stacked
    Woodbury program with this state reproduces the plain substitution
    (the correction term is exactly zero — Y is the zero matrix), so a
    mixed clean/drifted gang dispatches ONE program. Y/Cinv take the
    compute dtype of `factor_dtype` (default `dtype`) — the dtype a
    real :func:`capacitance` output carries, so a prewarmed program
    signature matches live drift traffic."""
    cdtype = blas.compute_dtype(jnp.dtype(factor_dtype or dtype))
    z = jnp.zeros((n, kb), jnp.dtype(dtype))
    return z, z, jnp.zeros((n, kb), cdtype), jnp.eye(kb, dtype=cdtype)


def apply_update(A0, U, V):
    """Materialize the drifted matrix A0 + U V^H in A0's dtype — the
    refactor path's input (and the bench's full-refactor oracle).
    Batch-safe: leading axes of A0/U/V broadcast through the matmul."""
    cdtype = blas.compute_dtype(A0.dtype)
    Vh = jnp.swapaxes(V.astype(cdtype).conj(), -1, -2)
    return (A0.astype(cdtype)
            + jnp.matmul(U.astype(cdtype), Vh,
                         precision=_HI)).astype(A0.dtype)
