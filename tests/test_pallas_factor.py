"""Batched Pallas factor kernels: the ISSUE 14 contracts (DESIGN §29).

- `ops.pallas_factor.pallas_lu_factor_batched` elects the SAME pivot
  permutation as `lax.linalg.lu` and reconstructs A[perm] = L @ U across
  dtypes (f32/f64, f64 interpret-only) and shapes (N in {8, 48, 64,
  256} x B in {1, 4, 32}) — N=48 exercises the power-of-two identity
  tail; the Cholesky kernel reconstructs L @ L^T = A on SPD batches.
- Identity slots factor to EXACT bits (LU == I, perm == arange,
  L == I) — what makes identity pad slots free.
- Per-slot kernel outputs are bitwise invariant to the kernel batch
  size and to the pad contents (grid slots never interact), and the
  fused probe row (`probe_w=`) is bit-neutral to the factors.
- The `ops.blas` registry entries resolve `backend=` (XLA vmapped
  `lax.linalg.lu` / `lax.linalg.cholesky` default, kernel on 'pallas')
  and `batched.lu_factor_batched` / `cholesky_factor_batched` route
  eligible calls (mesh-less, f32/f64) to the kernel.
- Serve wiring: a `backend='pallas'` plan's stacked factor programs
  keep the §21 bucket/pad bitwise-invariance contract, `plan.factor`
  matches the CHECKED coalesced program bitwise, the fused Dinv blocks
  equal a second `diag_block_inverses` pass over the kernel's LU, the
  in-kernel Freivalds verdict agrees with the XLA-backend
  `_factor_health_fn` (healthy AND forced-unhealthy slots), a poisoned
  slot trips alone with its neighbors' factors bitwise untouched, and
  steady-state bucket calls re-trace NOTHING. Ineligible keys
  (factor_dtype != dtype) fall back to the vmapped XLA body.
- Engine end-to-end: coalesced cold starts on a pallas plan solve
  bitwise identically to `plan.factor` sessions.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from conflux_tpu import profiler, serve
from conflux_tpu.batched import cholesky_factor_batched, lu_factor_batched
from conflux_tpu.engine import ServeEngine
from conflux_tpu.ops import blas
from conflux_tpu.ops import pallas_factor as pf
from conflux_tpu.ops.batched_trsm import diag_block_inverses
from conflux_tpu.resilience import HealthPolicy


def _gen(rng, b, n, dtype):
    return (rng.standard_normal((b, n, n)) / np.sqrt(n)
            + 2.0 * np.eye(n)).astype(dtype)


def _spd(rng, b, n, dtype):
    G = rng.standard_normal((b, n, n))
    return (G @ np.swapaxes(G, -1, -2) / n
            + 2.0 * np.eye(n)).astype(dtype)


def _unpack(LU):
    n = LU.shape[-1]
    L = np.tril(LU, -1) + np.eye(n, dtype=LU.dtype)
    return L, np.triu(LU)


# --------------------------------------------------------------------- #
# the kernels vs the LAPACK oracles
# --------------------------------------------------------------------- #

_GRID = [
    (np.float32, 8, 1), (np.float32, 8, 4), (np.float32, 8, 32),
    (np.float32, 48, 1), (np.float32, 48, 4), (np.float32, 48, 32),
    (np.float32, 64, 1), (np.float32, 64, 4), (np.float32, 64, 32),
    (np.float32, 256, 1),
    (np.float64, 8, 4), (np.float64, 48, 1), (np.float64, 64, 32),
]
# N=256 interpret-mode cells run ~13 s each — slow lane
_GRID_SLOW = [(np.float32, 256, 4), (np.float32, 256, 32),
              (np.float64, 256, 1)]


def _check_lu_cell(dtype, n, b):
    rng = np.random.default_rng(7 * n + b)
    A = _gen(rng, b, n, dtype)
    LU, perm = pf.pallas_lu_factor_batched(A)
    assert LU.dtype == jnp.dtype(dtype) and perm.shape == (b, n)
    # same pivot elections as the oracle (no ties on gaussian data)
    _lu, _piv, operm = jax.vmap(lax.linalg.lu)(jnp.asarray(A))
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(operm))
    # reconstruction: A[perm] = L @ U per slot (accumulated in f64)
    tol = 5e-4 if dtype == np.float32 else 1e-10
    LUn = np.asarray(LU, np.float64)
    pn = np.asarray(perm)
    for i in range(b):
        L, U = _unpack(LUn[i])
        np.testing.assert_allclose(L @ U, A[i][pn[i]].astype(np.float64),
                                   atol=tol, err_msg=f"slot {i}")


def _check_chol_cell(dtype, n, b):
    rng = np.random.default_rng(11 * n + b)
    A = _spd(rng, b, n, dtype)
    L = pf.pallas_cholesky_factor_batched(A)
    Ln = np.asarray(L, np.float64)
    # strictly-upper parts are literal zeros (the contract downstream
    # blocked substitution relies on)
    assert (np.triu(Ln, 1) == 0.0).all()
    tol = 5e-4 if dtype == np.float32 else 1e-10
    np.testing.assert_allclose(Ln @ np.swapaxes(Ln, -1, -2),
                               A.astype(np.float64), atol=tol)
    ref = lax.linalg.cholesky(jnp.asarray(A), symmetrize_input=False)
    np.testing.assert_allclose(Ln, np.asarray(ref, np.float64), atol=tol)


@pytest.mark.parametrize("dtype,n,b", _GRID)
def test_lu_kernel_matches_oracle(dtype, n, b):
    _check_lu_cell(dtype, n, b)


@pytest.mark.slow
@pytest.mark.parametrize("dtype,n,b", _GRID_SLOW)
def test_lu_kernel_matches_oracle_slow(dtype, n, b):
    _check_lu_cell(dtype, n, b)


@pytest.mark.parametrize("dtype,n,b", [
    (np.float32, 8, 4), (np.float32, 48, 4), (np.float32, 64, 32),
    (np.float32, 256, 1), (np.float64, 64, 4)])
def test_cholesky_kernel_matches_oracle(dtype, n, b):
    _check_chol_cell(dtype, n, b)


@pytest.mark.slow
@pytest.mark.parametrize("dtype,n,b", [(np.float32, 256, 32)])
def test_cholesky_kernel_matches_oracle_slow(dtype, n, b):
    _check_chol_cell(dtype, n, b)


def test_identity_slots_factor_to_exact_bits():
    """Identity matrices factor with NO rounding: LU == I and
    perm == arange bitwise (likewise L == I for Cholesky) — the
    property that makes identity pad slots free in the factor lane."""
    rng = np.random.default_rng(3)
    eye = np.eye(64, dtype=np.float32)
    A = np.stack([_gen(rng, 1, 64, np.float32)[0], eye])
    LU, perm = pf.pallas_lu_factor_batched(A)
    np.testing.assert_array_equal(np.asarray(LU)[1], eye)
    np.testing.assert_array_equal(np.asarray(perm)[1], np.arange(64))
    L = pf.pallas_cholesky_factor_batched(A[1:])
    np.testing.assert_array_equal(np.asarray(L)[0], eye)


def test_kernel_bucket_and_pad_bitwise_invariance():
    """Slot i's outputs are bitwise invariant to the kernel batch size
    (B=1 rides the batch-floor pad) and to the other slots' contents —
    grid slots never interact."""
    rng = np.random.default_rng(29)
    A = _gen(rng, 4, 48, np.float32)
    junk = 1e3 * rng.standard_normal((3, 48, 48)).astype(np.float32)
    LU1, p1 = pf.pallas_lu_factor_batched(A[:1])
    LU4, p4 = pf.pallas_lu_factor_batched(A)
    LUj, pj = pf.pallas_lu_factor_batched(
        np.concatenate([A[:1], junk]))
    np.testing.assert_array_equal(np.asarray(LU1)[0], np.asarray(LU4)[0])
    np.testing.assert_array_equal(np.asarray(p1)[0], np.asarray(p4)[0])
    np.testing.assert_array_equal(np.asarray(LU1)[0], np.asarray(LUj)[0])
    np.testing.assert_array_equal(np.asarray(p1)[0], np.asarray(pj)[0])
    S = _spd(rng, 4, 48, np.float32)
    L1 = pf.pallas_cholesky_factor_batched(S[:1])
    L4 = pf.pallas_cholesky_factor_batched(S)
    np.testing.assert_array_equal(np.asarray(L1)[0], np.asarray(L4)[0])


def test_probe_row_is_bit_neutral_and_correct():
    """`probe_w=` adds the step-0 wA dot WITHOUT touching the
    elimination: factors/pivots keep their exact bits, and wA equals
    w^T A to accumulator precision."""
    rng = np.random.default_rng(31)
    A = _gen(rng, 4, 48, np.float32)
    w = np.sign(rng.standard_normal(48)).astype(np.float32)
    LU0, p0 = pf.pallas_lu_factor_batched(A)
    LU1, p1, wa = pf.pallas_lu_factor_batched(A, probe_w=w)
    np.testing.assert_array_equal(np.asarray(LU0), np.asarray(LU1))
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    np.testing.assert_allclose(np.asarray(wa, np.float64),
                               w.astype(np.float64) @ A.astype(np.float64),
                               rtol=1e-4, atol=1e-4)
    S = _spd(rng, 2, 48, np.float32)
    L0 = pf.pallas_cholesky_factor_batched(S)
    L1, wa = pf.pallas_cholesky_factor_batched(S, probe_w=w)
    np.testing.assert_array_equal(np.asarray(L0), np.asarray(L1))
    np.testing.assert_allclose(np.asarray(wa, np.float64),
                               w.astype(np.float64) @ S.astype(np.float64),
                               rtol=1e-4, atol=1e-4)


def test_kernel_rejects_bad_shapes():
    with pytest.raises(ValueError, match="batched factor"):
        pf.pallas_lu_factor_batched(np.eye(8, dtype=np.float32))
    with pytest.raises(ValueError, match="batched factor"):
        pf.pallas_cholesky_factor_batched(
            np.zeros((2, 8, 4), np.float32))


# --------------------------------------------------------------------- #
# registry + batched entry-point routing
# --------------------------------------------------------------------- #


def test_blas_registry_resolves_backend():
    """`blas.batched_lu_factor` / `batched_cholesky_factor` honor
    `backend=`: the XLA default is the vmapped LAPACK oracle verbatim,
    and 'pallas' lands on the kernel with the same pivots."""
    rng = np.random.default_rng(37)
    A = _gen(rng, 4, 64, np.float32)
    LUx, px = blas.batched_lu_factor(A)  # module backend (xla)
    olu, _p, op = jax.vmap(lax.linalg.lu)(jnp.asarray(A))
    np.testing.assert_array_equal(np.asarray(LUx), np.asarray(olu))
    np.testing.assert_array_equal(np.asarray(px), np.asarray(op))
    LUp, pp = blas.batched_lu_factor(A, backend="pallas")
    np.testing.assert_array_equal(np.asarray(pp), np.asarray(px))
    np.testing.assert_allclose(np.asarray(LUp), np.asarray(LUx),
                               rtol=1e-4, atol=1e-5)
    # probe rows ride both backends
    w = np.sign(rng.standard_normal(64)).astype(np.float32)
    *_x, wax = blas.batched_lu_factor(A, probe_w=w)
    *_p, wap = blas.batched_lu_factor(A, probe_w=w, backend="pallas")
    np.testing.assert_allclose(np.asarray(wax), np.asarray(wap),
                               rtol=1e-4, atol=1e-4)
    S = _spd(rng, 2, 64, np.float32)
    Lx = blas.batched_cholesky_factor(S)
    Lp = blas.batched_cholesky_factor(S, backend="pallas")
    np.testing.assert_allclose(np.asarray(Lp), np.asarray(Lx),
                               rtol=1e-4, atol=1e-5)


def test_ops_exports_registry_entries():
    import conflux_tpu.ops as ops

    assert ops.batched_lu_factor is blas.batched_lu_factor
    assert ops.batched_cholesky_factor is blas.batched_cholesky_factor


def test_batched_entry_points_route_to_kernel():
    """`lu_factor_batched(..., backend='pallas')` (mesh-less, f32) is
    the kernel bitwise; the XLA route still answers and the tile-size
    guard still fires."""
    rng = np.random.default_rng(41)
    A = _gen(rng, 3, 64, np.float32)
    LU, perm = lu_factor_batched(A, 16, backend="pallas")
    kLU, kperm = pf.pallas_lu_factor_batched(A)
    np.testing.assert_array_equal(np.asarray(LU), np.asarray(kLU))
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(kperm))
    LUx, permx = lu_factor_batched(A, 16)
    np.testing.assert_allclose(np.asarray(LU), np.asarray(LUx),
                               rtol=1e-4, atol=1e-5)
    S = _spd(rng, 2, 64, np.float32)
    L = cholesky_factor_batched(S, 16, backend="pallas")
    np.testing.assert_array_equal(
        np.asarray(L), np.asarray(pf.pallas_cholesky_factor_batched(S)))
    with pytest.raises(ValueError, match="tile size"):
        lu_factor_batched(A, 48, backend="pallas")


# --------------------------------------------------------------------- #
# serve wiring: the pallas factor lane
# --------------------------------------------------------------------- #

N, V = 64, 16


def _plans(spd=False):
    serve.clear_plans()
    pall = serve.FactorPlan.create((N, N), jnp.float32, v=V, spd=spd,
                                   backend="pallas")
    xla = serve.FactorPlan.create((N, N), jnp.float32, v=V, spd=spd)
    assert pall._pallas_factor and not xla._pallas_factor
    return pall, xla


def test_pallas_plan_bucket_and_pad_bitwise_invariance():
    """The §21 lane contract on a pallas plan: slot i's factor pytree is
    bitwise identical across stack buckets and pad contents (the kernel
    dispatches standalone — never fused into a bucket-shaped jit — so
    the interpret-mode graph can't re-fuse per bucket)."""
    pall, _ = _plans()
    rng = np.random.default_rng(43)
    A = _gen(rng, 4, N, np.float32)
    F1 = pall._stacked_factor_fn(1)(jnp.asarray(A[:1]))
    F4 = pall._stacked_factor_fn(4)(jnp.asarray(A))
    for l1, l4 in zip(F1, F4):
        np.testing.assert_array_equal(np.asarray(l1)[0], np.asarray(l4)[0])
    Apad = np.stack([A[0], np.eye(N, dtype=np.float32)])
    F2 = pall._stacked_factor_fn(2)(jnp.asarray(Apad))
    for l1, l2 in zip(F1, F2):
        np.testing.assert_array_equal(np.asarray(l1)[0], np.asarray(l2)[0])
    with pytest.raises(AssertionError, match="power-of-two"):
        # conflint: disable=CFX-RECOMPILE asserting the bucket contract rejects 3
        pall._stacked_factor_fn(3)


@pytest.mark.parametrize("spd", [False, True], ids=["lu", "chol"])
def test_plan_factor_matches_checked_coalesced_bitwise(spd):
    """`plan.factor` (bucket 1) and the CHECKED coalesced program emit
    the same factor bits — the fused verdict changes the program, not
    the factors — and the verdict reads healthy."""
    pall, _ = _plans(spd=spd)
    rng = np.random.default_rng(47)
    A = (_spd if spd else _gen)(rng, 4, N, np.float32)
    s = pall.factor(jnp.asarray(A[0]))
    F, wA, verdict = pall._factor_health_fn(4)(jnp.asarray(A))
    for got, ref in zip(F, s._factors):
        np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(ref))
    v = np.asarray(verdict)
    assert v.shape == (2, 4)
    assert (v[0] == 1.0).all() and (v[1] < 1e-3).all()
    # the in-kernel probe rows are the sessions' probe rows
    np.testing.assert_allclose(
        np.asarray(wA)[0], np.asarray(s._probe_row()),
        rtol=1e-4, atol=1e-4)
    # and the sessions solve to residual
    b = rng.standard_normal((N, 2)).astype(np.float32)
    x = np.asarray(s.solve(jnp.asarray(b)))
    assert np.abs(A[0] @ x - b).max() < 1e-3


def test_fused_dinv_matches_second_pass():
    """The epilogue-fused `substitution='blocked'` diagonal-block
    inverses equal a separate `diag_block_inverses` pass over the SAME
    kernel LU — fusion moved the op, not the math."""
    pall, _ = _plans()
    assert pall.key.substitution == "blocked"
    rng = np.random.default_rng(53)
    A = _gen(rng, 2, N, np.float32)
    LU, Dl, Du, _perm = pall._stacked_factor_fn(2)(jnp.asarray(A))
    rDl = jax.vmap(lambda t: diag_block_inverses(
        t, lower=True, unit_diagonal=True))(LU)
    rDu = jax.vmap(lambda t: diag_block_inverses(t, lower=False))(LU)
    np.testing.assert_allclose(np.asarray(Dl), np.asarray(rDl),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(Du), np.asarray(rDu),
                               rtol=1e-6, atol=1e-7)


def test_fused_verdict_agrees_with_xla_health_path():
    """The in-kernel Freivalds verdict and the XLA-backend
    `_factor_health_fn` agree on the same traffic: all-healthy on clean
    systems, and a forced-unhealthy (singular) slot trips BOTH paths in
    the same slot while its neighbors stay healthy."""
    pall, xla = _plans()
    rng = np.random.default_rng(59)
    A = _gen(rng, 4, N, np.float32)
    limit = HealthPolicy().resolved_residual_limit(np.float32, N)
    vp = np.asarray(pall._factor_health_fn(4)(jnp.asarray(A))[2])
    vx = np.asarray(xla._factor_health_fn(4)(jnp.asarray(A))[2])
    np.testing.assert_array_equal(vp[0], vx[0])
    assert (vp[1] < limit).all() and (vx[1] < limit).all()
    # forced-unhealthy: a zero column makes slot 2 exactly singular
    bad = A.copy()
    bad[2, :, 5] = 0.0
    vp = np.asarray(pall._factor_health_fn(4)(jnp.asarray(bad))[2])
    vx = np.asarray(xla._factor_health_fn(4)(jnp.asarray(bad))[2])
    for v in (vp, vx):
        healthy = (v[0] >= 0.5) & (v[1] <= limit)
        assert not healthy[2]
        assert healthy[[0, 1, 3]].all()


def test_poisoned_slot_trips_alone_neighbors_bitwise():
    """A NaN-poisoned slot fails its OWN verdict; co-batched slots keep
    their exact clean-run factor bits (grid-level blast isolation)."""
    pall, _ = _plans()
    rng = np.random.default_rng(61)
    A = _gen(rng, 4, N, np.float32)
    Fc, _wc, vc = pall._factor_health_fn(4)(jnp.asarray(A))
    bad = A.copy()
    bad[1] = np.nan
    Fp, _wp, vp = pall._factor_health_fn(4)(jnp.asarray(bad))
    vc, vp = np.asarray(vc), np.asarray(vp)
    assert vp[0, 1] == 0.0
    assert (vp[0, [0, 2, 3]] == 1.0).all()
    assert (vc[0] == 1.0).all()
    for lc, lp in zip(Fc, Fp):
        np.testing.assert_array_equal(np.asarray(lc)[[0, 2, 3]],
                                      np.asarray(lp)[[0, 2, 3]])


def test_batched_pallas_plan_folds_stack_into_kernel_batch():
    """A batched (B, N, N) pallas plan folds (bb, B) into one kernel
    batch and unflattens back: sessions solve to residual and the
    checked program's per-slot verdict max-reduces over the plan's own
    batch axis."""
    serve.clear_plans()
    Bp = 4
    plan = serve.FactorPlan.create((Bp, N, N), jnp.float32, v=V,
                                   backend="pallas")
    assert plan._pallas_factor and plan.batched
    rng = np.random.default_rng(67)
    A = _gen(rng, Bp, N, np.float32)
    s = plan.factor(jnp.asarray(A))
    b = rng.standard_normal((Bp, N)).astype(np.float32)
    x = np.asarray(s.solve(jnp.asarray(b)))
    assert np.abs(np.einsum("bij,bj->bi", A, x) - b).max() < 1e-3
    Ast = np.stack([A, _gen(rng, Bp, N, np.float32)])
    F, wA, verdict = plan._factor_health_fn(2)(jnp.asarray(Ast))
    assert np.asarray(wA).shape == (2, Bp, N)
    v = np.asarray(verdict)
    assert v.shape == (2, 2)
    assert (v[0] == 1.0).all() and (v[1] < 1e-3).all()
    # slot 0 of the stack is the plan.factor session, bitwise
    for got, ref in zip(F, s._factors):
        np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(ref))


def test_pallas_bucket_programs_trace_once():
    """Steady-state bucket calls on a pallas plan re-trace nothing: the
    eager kernel dispatch + jitted epilogue pair is memoized per bucket
    like every other program family."""
    pall, _ = _plans()
    rng = np.random.default_rng(71)
    A = _gen(rng, 2, N, np.float32)
    pall._stacked_factor_fn(2)(jnp.asarray(A))
    pall._factor_health_fn(2)(jnp.asarray(A))
    snapshot = dict(pall.trace_counts)
    compiles0 = profiler.compile_count()
    for _ in range(3):
        pall._stacked_factor_fn(2)(jnp.asarray(A))
        pall._factor_health_fn(2)(jnp.asarray(A))
    assert dict(pall.trace_counts) == snapshot, \
        "steady-state pallas factor buckets traced a program"
    assert profiler.compile_count() == compiles0, \
        "steady-state pallas factor buckets compiled"


def test_ineligible_keys_fall_back_to_xla_body():
    """backend='pallas' with factor_dtype != dtype is OUTSIDE the
    kernel's eligibility gate (the in-kernel probe row must read the
    same operand `probe_row` would): the plan factors through the
    vmapped XLA body and still serves."""
    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V,
                                   backend="pallas",
                                   factor_dtype=jnp.float64)
    assert not plan._pallas_factor
    rng = np.random.default_rng(73)
    A = _gen(rng, 1, N, np.float32)
    s = plan.factor(jnp.asarray(A[0]))
    b = rng.standard_normal((N, 1)).astype(np.float32)
    x = np.asarray(s.solve(jnp.asarray(b)))
    assert np.abs(A[0] @ x - b).max() < 1e-3


def test_engine_factor_lane_on_pallas_plan_bitwise():
    """Coalesced cold starts on a pallas plan (checked lane) open
    sessions that solve bitwise identically to `plan.factor` — §29
    rides the §21 lane unchanged."""
    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V,
                                   backend="pallas")
    rng = np.random.default_rng(79)
    A = _gen(rng, 3, N, np.float32)
    b = rng.standard_normal((N, 2)).astype(np.float32)
    with ServeEngine(max_batch_delay=0.05, max_factor_batch=4,
                     health=HealthPolicy()) as eng:
        futs = [eng.submit_factor(plan, A[i]) for i in range(3)]
        sessions = [f.result(timeout=120) for f in futs]
        for i, s in enumerate(sessions):
            ref = plan.factor(jnp.asarray(A[i]))
            np.testing.assert_array_equal(np.asarray(s.solve(b)),
                                          np.asarray(ref.solve(b)),
                                          err_msg=f"session {i}")
        assert sessions[0]._probe is not None
        stats = eng.stats()
    assert stats["factor_requests"] == 3
    assert stats["factor_batches"] == 1
