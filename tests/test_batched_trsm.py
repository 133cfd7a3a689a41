"""Blocked batched trsm: the ISSUE 11 contracts (DESIGN §27).

- `ops.batched_trsm.blocked_trsm` agrees with `lax.linalg.triangular_solve`
  across dtypes (f32/f64), shapes (N in {8, 64, 256}, B in {1, 4, 32}),
  sides (lower/upper) and unit/non-unit diagonals — including N not a
  multiple of the block size (identity-extended tail block).
- The Pallas kernel (interpret mode on CPU) matches the pure-XLA path.
- The fused Freivalds probe epilogue leaves x untouched and its in-loop
  accumulators equal the post-hoc reductions.
- `substitution="auto"` resolves to 'blocked' for every servable plan
  (batched AND single-system — the gang/factor-lane-served shapes);
  'inv'/'trsm' stay explicit opt-ins; the blocked engine's answers hold
  the other engines' residual bars, drift/refactor included.
- The fused-probe checked programs live in the dedicated `_trsm_cache`
  (never polluting `_solve_cache`, whose key set tests pin), and ride
  `bucket_ready`/`release_buckets` like every other family.
- The vmapped blocked programs keep the bucket/pad bitwise-invariance
  contract (slot i identical across stack buckets and pad contents).
- Gang end-to-end: a `substitution="blocked"` (auto) plan serves
  stacked — clean, drifted (Woodbury) and checked (fused per-slot
  verdict) legs — with the exclusion counters at literal zero: the
  "gang plans must open with inv" rule is retired.
- `PlanKey.substitution` round-trips through the tier layer's
  fleet.json save/restore codec.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from conflux_tpu import serve
from conflux_tpu.batched import solve_batched, stack_trees
from conflux_tpu.engine import ServeEngine
from conflux_tpu.ops import batched_trsm as bt
from conflux_tpu.ops import blas
from conflux_tpu.resilience import HealthPolicy


def _tri(rng, B, N, dtype, lower):
    A = (rng.standard_normal((B, N, N)) / np.sqrt(N)
         + 2.0 * np.eye(N)).astype(dtype)
    return np.tril(A) if lower else np.triu(A), A


# --------------------------------------------------------------------- #
# the kernel engine vs lax.linalg.triangular_solve
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("dtype,rtol", [(np.float32, 3e-4),
                                        (np.float64, 1e-10)])
@pytest.mark.parametrize("B,N", [(32, 8), (4, 64), (1, 256), (32, 256)])
@pytest.mark.parametrize("lower,unit", [(True, False), (True, True),
                                        (False, False)])
def test_blocked_trsm_matches_lax(dtype, rtol, B, N, lower, unit):
    rng = np.random.default_rng(N * B + lower + 2 * unit)
    T, A = _tri(rng, B, N, dtype, lower)
    # unit solves read the packed form: pass the FULL matrix (garbage
    # on/above the diagonal from the other factor) like packed LU does
    operand = A if unit else T
    b = rng.standard_normal((B, N, 2)).astype(dtype)
    x = bt.blocked_trsm(operand, b, lower=lower, unit_diagonal=unit)
    ref = lax.linalg.triangular_solve(
        jnp.asarray(T), jnp.asarray(b), left_side=True, lower=lower,
        unit_diagonal=unit)
    np.testing.assert_allclose(np.asarray(x), np.asarray(ref),
                               rtol=rtol, atol=30 * rtol)


def test_blocked_trsm_ragged_tail_block():
    """N=48 is not a multiple of the default 32-wide block: the tail
    block identity-extends, and padded answers slice back exactly."""
    rng = np.random.default_rng(48)
    T, _ = _tri(rng, 3, 48, np.float32, True)
    b = rng.standard_normal((3, 48, 1)).astype(np.float32)
    x = bt.blocked_trsm(T, b, lower=True)
    ref = lax.linalg.triangular_solve(
        jnp.asarray(T), jnp.asarray(b), left_side=True, lower=True)
    np.testing.assert_allclose(np.asarray(x), np.asarray(ref),
                               rtol=3e-4, atol=1e-5)


def test_blocked_trsm_vector_rhs_and_shape_checks():
    rng = np.random.default_rng(5)
    T, _ = _tri(rng, 2, 64, np.float32, True)
    b = rng.standard_normal((2, 64)).astype(np.float32)
    x = bt.blocked_trsm(T, b)
    assert x.shape == (2, 64)
    with pytest.raises(ValueError, match="rhs"):
        bt.blocked_trsm(T, b[:, :32])
    with pytest.raises(ValueError, match="T must be"):
        bt.blocked_trsm(T[:, :32, :], b)


def test_pallas_kernel_matches_xla_path():
    """The Pallas batched kernel (interpret mode off-TPU) is bitwise-
    grade close to the pure-XLA block loop, lower and upper, ragged
    included — the §7 interpret-mode correctness discipline."""
    rng = np.random.default_rng(9)
    for N, k, lower in [(128, 1, True), (128, 4, False), (48, 2, True)]:
        T, _ = _tri(rng, 4, N, np.float32, lower)
        b = rng.standard_normal((4, N, k)).astype(np.float32)
        xp = bt.blocked_trsm(T, b, lower=lower, backend="pallas")
        xx = bt.blocked_trsm(T, b, lower=lower, backend="xla")
        np.testing.assert_allclose(np.asarray(xp), np.asarray(xx),
                                   rtol=1e-5, atol=1e-6)


def test_blas_registry_entry_resolves_backend():
    rng = np.random.default_rng(13)
    T, _ = _tri(rng, 2, 64, np.float32, True)
    b = rng.standard_normal((2, 64, 1)).astype(np.float32)
    x0 = blas.blocked_trsm(T, b)  # module backend (xla)
    x1 = blas.blocked_trsm(T, b, backend="pallas")
    np.testing.assert_allclose(np.asarray(x0), np.asarray(x1),
                               rtol=1e-5, atol=1e-6)


def test_probe_epilogue_accumulates_in_loop():
    """The fused epilogue's accumulators equal the post-hoc reductions
    and leave x exactly the unfused solve's bits."""
    rng = np.random.default_rng(21)
    T, _ = _tri(rng, 1, 64, np.float32, False)
    T = T[0]
    dinv = bt.diag_block_inverses(jnp.asarray(T), lower=False)
    b = rng.standard_normal((64, 2)).astype(np.float32)
    wA = rng.standard_normal(64).astype(np.float32)
    x, xsum, wAx = bt.blocked_solve_probe(
        jnp.asarray(T), dinv, jnp.asarray(b), jnp.asarray(wA),
        lower=False, stats_dtype=jnp.float32)
    x0 = bt.blocked_solve(jnp.asarray(T), dinv, jnp.asarray(b),
                          lower=False)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x0))
    assert np.isclose(float(xsum), float(np.sum(np.asarray(x))),
                      rtol=1e-4)
    assert np.isclose(float(wAx),
                      float(np.dot(wA, np.asarray(x)[:, 0])),
                      rtol=1e-3, atol=1e-4)


# --------------------------------------------------------------------- #
# plan wiring: auto resolution, residual bars, cache isolation
# --------------------------------------------------------------------- #

N, V = 64, 16


def _mk(rng, n=1):
    A = (rng.standard_normal((n, N, N)) / np.sqrt(N)
         + 2.0 * np.eye(N)).astype(np.float32)
    return A


def test_auto_resolves_to_blocked_everywhere():
    serve.clear_plans()
    single = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    batched = serve.FactorPlan.create((4, N, N), jnp.float32, v=V)
    assert single.key.substitution == "blocked"
    assert batched.key.substitution == "blocked"
    # explicit opt-ins still resolve verbatim
    for sub in ("inv", "trsm", "blocked"):
        p = serve.FactorPlan.create((N, N), jnp.float32, v=V,
                                    substitution=sub)
        assert p.key.substitution == sub
    with pytest.raises(ValueError, match="substitution"):
        serve.FactorPlan.create((N, N), jnp.float32, v=V,
                                substitution="nope")


@pytest.mark.parametrize("spd", [False, True])
def test_blocked_plan_holds_residual_bars(spd):
    serve.clear_plans()
    rng = np.random.default_rng(31)
    A = _mk(rng)[0]
    if spd:
        A = (A @ A.T / N + 2.0 * np.eye(N)).astype(np.float32)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V, spd=spd)
    assert plan.key.substitution == "blocked"
    s = plan.factor(jnp.asarray(A))
    b = rng.standard_normal((N, 3)).astype(np.float32)
    x = np.asarray(s.solve(jnp.asarray(b)))
    assert np.abs(A @ x - b).max() < 1e-4
    # drift + refactor ride the blocked corr too (spd plans need an
    # SPD-preserving drift — a refactor re-runs Cholesky on the
    # drifted base)
    U = (0.01 * rng.standard_normal((N, 2))).astype(np.float32)
    Vv = U if spd else (0.01 * rng.standard_normal((N, 2))
                        ).astype(np.float32)
    s.update(U, Vv)
    xd = np.asarray(s.solve(jnp.asarray(b)))
    assert np.abs((A + U @ Vv.T) @ xd - b).max() < 1e-4
    s.refactor()
    xr = np.asarray(s.solve(jnp.asarray(b)))
    assert np.abs((A + U @ Vv.T) @ xr - b).max() < 1e-4


def test_solve_batched_blocked_substitution():
    rng = np.random.default_rng(37)
    A = _mk(rng, 6)
    b = rng.standard_normal((6, N)).astype(np.float32)
    xt = np.asarray(solve_batched(A, b, v=V))
    xb = np.asarray(solve_batched(A, b, v=V, substitution="blocked"))
    np.testing.assert_allclose(xb, xt, rtol=2e-4, atol=1e-5)
    with pytest.raises(ValueError, match="substitution"):
        solve_batched(A, b, v=V, substitution="inv")


def test_fused_checked_programs_live_in_trsm_cache():
    """The blocked engine's checked programs are their own program
    family: dedicated memo dict (test_serve pins _solve_cache's key
    set), bucket_ready sees their warmth, release_buckets retires
    them with the width bucket."""
    serve.clear_plans()
    rng = np.random.default_rng(41)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    s = plan.factor(jnp.asarray(_mk(rng)[0]))
    b = rng.standard_normal((N, 2)).astype(np.float32)
    x, verdict = s.solve_checked(jnp.asarray(b))
    v = np.asarray(verdict)
    assert v[0] == 1.0 and v[1] < 1e-4
    assert ("health", 2) in plan._trsm_cache
    assert ("health", 2) not in plan._solve_cache
    assert plan.bucket_ready(width=2, checked=True)
    # stacked checked program: same family, same dict
    F = stack_trees([s._factors, s._factors])
    wA = jnp.stack([s._probe_row(), s._probe_row()])
    buf = np.stack([b, b]).astype(np.float32)
    xs, vs = plan._stacked_solve_health_fn(2, 2)(F, None, wA,
                                                 jnp.asarray(buf))
    vs = np.asarray(vs)
    assert vs.shape == (2, 2)
    assert vs[0].all() and (vs[1] < 1e-4).all()
    assert ("gstack_health", 2, 2) in plan._trsm_cache
    assert plan.bucket_ready(stack=(2, 2), checked=True)
    # the checked answer equals the plain blocked solve's columns
    np.testing.assert_allclose(np.asarray(x),
                               np.asarray(s.solve(jnp.asarray(b))),
                               rtol=2e-5, atol=1e-6)
    # retirement drops the family with the width bucket
    dropped = plan.release_buckets(widths=(2,))
    assert ("health", 2) not in plan._trsm_cache
    assert ("gstack_health", 2, 2) not in plan._trsm_cache
    assert dropped >= 2
    assert not plan.bucket_ready(width=2, checked=True)
    # a re-touch re-traces and answers (released, not forbidden)
    x2, v2 = s.solve_checked(jnp.asarray(b))
    assert np.asarray(v2)[0] == 1.0


def test_fused_verdict_trips_on_poison():
    """A non-finite RHS trips the fused finite accumulator — the
    epilogue is a real verdict, not a vestige."""
    serve.clear_plans()
    rng = np.random.default_rng(43)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    s = plan.factor(jnp.asarray(_mk(rng)[0]))
    b = np.ones((N, 1), np.float32)
    b[3] = np.nan
    _x, verdict = s.solve_checked(jnp.asarray(b))
    assert np.asarray(verdict)[0] == 0.0


def test_stacked_blocked_bucket_pad_invariance():
    """The vmapped blocked programs keep the §21/§26 contract: slot i
    is BITWISE invariant to the stack bucket size and pad contents."""
    serve.clear_plans()
    rng = np.random.default_rng(47)
    A = _mk(rng, 2)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    s0, s1 = plan.factor(jnp.asarray(A[0])), plan.factor(jnp.asarray(A[1]))
    b = rng.standard_normal((N, 1)).astype(np.float32)
    F2 = stack_trees([s0._factors, s1._factors])
    F4 = stack_trees([s0._factors, s1._factors,
                      s0._factors, s0._factors])
    buf2 = np.zeros((2, N, 1), np.float32)
    buf2[0] = b
    buf4 = rng.standard_normal((4, N, 1)).astype(np.float32)
    buf4[0] = b
    x2 = np.asarray(plan._stacked_solve_fn(2, 1)(F2, None, buf2))[0]
    x4 = np.asarray(plan._stacked_solve_fn(4, 1)(F4, None, buf4))[0]
    np.testing.assert_array_equal(x2, x4)
    # the checked (fused-probe) stacked program holds it too
    wA2 = jnp.stack([s0._probe_row(), s1._probe_row()])
    wA4 = jnp.stack([s0._probe_row(), s1._probe_row(),
                     s0._probe_row(), s0._probe_row()])
    h2 = np.asarray(plan._stacked_solve_health_fn(2, 1)(
        F2, None, wA2, jnp.asarray(buf2))[0])[0]
    h4 = np.asarray(plan._stacked_solve_health_fn(4, 1)(
        F4, None, wA4, jnp.asarray(buf4))[0])[0]
    np.testing.assert_array_equal(h2, h4)


# --------------------------------------------------------------------- #
# gang end-to-end: the retired inv rule
# --------------------------------------------------------------------- #


def test_gang_serves_blocked_plan_clean_drifted_checked():
    """A substitution='auto' (blocked) plan gangs at full function:
    clean, drifted (stacked Woodbury) and checked (fused per-slot
    verdict) windows all ride the stacked path with exclusion counters
    at zero — no inv opt-in anywhere."""
    serve.clear_plans()
    rng = np.random.default_rng(53)
    A = _mk(rng, 4)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    assert plan.key.substitution == "blocked"
    fleet = [plan.factor(jnp.asarray(A[i]), sid=f"u{i}")
             for i in range(4)]
    bs = [rng.standard_normal((N, 1)).astype(np.float32)
          for _ in range(4)]
    direct = [np.asarray(s.solve(b)) for s, b in zip(fleet, bs)]

    # clean window
    eng = ServeEngine(max_batch_delay=60.0, stack_sessions=True,
                      max_stack=8)
    futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
    eng.close(timeout=120)
    res = [np.asarray(f.result(60)) for f in futs]
    for r, d in zip(res, direct):
        np.testing.assert_allclose(r, d, rtol=2e-5, atol=1e-6)
    st = eng.stats()
    assert st["gang_batches"] == 1
    for reason in ("upd_pending", "checked", "mesh"):
        assert st["stack_exclusions"][reason] == 0

    # drifted + checked window
    from conflux_tpu.resilience import health_stats

    ladder = ("output_failures", "gang_unhealthy_slots",
              "survivor_redispatches", "refactor_escalations",
              "refine_escalations", "unhealthy")
    h0 = health_stats()
    U = (0.01 * rng.standard_normal((N, 2))).astype(np.float32)
    Vv = (0.01 * rng.standard_normal((N, 2))).astype(np.float32)
    fleet[0].update(U, Vv)
    fleet[2].update(U, Vv)
    drifted_direct = [np.asarray(s.solve(b))
                      for s, b in zip(fleet, bs)]
    engH = ServeEngine(max_batch_delay=60.0, stack_sessions=True,
                       max_stack=8, health=HealthPolicy())
    futs = [engH.submit(s, b) for s, b in zip(fleet, bs)]
    engH.close(timeout=120)
    res = [np.asarray(f.result(60)) for f in futs]
    for r, d in zip(res, drifted_direct):
        np.testing.assert_allclose(r, d, rtol=2e-5, atol=1e-6)
    stH = engH.stats()
    assert stH["gang_batches"] >= 1
    for reason in ("upd_pending", "checked", "mesh"):
        assert stH["stack_exclusions"][reason] == 0
    # the fused verdicts passed clean: no slot failed, no ladder ran
    h1 = health_stats()
    assert {k: h1.get(k, 0) - h0.get(k, 0) for k in ladder} \
        == dict.fromkeys(ladder, 0)


def test_gang_blocked_zero_compiles_after_prewarm():
    """Steady-state stacked windows on a blocked plan trace nothing
    after prewarm — the §26 zero-compile contract carries over."""
    serve.clear_plans()
    rng = np.random.default_rng(59)
    A = _mk(rng, 4)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    fleet = [plan.factor(jnp.asarray(A[i])) for i in range(4)]
    eng = ServeEngine(max_batch_delay=0.05, stack_sessions=True,
                      max_stack=4)
    eng.prewarm(fleet[0], widths=(1,), stacks=(4,))
    bs = [rng.standard_normal((N, 1)).astype(np.float32)
          for _ in range(4)]
    futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
    for f in futs:
        f.result(60)
    snapshot = dict(plan.trace_counts)
    for _ in range(3):
        futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
        for f in futs:
            f.result(60)
    assert plan.trace_counts == snapshot, \
        "steady-state blocked gang windows traced a program"
    eng.close(timeout=120)


# --------------------------------------------------------------------- #
# checkpoint codec round-trip
# --------------------------------------------------------------------- #


def test_plankey_substitution_roundtrips_fleet_codec():
    """tier.py's fleet.json plan codec reconstructs the EXACT PlanKey
    — substitution='blocked' included — and lands on the same cached
    plan object (`FactorPlan.from_key`)."""
    from conflux_tpu.tier import _plan_fields, _plan_from_fields

    serve.clear_plans()
    for sub in ("blocked", "inv", "trsm"):
        plan = serve.FactorPlan.create((N, N), jnp.float32, v=V,
                                       substitution=sub)
        d = _plan_fields(plan)
        assert d["substitution"] == sub
        import json

        restored = _plan_from_fields(json.loads(json.dumps(d)))
        assert restored is plan
        assert restored.key.substitution == sub
