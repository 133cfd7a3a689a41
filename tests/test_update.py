"""Incremental low-rank refresh tests (ISSUE 2): Woodbury correctness,
drift-policy refactor triggers, and the compiled-once-per-bucket contract.

The acceptance contracts, asserted rather than trusted: an updated
session solves the DRIFTED system (held to the full-refactor oracle's
residual bars), accumulation composes (two rank-1 updates == one rank-2
update bitwise), `update()` + corrected solves perform zero recompiles
after the first call per (rank bucket, RHS bucket) via the plan's
trace-count hook, and the drift policy pays exactly one true
refactorization when rank/conditioning stops paying.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from conflux_tpu import batched, serve, solvers
from conflux_tpu.update import DriftPolicy, apply_update, rank_bucket

B, N, V, K = 8, 32, 16, 3


def _systems(b=B, n=N, seed=0, spd=False):
    rng = np.random.default_rng(seed)
    lead = () if b is None else (b,)
    A = (rng.standard_normal(lead + (n, n)) / np.sqrt(n)
         + 2.0 * np.eye(n)).astype(np.float32)
    if spd:
        A = (A @ np.swapaxes(A, -1, -2)
             + np.eye(n, dtype=np.float32)).astype(np.float32)
    U = (rng.standard_normal(lead + (n, K)) / np.sqrt(n)).astype(np.float32)
    Vm = (rng.standard_normal(lead + (n, K)) / np.sqrt(n)).astype(np.float32)
    rhs = rng.standard_normal(lead + (n,)).astype(np.float32)
    return A, U, Vm, rhs


def _res(A1, x, b):
    """Relative residuals against the DRIFTED matrix, per element."""
    A64 = np.asarray(A1, np.float64)
    x64, b64 = np.asarray(x, np.float64), np.asarray(b, np.float64)
    if A64.ndim == 2:
        return np.linalg.norm(A64 @ x64 - b64) / np.linalg.norm(b64)
    r = np.einsum("bij,bj->bi", A64, x64) - b64
    return np.linalg.norm(r, axis=1) / np.linalg.norm(b64, axis=1)


def _refactor_bars(A1, b, **kw):
    """The full-refactor oracle: factor the drifted matrix directly."""
    if np.asarray(A1).ndim == 2:
        x = solvers.solve(jnp.asarray(A1), jnp.asarray(b), v=V, **kw)
        return _res(A1, x, b)
    xs = np.stack([
        np.asarray(solvers.solve(jnp.asarray(A1[i]), jnp.asarray(b[i]),
                                 v=V, **kw))
        for i in range(A1.shape[0])])
    return _res(A1, xs, b)


def _bars(A1, b, **kw):
    return np.maximum(10.0 * _refactor_bars(A1, b, **kw), 1e-6)


# --------------------------------------------------------------------------- #
# rank buckets
# --------------------------------------------------------------------------- #


def test_rank_bucket_contract():
    assert [rank_bucket(k) for k in (1, 2, 3, 4, 5, 8, 9)] \
        == [1, 2, 4, 4, 8, 8, 16]
    with pytest.raises(ValueError, match="positive"):
        rank_bucket(0)


# --------------------------------------------------------------------------- #
# session update correctness
# --------------------------------------------------------------------------- #


def test_session_update_solves_drifted_system():
    serve.clear_plans()
    A, U, Vm, b = _systems(b=None, seed=1)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    session = plan.factor(jnp.asarray(A))
    session.update(jnp.asarray(U), jnp.asarray(Vm))
    assert session.update_rank == K and session.updates == 1
    x = session.solve(jnp.asarray(b))
    A1 = A + U @ Vm.T
    assert _res(A1, x, b) <= _bars(A1, b), "updated solve missed the bar"
    assert session.factorizations == 1, "update refactored"
    # the un-drifted base is NOT what we solve anymore
    assert _res(A, x, b) > 1e-4


def test_session_update_batched_matches_oracle():
    serve.clear_plans()
    A, U, Vm, b = _systems(seed=2)
    plan = serve.FactorPlan.create((B, N, N), jnp.float32, v=V)
    session = plan.factor(jnp.asarray(A))
    x = session.update(jnp.asarray(U), jnp.asarray(Vm)).solve(jnp.asarray(b))
    A1 = np.asarray(apply_update(jnp.asarray(A), jnp.asarray(U),
                                 jnp.asarray(Vm)))
    assert (_res(A1, x, b) <= _bars(A1, b)).all()
    assert session.factorizations == 1


def test_session_update_mesh_sharded():
    serve.clear_plans()
    A, U, Vm, b = _systems(seed=3)
    mesh = batched.batch_mesh()
    plan = serve.FactorPlan.create((B, N, N), jnp.float32, v=V, mesh=mesh)
    session = plan.factor(jnp.asarray(A))
    session.update(jnp.asarray(U), jnp.asarray(Vm))
    x = session.solve(jnp.asarray(b))
    assert len(x.sharding.device_set) == 8
    A1 = A + np.einsum("bik,bjk->bij", U, Vm)
    assert (_res(A1, x, b) <= _bars(A1, b)).all()


def test_session_update_spd_base():
    """Cholesky base factors; the drift need not preserve symmetry."""
    serve.clear_plans()
    A, U, Vm, b = _systems(b=None, seed=4, spd=True)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V, spd=True)
    session = plan.factor(jnp.asarray(A))
    x = session.update(jnp.asarray(U), jnp.asarray(Vm)).solve(jnp.asarray(b))
    A1 = A + U @ Vm.T
    assert _res(A1, x, b) <= _bars(A1, b)


def test_session_update_accumulates_and_replaces():
    serve.clear_plans()
    A, U, Vm, b = _systems(b=None, seed=5)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    # two stacked updates == one combined update, bitwise (same padded
    # capacitance program, same accumulated factors)
    s1 = plan.factor(jnp.asarray(A))
    s1.update(jnp.asarray(U[:, :1]), jnp.asarray(Vm[:, :1]))
    s1.update(jnp.asarray(U[:, 1:]), jnp.asarray(Vm[:, 1:]))
    assert s1.update_rank == K
    s2 = plan.factor(jnp.asarray(A))
    s2.update(jnp.asarray(U), jnp.asarray(Vm))
    np.testing.assert_array_equal(np.asarray(s1.solve(jnp.asarray(b))),
                                  np.asarray(s2.solve(jnp.asarray(b))))
    # replace=True measures the drift from the base again
    s1.update(jnp.asarray(U), jnp.asarray(Vm), replace=True)
    assert s1.update_rank == K
    np.testing.assert_array_equal(np.asarray(s1.solve(jnp.asarray(b))),
                                  np.asarray(s2.solve(jnp.asarray(b))))


def test_session_update_refine_backstop():
    """The IR backstop computes residuals against the DRIFTED matrix and
    tightens the refreshed solution."""
    serve.clear_plans()
    A, U, Vm, b = _systems(b=None, seed=6)
    A1 = A + U @ Vm.T
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    plain = plan.factor(jnp.asarray(A)) \
        .update(jnp.asarray(U), jnp.asarray(Vm)).solve(jnp.asarray(b))
    refined = plan.factor(jnp.asarray(A), policy=DriftPolicy(refine=2)) \
        .update(jnp.asarray(U), jnp.asarray(Vm)).solve(jnp.asarray(b))
    assert _res(A1, refined, b) <= max(float(_res(A1, plain, b)), 1e-7)


def test_session_update_rejects_bad_shapes():
    serve.clear_plans()
    A, U, Vm, _ = _systems(seed=7)
    plan = serve.FactorPlan.create((B, N, N), jnp.float32, v=V)
    session = plan.factor(jnp.asarray(A))
    with pytest.raises(ValueError, match="must agree"):
        session.update(jnp.asarray(U), jnp.asarray(Vm[:, :, :1]))
    with pytest.raises(ValueError, match="rank axis"):
        session.update(jnp.asarray(U[0]), jnp.asarray(Vm[0]))
    with pytest.raises(ValueError, match="rank axis"):
        session.update(jnp.asarray(U[:4]), jnp.asarray(Vm[:4]))


# --------------------------------------------------------------------------- #
# compile-count contract (the ISSUE 2 acceptance test)
# --------------------------------------------------------------------------- #


def test_update_zero_recompiles_per_bucket():
    """`update()` + corrected solves compile once per (rank bucket,
    RHS bucket) — repeat drift traffic (ranks/widths within the same
    buckets) traces nothing new."""
    serve.clear_plans()
    A, U, Vm, b = _systems(b=None, seed=8)
    rng = np.random.default_rng(80)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    session = plan.factor(jnp.asarray(A))
    session.update(jnp.asarray(U), jnp.asarray(Vm))  # k=3 -> bucket 4
    session.solve(jnp.asarray(b))                    # nrhs=1 -> bucket 1
    t = dict(plan.trace_counts)
    assert t["update"] == 1 and t["update_solve"] == 1
    for k in (3, 4, 3):  # same rank bucket (4), fresh drifts
        Un = (rng.standard_normal((N, k)) / np.sqrt(N)).astype(np.float32)
        Vn = (rng.standard_normal((N, k)) / np.sqrt(N)).astype(np.float32)
        session.update(jnp.asarray(Un), jnp.asarray(Vn), replace=True)
        session.solve(jnp.asarray(
            rng.standard_normal(N).astype(np.float32)))
    assert plan.trace_counts == t, "same-bucket drift traffic recompiled"
    assert session.refactors == 0, "the drift policy refactored"
    # a second session on the same plan shares every compiled program
    s2 = plan.factor(jnp.asarray(A))
    s2.update(jnp.asarray(U), jnp.asarray(Vm)).solve(jnp.asarray(b))
    assert plan.trace_counts == t, "second session recompiled"
    # a new rank bucket traces exactly one more update + solve pair
    U8 = (rng.standard_normal((N, 8)) / np.sqrt(N)).astype(np.float32)
    session.update(jnp.asarray(U8), jnp.asarray(U8), replace=True)
    session.solve(jnp.asarray(b))
    assert plan.trace_counts["update"] == t["update"] + 1
    assert plan.trace_counts["update_solve"] == t["update_solve"] + 1


# --------------------------------------------------------------------------- #
# drift policy
# --------------------------------------------------------------------------- #


def test_drift_policy_rank_trigger_refactors_once():
    serve.clear_plans()
    A, U, Vm, b = _systems(b=None, seed=9)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    session = plan.factor(jnp.asarray(A),
                          policy=DriftPolicy(max_rank=2 * K - 1))
    session.update(jnp.asarray(U), jnp.asarray(Vm))
    assert session.refactors == 0 and session.update_rank == K
    session.update(jnp.asarray(U), jnp.asarray(Vm))  # 2K > max_rank
    assert session.refactors == 1 and session.factorizations == 2
    assert session.update_rank == 0, "correction must reset after refactor"
    # the refactored base IS the twice-drifted matrix
    A2 = A + 2.0 * (U @ Vm.T)
    x = session.solve(jnp.asarray(b))
    assert _res(A2, x, b) <= _bars(A2, b)
    # and the plan's factor program was reused, not re-traced
    assert plan.trace_counts["factor"] == 1


def test_drift_policy_cond_trigger():
    """cond1(C) >= 1 by construction, so a sub-1 limit must always
    refactor — the ill-conditioned-capacitance escape hatch."""
    serve.clear_plans()
    A, U, Vm, b = _systems(b=None, seed=10)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    session = plan.factor(jnp.asarray(A),
                          policy=DriftPolicy(cond_limit=0.5))
    session.update(jnp.asarray(U), jnp.asarray(Vm))
    assert session.refactors == 1 and session.update_rank == 0
    A1 = A + U @ Vm.T
    x = session.solve(jnp.asarray(b))
    assert _res(A1, x, b) <= _bars(A1, b)


def test_drift_policy_default_max_rank():
    assert DriftPolicy().resolved_max_rank(1024) == 128
    assert DriftPolicy().resolved_max_rank(32) == 8
    assert DriftPolicy(max_rank=5).resolved_max_rank(1024) == 5


# --------------------------------------------------------------------------- #
# one-shot entry points
# --------------------------------------------------------------------------- #


def test_refactor_buffer_donation_and_live_array_parity():
    """ISSUE 3 satellite: a long-lived drifting session holds ONE
    resident base+factor set. The refresh program donates the superseded
    base once the session owns it (never the caller's array), old factor
    and Woodbury references drop before the replacement dispatch, and the
    live-buffer count stays flat across repeated refactors."""
    import gc

    import jax

    serve.clear_plans()
    A, U, Vm, b = _systems(b=None, seed=21)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    # max_rank=1 < K forces a true refactor on every update
    session = plan.factor(jnp.asarray(A), policy=DriftPolicy(max_rank=1))
    caller_A = session._A0
    session.update(jnp.asarray(U), jnp.asarray(Vm))
    assert session.refactors == 1
    # first refactor: the base was the CALLER's array — never donated
    assert not caller_A.is_deleted()
    owned_A = session._A0
    assert session._owns_base
    session.update(jnp.asarray(U), jnp.asarray(Vm))
    assert session.refactors == 2
    # later refactors: the session-owned base is donated to its successor
    assert owned_A.is_deleted(), \
        "superseded owned base survived the refresh dispatch"
    # the session still answers correctly after donation churn
    x = session.solve(jnp.asarray(b))
    A1 = np.asarray(apply_update(jnp.asarray(A), jnp.asarray(U),
                                 jnp.asarray(Vm)))
    A2 = np.asarray(apply_update(jnp.asarray(A1), jnp.asarray(U),
                                 jnp.asarray(Vm)))
    assert _res(A2, x, b) <= float(_bars(A2, b))
    # live-array parity: more refactors may not grow resident state
    x.block_until_ready()
    gc.collect()
    n0 = len(jax.live_arrays())
    for _ in range(4):
        session.update(jnp.asarray(U), jnp.asarray(Vm))
    session.solve(jnp.asarray(b)).block_until_ready()
    gc.collect()
    n1 = len(jax.live_arrays())
    assert n1 <= n0, \
        f"live buffers grew across refactors: {n0} -> {n1}"


def test_solve_updated_matches_refactor_oracle():
    A, U, Vm, b = _systems(b=None, seed=11)
    x = solvers.solve_updated(jnp.asarray(A), jnp.asarray(U),
                              jnp.asarray(Vm), jnp.asarray(b), v=V)
    A1 = A + U @ Vm.T
    assert _res(A1, x, b) <= _bars(A1, b)
    # multi-RHS + refine
    bk = np.stack([b, 2 * b], axis=1)
    xk = solvers.solve_updated(jnp.asarray(A), jnp.asarray(U),
                               jnp.asarray(Vm), jnp.asarray(bk), v=V,
                               refine=1)
    assert xk.shape == (N, 2)
    np.testing.assert_allclose(np.asarray(xk[:, 1]), 2 * np.asarray(xk[:, 0]),
                               rtol=1e-5, atol=1e-6)


def test_solve_updated_pads_non_tile_sizes():
    A, U, Vm, b = _systems(b=None, n=N - 2, seed=12)
    x = solvers.solve_updated(jnp.asarray(A), jnp.asarray(U),
                              jnp.asarray(Vm), jnp.asarray(b), v=V)
    assert x.shape == (N - 2,)
    A1 = A + U @ Vm.T
    assert _res(A1, x, b) < 1e-5


def test_solve_updated_batched_matches_oracle():
    A, U, Vm, b = _systems(seed=13)
    x = batched.solve_updated_batched(jnp.asarray(A), jnp.asarray(U),
                                      jnp.asarray(Vm), jnp.asarray(b), v=V)
    A1 = A + np.einsum("bik,bjk->bij", U, Vm)
    assert (_res(A1, x, b) <= _bars(A1, b)).all()
    with pytest.raises(ValueError, match="update factors"):
        batched.solve_updated_batched(jnp.asarray(A), jnp.asarray(U[0]),
                                      jnp.asarray(Vm[0]), jnp.asarray(b),
                                      v=V)


def test_solve_updated_batched_ragged_mesh():
    A, U, Vm, b = _systems(b=5, seed=14)
    mesh = batched.batch_mesh()
    x = batched.solve_updated_batched(jnp.asarray(A), jnp.asarray(U),
                                      jnp.asarray(Vm), jnp.asarray(b),
                                      v=V, mesh=mesh)
    assert x.shape == (5, N)
    A1 = A + np.einsum("bik,bjk->bij", U, Vm)
    assert (_res(A1, x, b) < 1e-5).all()
