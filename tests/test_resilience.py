"""Serve-path resilience tests: the ISSUE 4 acceptance contracts.

- Blast-radius isolation: a NaN injected into one request at STAGING
  fails that request's future alone — every co-batched request in the
  same coalescing window still returns a BITWISE-correct answer.
- Escalation ladder: an injected drift-solve health failure triggers
  exactly ONE forced-refactor escalation (riding the plan's cached
  factor program) and then succeeds; a full-ladder loss raises a
  structured `SolveUnhealthy` with per-rung evidence.
- Deadlines: lazy eviction fires mid-window, fails the future with
  `DeadlineExceeded`, and RELEASES the pending slot (un-wedging an
  `on_full='block'` submitter).
- Quarantine: the circuit breaker opens after K consecutive ladder
  failures (fast `SessionQuarantined`), half-open probes after the
  cooldown, and closes again on a healthy answer.
- Fault recovery: an injected drain crash re-dispatches the innocent
  survivors solo instead of failing the batch; a killed worker thread
  trips the watchdog, which fails pending work instead of queueing
  forever; a wedged `close(timeout)` names the stuck thread and fails
  still-pending futures.
- All outcomes surface through `profiler.serve_stats()['health']`.
"""

import dataclasses
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from conflux_tpu import profiler, resilience, serve
from conflux_tpu.engine import (
    EngineClosed,
    EngineSaturated,
    ServeEngine,
)
from conflux_tpu.resilience import (
    CircuitBreaker,
    DeadlineExceeded,
    FaultPlan,
    FaultSpec,
    HealthPolicy,
    RhsNonFinite,
    SessionQuarantined,
    SolveUnhealthy,
)

N, V = 32, 16


def _system(seed=0, n=N):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, n)) / np.sqrt(n)
            + 2.0 * np.eye(n)).astype(np.float32)


def _session(seed=0):
    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    return plan.factor(jnp.asarray(_system(seed)))


def _rhs(seed=1, w=2):
    rng = np.random.default_rng(seed)
    shape = (N, w) if w > 1 else (N,)
    return rng.standard_normal(shape).astype(np.float32)


def _delta(before, after):
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


# --------------------------------------------------------------------- #
# admission guards + blast-radius isolation
# --------------------------------------------------------------------- #


def test_submit_guard_rejects_nonfinite_rhs():
    session = _session(11)
    bad = _rhs(11)
    bad[3, 0] = np.inf
    h0 = resilience.health_stats()
    with ServeEngine(max_batch_delay=0.0, health=HealthPolicy()) as eng:
        with pytest.raises(RhsNonFinite, match="admission"):
            eng.submit(session, bad)
        # the reject consumed no pending slot
        assert eng.stats()["pending"] == 0
        good = _rhs(12)
        np.testing.assert_allclose(
            eng.solve(session, good, timeout=60),
            np.asarray(session.solve(good)), rtol=1e-5, atol=1e-6)
    assert resilience.health_stats()["rhs_rejects"] \
        - h0["rhs_rejects"] == 1


def test_staging_nan_isolates_to_one_future_bitwise_survivors():
    """The acceptance contract: a request poisoned AFTER admission (the
    seeded staging fault) fails its own future; the co-batched requests
    in the SAME window get bitwise the answers they would have gotten
    alone."""
    session = _session(13)
    bs = [_rhs(20 + i, w) for i, w in enumerate((2, 2, 1))]
    direct = [np.asarray(session.solve(b)) for b in bs]
    faults = FaultPlan([FaultSpec("staging", "nan", count=1)])
    h0 = resilience.health_stats()
    eng = ServeEngine(max_batch_delay=60.0, health=HealthPolicy(),
                      fault_plan=faults)
    futs = [eng.submit(session, b) for b in bs]  # one window, one batch
    assert eng.close(timeout=120) == []
    with pytest.raises(RhsNonFinite, match="staging"):
        futs[0].result(0)  # the poisoned request fails ALONE
    for f, d in zip(futs[1:], direct[1:]):  # survivors: bitwise
        np.testing.assert_array_equal(np.asarray(f.result(0)), d)
    dh = _delta(h0, resilience.health_stats())
    assert dh["staging_isolations"] == 1
    assert dh["faults_injected"] == 1
    assert faults.injected[("staging", "nan")] == 1


def test_drain_crash_redispatches_survivors():
    """Satellite: a batch-attributable drain failure routes through solo
    survivor re-dispatch — every innocent request still gets its answer,
    and the worker thread survives to serve later traffic."""
    session = _session(17)
    bs = [_rhs(30 + i, 2) for i in range(3)]
    direct = [np.asarray(session.solve(b)) for b in bs]
    faults = FaultPlan([FaultSpec("drain", "crash", count=1)])
    h0 = resilience.health_stats()
    eng = ServeEngine(max_batch_delay=60.0, fault_plan=faults)
    futs = [eng.submit(session, b) for b in bs]
    # close() dispatches the window; the crash hits at drain; all three
    # requests recover through solo re-dispatch
    assert eng.close(timeout=120) == []
    for f, d in zip(futs, direct):
        np.testing.assert_array_equal(np.asarray(f.result(0)), d)
    assert _delta(h0, resilience.health_stats())[
        "survivor_redispatches"] == 3


# --------------------------------------------------------------------- #
# output health + the escalation ladder
# --------------------------------------------------------------------- #


def test_drift_health_failure_one_refactor_escalation_then_succeeds():
    """The acceptance contract: an injected health failure on a DRIFTED
    solve climbs exactly one rung — a forced refactor through the
    plan's cached factor program — and the retried answer is healthy
    and correct against the drifted oracle."""
    session = _session(19)
    A = _system(19)
    rng = np.random.default_rng(91)
    U = (0.01 * rng.standard_normal((N, 2))).astype(np.float32)
    Vm = (0.01 * rng.standard_normal((N, 2))).astype(np.float32)
    session.update(U, Vm)
    assert session.update_rank == 2 and session.refactors == 0
    b = _rhs(92, 2)
    faults = FaultPlan([FaultSpec("solve", "unhealthy", count=1)])
    h0 = resilience.health_stats()
    trace0 = dict(session.plan.trace_counts)
    with ServeEngine(max_batch_delay=0.0, health=HealthPolicy(),
                     fault_plan=faults) as eng:
        x = eng.solve(session, b, timeout=120)
    dh = _delta(h0, resilience.health_stats())
    assert dh["output_failures"] == 1
    assert dh["refactor_escalations"] == 1
    assert "refine_escalations" not in dh and "unhealthy" not in dh
    assert session.refactors == 1 and session.update_rank == 0
    # the escalation rode the CACHED factor program — no new factor trace
    assert session.plan.trace_counts["factor"] == trace0["factor"]
    oracle = np.linalg.solve(A + U @ Vm.T, b)
    np.testing.assert_allclose(np.asarray(x), oracle, rtol=2e-3,
                               atol=1e-4)


def test_ladder_exhaustion_raises_structured_solve_unhealthy():
    session = _session(23)
    b = _rhs(94, 1)
    # initial verdict + refactor rung + refine rung all forced unhealthy
    faults = FaultPlan([FaultSpec("solve", "unhealthy", count=3)])
    h0 = resilience.health_stats()
    with ServeEngine(max_batch_delay=0.0, health=HealthPolicy(),
                     fault_plan=faults) as eng:
        fut = eng.submit(session, b)
        with pytest.raises(SolveUnhealthy) as ei:
            fut.result(120)
    ev = ei.value.evidence
    assert [r["rung"] for r in ev["rungs"]] == \
        ["dispatch", "refactor", "refine"]
    assert ev["residual_limit"] > 0 and ev["update_rank"] == 0
    dh = _delta(h0, resilience.health_stats())
    assert dh["unhealthy"] == 1
    assert dh["refactor_escalations"] == 1
    assert dh["refine_escalations"] == 1


def test_unhealthy_batch_isolates_then_survivors_answer():
    """A forced-unhealthy verdict on a MULTI-request batch first
    isolates (solo re-dispatch); the re-checks pass, so every request
    answers — no collateral failures from one bad verdict."""
    session = _session(27)
    bs = [_rhs(40 + i, 1) for i in range(3)]
    direct = [np.asarray(session.solve(b)) for b in bs]
    faults = FaultPlan([FaultSpec("solve", "unhealthy", count=1)])
    h0 = resilience.health_stats()
    eng = ServeEngine(max_batch_delay=60.0, health=HealthPolicy(),
                      fault_plan=faults)
    futs = [eng.submit(session, b) for b in bs]
    assert eng.close(timeout=120) == []
    for f, d in zip(futs, direct):
        np.testing.assert_array_equal(np.asarray(f.result(0)), d)
    dh = _delta(h0, resilience.health_stats())
    assert dh["output_failures"] == 1
    assert dh["survivor_redispatches"] == 3


# --------------------------------------------------------------------- #
# deadlines + backpressure hints
# --------------------------------------------------------------------- #


def test_deadline_evicts_mid_window():
    session = _session(29)
    h0 = resilience.health_stats()
    eng = ServeEngine(max_batch_delay=60.0)
    t0 = time.perf_counter()
    fut = eng.submit(session, _rhs(95), deadline=0.1)
    with pytest.raises(DeadlineExceeded, match="slot released"):
        fut.result(30)
    # evicted when the deadline passed, not when the 60s window closed
    assert time.perf_counter() - t0 < 30
    assert eng.stats()["pending"] == 0
    assert eng.close(timeout=60) == []
    assert _delta(h0, resilience.health_stats())["evictions"] == 1


def test_deadline_eviction_frees_slots_under_block():
    """The acceptance contract: expired requests release their pending
    slots, so a blocked submitter gets through instead of deadlocking
    behind abandoned work."""
    session = _session(31)
    b = _rhs(96)
    eng = ServeEngine(max_batch_delay=60.0, max_pending=2,
                      on_full="block")
    f1 = eng.submit(session, b, deadline=0.0)   # already expired:
    f2 = eng.submit(session, b, deadline=0.0)   # lazy eviction fodder
    got = []
    t = threading.Thread(target=lambda: got.append(eng.submit(session, b)))
    t.start()
    t.join(timeout=60)
    assert not t.is_alive(), "eviction did not release the blocked slot"
    for f in (f1, f2):
        with pytest.raises(DeadlineExceeded):
            f.result(30)
    assert eng.close(timeout=120) == []
    np.testing.assert_array_equal(np.asarray(got[0].result(0)),
                                  np.asarray(session.solve(b)))


def test_saturated_carries_growing_backoff_hint():
    session = _session(37)
    b = _rhs(97)
    eng = ServeEngine(max_batch_delay=60.0, max_pending=1)
    eng.submit(session, b)
    hints = []
    for _ in range(3):
        with pytest.raises(EngineSaturated) as ei:
            eng.submit(session, b)
        assert "retry in" in str(ei.value)
        hints.append(ei.value.retry_after)
    assert hints[0] > 0 and hints[1] == 2 * hints[0] \
        and hints[2] == 2 * hints[1]
    assert eng.close(timeout=60) == []


# --------------------------------------------------------------------- #
# quarantine circuit breaker
# --------------------------------------------------------------------- #


def test_quarantine_opens_and_half_open_recovers():
    """The breaker opens after `quarantine_after` consecutive ladder
    failures (fast SessionQuarantined, no device work), admits one probe
    after the cooldown, and a healthy probe closes the circuit."""
    session = _session(41)
    b = _rhs(98, 1)
    # one full ladder loss: initial + refactor + refine verdicts forced
    faults = FaultPlan([FaultSpec("solve", "unhealthy", count=3)])
    policy = HealthPolicy(quarantine_after=1, quarantine_cooldown=30.0)
    h0 = resilience.health_stats()
    eng = ServeEngine(max_batch_delay=0.0, health=policy,
                      fault_plan=faults)
    with pytest.raises(SolveUnhealthy):
        eng.submit(session, b).result(120)
    assert session._breaker.state == "open"
    with pytest.raises(SessionQuarantined) as ei:
        eng.submit(session, b)
    assert ei.value.retry_after > 0
    # cooldown elapses (deterministically — no sleep): half-open probe
    session._breaker.cooldown = 0.0
    x = eng.solve(session, b, timeout=120)   # the probe, now healthy
    assert session._breaker.state == "closed"
    np.testing.assert_array_equal(np.asarray(x),
                                  np.asarray(session.solve(b)))
    assert eng.close(timeout=60) == []
    dh = _delta(h0, resilience.health_stats())
    assert dh["quarantine_opened"] == 1
    assert dh["quarantine_probes"] >= 1
    assert dh["quarantine_recoveries"] == 1


def test_breaker_sick_probe_reopens():
    clock = [0.0]
    br = CircuitBreaker(threshold=2, cooldown=10.0,
                        clock=lambda: clock[0])
    assert br.allow() == (True, 0.0)
    br.record_failure()
    br.record_failure()
    assert br.state == "open"
    ok, retry = br.allow()
    assert not ok and retry == pytest.approx(10.0)
    clock[0] = 11.0
    assert br.allow()[0]            # the half-open probe
    assert not br.allow()[0]        # only ONE probe per window
    br.record_failure()             # sick probe: straight back open
    assert br.state == "open"
    clock[0] = 22.0
    assert br.allow()[0]
    br.record_success()
    assert br.state == "closed"


# --------------------------------------------------------------------- #
# watchdog + close(timeout)
# --------------------------------------------------------------------- #


def test_watchdog_fails_pending_when_worker_dies():
    session = _session(43)
    faults = FaultPlan([FaultSpec("drain", "kill", count=1)])
    h0 = resilience.health_stats()
    eng = ServeEngine(max_batch_delay=0.0, fault_plan=faults,
                      watchdog_interval=0.05)
    fut = eng.submit(session, _rhs(99))
    with pytest.raises(EngineClosed, match="died"):
        fut.result(60)
    with pytest.raises(EngineClosed):
        eng.submit(session, _rhs(99))
    assert _delta(h0, resilience.health_stats())["watchdog_trips"] >= 1
    eng.close(timeout=10)


def test_close_timeout_reports_wedged_thread_and_fails_pending():
    """Satellite: a wedged close() names the stuck worker and fails the
    still-pending futures with EngineClosed instead of leaving them
    hanging forever — and the wedged thread waking up later cannot
    double-resolve them (resolution ownership)."""
    session = _session(47)
    faults = FaultPlan([FaultSpec("dispatch", "delay", delay_s=1.5,
                                  count=1)])
    eng = ServeEngine(max_batch_delay=0.0, fault_plan=faults)
    fut = eng.submit(session, _rhs(100))
    time.sleep(0.05)  # let the dispatcher enter the injected sleep
    wedged = eng.close(timeout=0.2)
    assert "serve-engine-dispatch" in wedged
    with pytest.raises(EngineClosed, match="wedged"):
        fut.result(10)
    time.sleep(1.6)  # wedged worker wakes; must not double-resolve
    with pytest.raises(EngineClosed):
        fut.result(0)


# --------------------------------------------------------------------- #
# clean path + thread hammer + observability
# --------------------------------------------------------------------- #


def test_guarded_clean_path_zero_compiles_after_prewarm():
    """Clean traffic through the full HealthPolicy: zero compiles after
    prewarm, the guards stay SILENT (a false positive is an escalation:
    right answers, wasted device work), and the guarded answers are the
    direct solves'."""
    session = _session(53)
    plan = session.plan
    reqs = [_rhs(50 + i, 1 + i % 2) for i in range(12)]
    h0 = resilience.health_stats()
    with ServeEngine(max_batch_delay=0.02, max_coalesce_width=4,
                     health=HealthPolicy()) as eng:
        eng.prewarm(session, widths=(1, 2, 4))
        snapshot = dict(plan.trace_counts)
        futs = [eng.submit(session, b) for b in reqs]
        xs = [np.asarray(f.result(timeout=60)) for f in futs]
        assert plan.trace_counts == snapshot, \
            "guarded steady-state traffic compiled after prewarm"
    trips = {k: v for k, v in _delta(h0, resilience.health_stats()).items()
             if k in ("output_failures", "staging_isolations",
                      "rhs_rejects", "unhealthy", "refactor_escalations",
                      "refine_escalations", "survivor_redispatches")}
    assert not trips, f"guards tripped on clean traffic: {trips}"
    for i, (x, b) in enumerate(zip(xs, reqs)):
        np.testing.assert_allclose(x, np.asarray(session.solve(b)),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"request {i}")


def test_thread_hammer_every_future_resolves():
    """Chaos hammer: concurrent submitters, mixed clean / poisoned /
    zero-deadline traffic, staging faults injected — every future
    resolves (an answer or a structured resilience error), no request
    leaks a slot, clean answers match direct solves."""
    sessions = [_session(59), ]
    plan = sessions[0].plan
    sessions.append(plan.factor(jnp.asarray(_system(61))))
    faults = FaultPlan([FaultSpec("staging", "nan", prob=0.2, count=4),
                        FaultSpec("drain", "crash", count=1)], seed=7)
    eng = ServeEngine(max_batch_delay=0.001, health=HealthPolicy(),
                      fault_plan=faults)
    results: list = []
    lock = threading.Lock()
    errs: list = []

    def pump(tid):
        rng = np.random.default_rng(tid)
        for i in range(10):
            s = sessions[(tid + i) % 2]
            kind = i % 5
            try:
                if kind == 3:  # poisoned at the source
                    bad = rng.standard_normal((N, 1)).astype(np.float32)
                    bad[0, 0] = np.nan
                    fut, b = eng.submit(s, bad), None
                elif kind == 4:  # born expired
                    b = rng.standard_normal(N).astype(np.float32)
                    fut = eng.submit(s, b, deadline=0.0)
                else:
                    b = rng.standard_normal(
                        (N, 1 + i % 2)).astype(np.float32)
                    fut = eng.submit(s, b)
            except (RhsNonFinite, SessionQuarantined):
                continue
            with lock:
                results.append((s, b, kind, fut))

    threads = [threading.Thread(target=pump, args=(t,)) for t in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "hammer submitter wedged"
    assert eng.close(timeout=300) == []
    ok_kinds = (RhsNonFinite, DeadlineExceeded, SolveUnhealthy,
                SessionQuarantined)
    for s, b, kind, fut in results:
        assert fut.done(), "close() left a future unresolved"
        try:
            x = fut.result(0)
        except ok_kinds:
            continue
        except Exception as e:  # noqa: BLE001
            errs.append(e)
            continue
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(s.solve(b)), rtol=1e-4, atol=1e-5,
            err_msg=f"kind={kind}")
    assert not errs, f"unstructured failures leaked: {errs[:3]}"
    stats = eng.stats()
    assert stats["pending"] == 0
    assert stats["completed"] + stats["failed"] == stats["requests"]


def test_health_counters_in_serve_stats():
    session = _session(67)
    bad = _rhs(101)
    bad[0, 0] = np.nan
    with ServeEngine(max_batch_delay=0.0, health=HealthPolicy()) as eng:
        with pytest.raises(RhsNonFinite):
            eng.submit(session, bad)
        eng.solve(session, _rhs(102), timeout=60)
    stats = profiler.serve_stats()
    assert set(resilience._HEALTH_KEYS) <= set(stats["health"])
    assert stats["health"]["rhs_rejects"] >= 1
    # the health counters are global like the region tables: clear()
    # resets them (engine counters, living on engines, survive)
    profiler.clear()
    stats = profiler.serve_stats()
    assert stats["health"]["rhs_rejects"] == 0
    assert stats["engine"]["requests"] >= 1


def test_cond_guard_trip_counts_into_health():
    session = _session(71)
    rng = np.random.default_rng(103)
    U = rng.standard_normal((N, 2)).astype(np.float32)
    h0 = resilience.health_stats()
    session.policy = dataclasses.replace(session.policy,
                                         cond_limit=1.0 + 1e-9)
    session.update(U, U)  # cond(C) > 1 for any real drift: guard trips
    assert session.refactors == 1
    assert _delta(h0, resilience.health_stats())["cond_refactors"] == 1


def test_fault_spec_validation_and_determinism():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec("nowhere", "nan")
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("staging", "meteor")
    a = FaultPlan([FaultSpec("dispatch", "delay", prob=0.5,
                             delay_s=0.0)], seed=3)
    b = FaultPlan([FaultSpec("dispatch", "delay", prob=0.5,
                             delay_s=0.0)], seed=3)
    fires = [(a.fire("dispatch") is not None,
              b.fire("dispatch") is not None) for _ in range(64)]
    assert all(x == y for x, y in fires), "seeded streams diverged"
    assert any(x for x, _ in fires) and not all(x for x, _ in fires)
