"""Adaptive-controller tests: the ISSUE 8 acceptance contracts.

- `controller=None` engines are byte-identical to the pre-controller
  engine (no thread, no stats key, bitwise answers).
- Each decision block steers its knob the right way, driven
  deterministically through `AdaptiveController.step()` with synthetic
  telemetry windows (no timing, no sleeps).
- Knob moves are prewarm-gated: the width cap grows only after the
  target bucket's program is warm on every active plan, and moves never
  compile anything.
- `EngineSaturated.retry_after` rides the measured drain rate when an
  estimate exists and falls back to the exponential guess otherwise.
- Guard relaxation backs off sampling only after a clean streak and
  restores INSTANTLY (engine-side) on any trip.
- `FactorPlan.release_buckets` drops retired bucket programs (and only
  them) — grow-then-shrink leaves no stale programs.
- The windowed profiler API: per-window deltas are consistent under
  concurrent writers, `clear()` keeps its semantics, and cumulative
  consumers are unchanged.
"""

import threading

import numpy as np
import pytest

import jax.numpy as jnp

from conflux_tpu import profiler, resilience, serve
from conflux_tpu.control import AdaptiveController, ControlLimits
from conflux_tpu.engine import EngineSaturated, ServeEngine
from conflux_tpu.resilience import HealthPolicy, RhsNonFinite

N, V = 32, 16


def _session(seed=0, v=V):
    rng = np.random.default_rng(seed)
    A = (rng.standard_normal((N, N)) / np.sqrt(N)
         + 2.0 * np.eye(N)).astype(np.float32)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=v)
    return plan, plan.factor(jnp.asarray(A))


class _FakeWindow:
    """A scripted StatsWindow: yields each delta once, then repeats the
    last — deterministic telemetry for step()-driven tests."""

    def __init__(self, deltas):
        self.deltas = list(deltas)

    def delta(self):
        if len(self.deltas) > 1:
            return self.deltas.pop(0)
        return self.deltas[0]


def _ctl(eng, **kw):
    kw.setdefault("slo_p99_ms", 25.0)
    kw.setdefault("interval", 60.0)  # never ticks on its own
    ctl = AdaptiveController(**kw)
    ctl.attach(eng)
    return ctl


# --------------------------------------------------------------------- #
# opt-in contract
# --------------------------------------------------------------------- #


def test_controller_none_default_unchanged():
    """No controller: no thread, no stats key, bitwise answers."""
    serve.clear_plans()
    _plan, s = _session(seed=3)
    b = np.ones((N, 1), np.float32)
    before = {t.name for t in threading.enumerate()}
    with ServeEngine(max_batch_delay=0.01) as eng:
        x = np.asarray(eng.solve(s, b, timeout=60))
        st = eng.stats()
    assert "controller" not in st
    assert "serve-engine-controller" not in before
    np.testing.assert_array_equal(x, np.asarray(s.solve(b)))


def test_controller_lifecycle_and_stats():
    serve.clear_plans()
    _plan, s = _session(seed=5)
    ctl = AdaptiveController(slo_p99_ms=25.0, interval=0.01)
    eng = ServeEngine(max_batch_delay=0.0, controller=ctl)
    try:
        b = np.ones((N, 1), np.float32)
        eng.solve(s, b, timeout=60)
        deadline = threading.Event()
        for _ in range(200):  # wait for a couple of real ticks
            if ctl.stats()["ticks"] >= 2:
                break
            deadline.wait(0.01)
        # clean traffic while the controller ticks: every future
        # answers, and answers the direct solve
        bs = [np.full((N, 1), float(i + 1), np.float32) for i in range(16)]
        futs = [eng.submit(s, bi) for bi in bs]
        for f, bi in zip(futs, bs):
            np.testing.assert_array_equal(np.asarray(f.result(timeout=60)),
                                          np.asarray(s.solve(bi)))
        st = eng.stats()
        assert st["controller"]["ticks"] >= 2
        assert st["controller"]["errors"] == 0
        assert st["failed"] == 0
        assert st["knobs"]["max_batch_delay"] == eng.max_batch_delay
    finally:
        eng.close(timeout=60)
    assert not ctl._thread.is_alive(), "close() left the controller running"
    eng.close()  # idempotent with the controller attached


def test_attach_twice_raises():
    serve.clear_plans()
    with ServeEngine(max_batch_delay=0.0) as e1, \
            ServeEngine(max_batch_delay=0.0) as e2:
        ctl = AdaptiveController()
        ctl.attach(e1)
        with pytest.raises(RuntimeError, match="already attached"):
            ctl.attach(e2)


# --------------------------------------------------------------------- #
# knob setters + retry_after
# --------------------------------------------------------------------- #


def test_set_knobs_validates_and_buckets():
    serve.clear_plans()
    with ServeEngine(max_batch_delay=0.002) as eng:
        with pytest.raises(ValueError, match="max_batch_delay"):
            eng.set_knobs(max_batch_delay=-1.0)
        with pytest.raises(ValueError, match=">= 1"):
            eng.set_knobs(max_pending=0)
        with pytest.raises(ValueError, match="staging_stride"):
            eng.set_knobs(staging_stride=0)
        k = eng.set_knobs(max_batch_delay=0.004, max_pending=99,
                          max_factor_batch=9)
        assert k["max_batch_delay"] == 0.004
        assert k["max_pending"] == 99
        assert k["max_factor_batch"] == 16  # rounds to its pow2 bucket
        assert eng.knobs() == k


def test_retry_after_measured_drain_rate_with_fallback():
    """The satellite: shed hints ride the measured drain rate when one
    exists; the exponential guess is the no-estimate fallback."""
    serve.clear_plans()
    _plan, s = _session(seed=7)
    b = np.ones(N, np.float32)
    # a huge window parks the dispatcher so the bound trips reliably
    eng = ServeEngine(max_batch_delay=60.0, max_pending=2)
    try:
        eng.submit(s, b)
        eng.submit(s, b)
        with pytest.raises(EngineSaturated, match="backoff") as ei:
            eng.submit(s, b)
        assert ei.value.retry_after == pytest.approx(1e-3)  # 2^0 ms
        eng.set_knobs(drain_rate=100.0)
        with pytest.raises(EngineSaturated, match="drain rate") as ei:
            eng.submit(s, b)
        # second consecutive shed at 100/s drain: 2 drain intervals
        assert ei.value.retry_after == pytest.approx(2 / 100.0)
    finally:
        eng.close(timeout=60)


# --------------------------------------------------------------------- #
# decision blocks (deterministic: scripted windows through step())
# --------------------------------------------------------------------- #


def test_delay_shrinks_when_p99_near_slo():
    serve.clear_plans()
    with ServeEngine(max_batch_delay=0.008) as eng:
        ctl = _ctl(eng)
        d = AdaptiveController.blank_delta()
        d["engine"].update(latency_samples=64, latency_p99_ms=24.0,
                           requests=64, completed=64, batches=8,
                           coalesced_requests=64, coalesced_mean=8.0)
        ctl._window = _FakeWindow([d])
        ctl.step()
        assert eng.max_batch_delay == pytest.approx(0.004)
        ctl.step()  # still near the SLO: keeps shrinking
        assert eng.max_batch_delay == pytest.approx(0.002)
        log = ctl.stats()["decisions_log"]
        assert any(e["knob"] == "max_batch_delay" and "shrink" in e["reason"]
                   for e in log)


def test_delay_widens_when_under_coalesced_and_backlogged():
    serve.clear_plans()
    with ServeEngine(max_batch_delay=0.0) as eng:
        ctl = _ctl(eng)
        d = AdaptiveController.blank_delta()
        d["engine"].update(latency_samples=64, latency_p99_ms=3.0,
                           requests=100, completed=60, batches=60,
                           coalesced_requests=60, coalesced_mean=1.0,
                           backlog_delta=40, pending=40)
        ctl._window = _FakeWindow([d])
        ctl.step()  # one window of pressure is a clump, not a regime
        assert eng.max_batch_delay == 0.0
        ctl.step()  # two consecutive: widen
        first = eng.max_batch_delay
        assert first > 0.0  # seeded out of the zero window
        ctl.step()
        assert eng.max_batch_delay > first  # multiplicative climb
        assert eng.max_batch_delay <= ctl.limits.max_batch_delay


def test_delay_decays_on_light_solo_traffic():
    serve.clear_plans()
    with ServeEngine(max_batch_delay=0.008) as eng:
        ctl = _ctl(eng)
        d = AdaptiveController.blank_delta()
        d["engine"].update(latency_samples=10, latency_p99_ms=9.0,
                           requests=10, completed=10, batches=10,
                           coalesced_requests=10, coalesced_mean=1.0,
                           backlog_delta=0, pending=0)
        ctl._window = _FakeWindow([d])
        ctl.step()
        assert eng.max_batch_delay == pytest.approx(0.004)


def test_max_pending_sized_from_drain_rate_with_deadband():
    serve.clear_plans()
    with ServeEngine(max_batch_delay=0.0, max_pending=1024) as eng:
        ctl = _ctl(eng, pending_slack=1.5)
        d = AdaptiveController.blank_delta(seconds=1.0)
        d["engine"].update(requests=1000, completed=1000, batches=100,
                           coalesced_requests=1000, coalesced_mean=10.0,
                           latency_samples=100, latency_p99_ms=5.0)
        ctl._window = _FakeWindow([d])
        ctl.step()
        # 1000/s drain x 25ms SLO x 1.5 slack = 37 (above the floor)
        assert eng.max_pending == 37
        assert eng.knobs()["drain_rate"] == pytest.approx(1000.0)
        before = eng.max_pending
        ctl.step()  # identical window: inside the deadband, no thrash
        assert eng.max_pending == before
        decisions = [e for e in ctl.stats()["decisions_log"]
                     if e["knob"] == "max_pending"]
        assert len(decisions) == 1


def test_width_growth_is_prewarm_gated_and_compile_free_at_switch():
    serve.clear_plans()
    plan, s = _session(seed=11)
    with ServeEngine(max_batch_delay=0.0, max_coalesce_width=4) as eng:
        eng.prewarm(s, widths=(1, 2, 4))
        b = np.ones((N, 1), np.float32)
        eng.solve(s, b, timeout=60)  # registers the session
        ctl = _ctl(eng, grow_after=1,
                   limits=ControlLimits(max_coalesce_width=8))
        d = AdaptiveController.blank_delta()
        d["engine"].update(requests=50, completed=50, batches=20,
                           coalesced_requests=50, coalesced_mean=2.5,
                           width_capped=10, latency_samples=50,
                           latency_p99_ms=2.0)
        ctl._window = _FakeWindow([d])
        assert not plan.bucket_ready(width=8)
        ctl.step()  # launches the background prewarm; cap must NOT move
        assert eng.max_coalesce_width == 4
        pre = ctl._width_prewarm
        assert pre is not None and pre[0] == 8
        pre[1].join(timeout=120)
        assert plan.bucket_ready(width=8), "prewarm did not warm bucket 8"
        snapshot = dict(plan.trace_counts)
        ctl.step()  # prewarm complete -> the cap moves, compiling nothing
        assert eng.max_coalesce_width == 8
        assert plan.trace_counts == snapshot, \
            "the knob move itself compiled a program"
        # and traffic at the new cap rides the warm bucket: still zero
        futs = [eng.submit(s, b) for _ in range(8)]
        for f in futs:
            f.result(timeout=60)
        assert plan.trace_counts == snapshot


def test_width_retirement_releases_cold_bucket_programs():
    serve.clear_plans()
    plan, s = _session(seed=13)
    with ServeEngine(max_batch_delay=0.0, max_coalesce_width=4) as eng:
        rng = np.random.default_rng(13)
        for w in (1, 4):
            eng.solve(s, rng.standard_normal((N, w)).astype(np.float32),
                      timeout=60)
        assert {1, 4} <= set(plan._solve_cache)
        ctl = _ctl(eng, retire_after=2)
        hot = AdaptiveController.blank_delta()
        hot["engine"].update(requests=6, completed=6, batches=6,
                             coalesced_requests=6, coalesced_mean=1.0)
        hot["bucket_hits"] = {1: 3, 4: 3}
        cold = AdaptiveController.blank_delta()
        cold["engine"].update(requests=3, completed=3, batches=3,
                              coalesced_requests=3, coalesced_mean=1.0)
        cold["bucket_hits"] = {1: 3}
        ctl._window = _FakeWindow([hot, cold])
        ctl.step()            # both buckets hot
        assert 4 in plan._solve_cache
        ctl.step()            # bucket 4 cold x1
        assert 4 in plan._solve_cache
        ctl.step()            # cold x2 == retire_after -> retired
        assert 4 not in plan._solve_cache
        assert 1 in plan._solve_cache
        assert eng.max_coalesce_width == 1  # cap follows live traffic
        # retirement is eviction, not prohibition: a late wide request
        # still answers (paying one re-trace)
        x = np.asarray(eng.solve(
            s, rng.standard_normal((N, 4)).astype(np.float32), timeout=60))
        assert x.shape == (N, 4)


def test_health_relaxes_after_calm_and_restores_instantly_on_trip():
    serve.clear_plans()
    _plan, s = _session(seed=17)
    strict = HealthPolicy(submit_guard_sample=4096)
    with ServeEngine(max_batch_delay=0.0, health=strict) as eng:
        eng.prewarm(s, widths=(1,))
        ctl = _ctl(eng, relax_health_after=3)
        ctl._window = _FakeWindow([AdaptiveController.blank_delta()])
        for _ in range(3):
            assert eng.health is strict
            ctl.step()
        assert eng.health is not strict
        assert eng.health.submit_guard_sample == \
            ctl.limits.relaxed_guard_sample
        assert eng._staging_stride == ctl.limits.staging_stride
        assert ctl.stats()["relaxed_guards"] is True
        # ANY trip restores full guarding on the tripping thread — the
        # engine does not wait for a controller tick
        bad = np.ones(N, np.float32)
        bad[0] = np.nan
        with pytest.raises(RhsNonFinite):
            eng.submit(s, bad)
        assert eng.health is strict
        assert eng._staging_stride == 1
        # the next window reports the trip; the controller re-syncs
        tripped = AdaptiveController.blank_delta()
        tripped["health"] = {"rhs_rejects": 1}
        ctl._window = _FakeWindow([tripped])
        ctl.step()
        assert ctl.stats()["relaxed_guards"] is False
        # good traffic still answers under the restored strict policy
        good = np.ones(N, np.float32)
        np.testing.assert_array_equal(
            np.asarray(eng.solve(s, good, timeout=60)),
            np.asarray(s.solve(good)))


def test_knob_moves_compile_nothing():
    serve.clear_plans()
    plan, s = _session(seed=19)
    with ServeEngine(max_batch_delay=0.002, max_coalesce_width=4) as eng:
        eng.prewarm(s, widths=(1, 2, 4))
        b = np.ones((N, 1), np.float32)
        eng.solve(s, b, timeout=60)
        snapshot = dict(plan.trace_counts)
        ctl = _ctl(eng)
        busy = AdaptiveController.blank_delta()
        busy["engine"].update(requests=100, completed=60, batches=60,
                              coalesced_requests=60, coalesced_mean=1.0,
                              backlog_delta=40, pending=40,
                              latency_samples=60, latency_p99_ms=30.0)
        ctl._window = _FakeWindow([busy])
        for _ in range(4):
            ctl.step()  # delay + pending moves under pressure
        futs = [eng.submit(s, b) for _ in range(8)]
        for f in futs:
            f.result(timeout=60)
        assert plan.trace_counts == snapshot, \
            "knob moves (or traffic after them) compiled a program"


# --------------------------------------------------------------------- #
# FactorPlan.release_buckets (the grow-then-shrink satellite)
# --------------------------------------------------------------------- #


def test_release_buckets_grow_then_shrink_leaves_no_stale_programs():
    serve.clear_plans()
    plan, s = _session(seed=23)
    rng = np.random.default_rng(23)
    for w in (1, 2, 4, 8):
        s.solve(jnp.asarray(rng.standard_normal((N, w)).astype(np.float32)))
    assert set(plan._solve_cache) == {1, 2, 4, 8}
    dropped = plan.release_buckets(widths=(4, 8))
    assert dropped == 2
    assert set(plan._solve_cache) == {1, 2}
    assert plan.release_buckets(widths=(4, 8)) == 0  # idempotent
    # checked programs and the probe: only the released bucket's
    # programs go; the probe program is not a bucket and survives.
    # Blocked (default) plans keep their fused-probe checked programs
    # in the dedicated _trsm_cache (DESIGN §27) — released with the
    # width bucket all the same
    s.solve_checked(jnp.asarray(np.ones(N, np.float32)))
    assert ("health", 1) in plan._trsm_cache
    assert ("health", 1) not in plan._solve_cache
    assert ("probe",) in plan._solve_cache
    plan.release_buckets(widths=(1,))
    assert ("health", 1) not in plan._trsm_cache
    assert 1 not in plan._solve_cache
    assert ("probe",) in plan._solve_cache
    # factor lane: stacked buckets release; bucket 1 is plan.factor's
    # own path and is refused
    plan._stacked_factor_fn(2)
    assert ("factor", 2) in plan._factor_cache
    assert plan.release_buckets(factor_batches=(2,)) == 1
    assert ("factor", 2) not in plan._factor_cache
    with pytest.raises(ValueError, match="bucket 1"):
        plan.release_buckets(factor_batches=(1,))
    # a released width still answers (re-traced, not forbidden)
    x = np.asarray(s.solve(jnp.asarray(
        rng.standard_normal((N, 8)).astype(np.float32))))
    assert x.shape == (N, 8)


def test_bucket_ready_reflects_warmth():
    serve.clear_plans()
    plan, s = _session(seed=29)
    assert not plan.bucket_ready(width=2)
    assert not plan.bucket_ready()  # nothing asked -> not ready
    s.solve(jnp.asarray(np.ones((N, 2), np.float32)))
    assert plan.bucket_ready(width=2)
    assert not plan.bucket_ready(width=2, checked=True)
    s.solve_checked(jnp.asarray(np.ones((N, 2), np.float32)))
    assert plan.bucket_ready(width=2, checked=True)
    assert not plan.bucket_ready(factor_batch=2)
    plan._stacked_factor_fn(2)  # built but never called: NOT ready
    assert not plan.bucket_ready(factor_batch=2)


# --------------------------------------------------------------------- #
# the windowed profiler API
# --------------------------------------------------------------------- #


def test_stats_window_engine_deltas_and_tokens():
    serve.clear_plans()
    _plan, s = _session(seed=31)
    b = np.ones((N, 1), np.float32)
    with ServeEngine(max_batch_delay=0.0) as eng:
        for f in [eng.submit(s, b) for _ in range(4)]:
            f.result(timeout=60)
        w = profiler.StatsWindow(eng)  # baseline AFTER the first 4
        for f in [eng.submit(s, b) for _ in range(3)]:
            f.result(timeout=60)
        d = w.delta()
        assert d["engine"]["completed"] == 3
        assert d["engine"]["latency_samples"] == 3
        assert d["engine"]["latency_p50_ms"] > 0.0
        assert d["engine"]["requests"] == 3
        d2 = w.delta()  # empty window
        assert d2["engine"]["completed"] == 0
        assert d2["engine"]["latency_samples"] == 0
        assert d2["engine"]["latency_p99_ms"] == 0.0
        # cumulative consumers are untouched by windowing
        assert eng.stats()["completed"] == 7


def test_stats_window_concurrent_writers_sum_to_cumulative():
    """Thread-hammer: windows taken WHILE workers bump the shared
    telemetry never lose or double-count — the window deltas sum to
    exactly the cumulative difference."""
    profiler.clear()
    w = profiler.StatsWindow()
    h0 = resilience.health_stats()["rhs_rejects"]
    c0 = profiler.serve_stats()["solve"]["count"]
    PER, WORKERS = 200, 4
    stop = threading.Event()
    sums = {"rhs_rejects": 0, "solve": 0}

    def hammer():
        for _ in range(PER):
            resilience.bump("rhs_rejects")
            with profiler.region("serve.solve"):
                pass

    def window_taker():
        while not stop.is_set():
            d = w.delta()
            sums["rhs_rejects"] += d["health"].get("rhs_rejects", 0)
            sums["solve"] += d["phases"]["solve"]["count"]

    ts = [threading.Thread(target=hammer) for _ in range(WORKERS)]
    taker = threading.Thread(target=window_taker)
    taker.start()
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    stop.set()
    taker.join(timeout=120)
    d = w.delta()  # the tail window
    sums["rhs_rejects"] += d["health"].get("rhs_rejects", 0)
    sums["solve"] += d["phases"]["solve"]["count"]
    total = WORKERS * PER
    assert sums["rhs_rejects"] == total
    assert sums["solve"] == total
    # cumulative consumers unchanged by any of it
    assert resilience.health_stats()["rhs_rejects"] - h0 == total
    assert profiler.serve_stats()["solve"]["count"] - c0 == total
    profiler.clear()


def test_stats_window_clear_clamps_not_negates():
    """profiler.clear() mid-window: the next delta reports the
    post-clear counts (clamped at zero), never negatives, and clear()'s
    cumulative semantics are preserved."""
    profiler.clear()
    w = profiler.StatsWindow()
    for _ in range(5):
        resilience.bump("rhs_rejects")
    assert w.delta()["health"]["rhs_rejects"] == 5
    for _ in range(3):
        resilience.bump("rhs_rejects")
    profiler.clear()
    for _ in range(2):
        resilience.bump("rhs_rejects")
    d = w.delta()
    assert d["health"]["rhs_rejects"] == 2  # post-clear counts
    assert all(v >= 0 for v in d["health"].values())
    assert resilience.health_stats()["rhs_rejects"] == 2
    profiler.clear()
