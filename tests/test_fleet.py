"""Mesh-sharded serve fleet (ISSUE 9): per-device lanes behind one
admission front.

- Placement is DETERMINISTIC: `place_session` is a pure function of
  (sid, device list), so equal sids land on equal devices across engine
  restarts (and across checkpoint/restore, which persists sids).
- A mixed solve/factor/update trace through a multi-lane engine is
  BITWISE the single-lane engine's answers: every CPU host device runs
  the same executable code, and lanes never change the staged bytes.
- Fault domains are lanes: a poisoned request fails alone while
  co-temporal requests on other lanes answer; an injected lane-thread
  death fails only that lane's pending work, the watchdog respawns the
  lane, and the engine keeps serving.
- `prewarm` warms EVERY lane (per-device executables) and dedupes
  (plan, bucket, device) work; steady-state traffic then observes zero
  XLA compiles on every lane (`profiler.compile_count`).
- Per-lane telemetry surfaces in `engine.stats()['lanes']` and merges
  into `profiler.serve_stats()['engine']`; `counters()` stays
  sort-free.
- `MeshPlanUnsupported` replaces the ad-hoc ValueErrors (structured,
  counted in serve_stats()['health']).
- `ResidentSet` per-device caps bound each device separately: a hot
  device's pressure evicts ITS residents, not the fleet's.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conflux_tpu import profiler, resilience, serve
from conflux_tpu.engine import (
    EngineClosed,
    ServeEngine,
    place_session,
)
from conflux_tpu.resilience import (
    FaultPlan,
    FaultSpec,
    HealthPolicy,
    MeshPlanUnsupported,
    RhsNonFinite,
)

N, V = 32, 16


def _mk(seed, n=N):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, n)) / np.sqrt(n)
            + 2.0 * np.eye(n)).astype(np.float32)


def _rhs(seed, w=1):
    b = np.random.default_rng(seed).standard_normal(
        (N, w) if w > 1 else (N,))
    return b.astype(np.float32)


# --------------------------------------------------------------------- #
# placement
# --------------------------------------------------------------------- #


def test_place_session_deterministic_across_engines():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest pins an 8-device CPU mesh"
    # pure function of (sid, device list): equal across calls and
    # across engine instances ("restarts")
    for sid in ("user-1", "user-2", 12345, "a-long-session-id"):
        assert place_session(sid, devs) is place_session(sid, devs)
    eng1 = ServeEngine(max_batch_delay=0.0, lanes="auto")
    d1 = {sid: eng1.placement(sid) for sid in map(str, range(32))}
    eng1.close(timeout=60)
    eng2 = ServeEngine(max_batch_delay=0.0, lanes="auto")
    d2 = {sid: eng2.placement(sid) for sid in map(str, range(32))}
    eng2.close(timeout=60)
    assert d1 == d2
    # and sids actually spread over more than one device
    assert len({str(d) for d in d1.values()}) > 1


def test_place_session_remap_only_removed_device():
    """The rendezvous (HRW) property the multi-host fabric rides
    (ISSUE 13): shrinking the device set remaps ONLY the sids the
    removed device owned — every other placement is bit-identical.
    Regression guard against mod-N style placement, where one removal
    reshuffles nearly every sid."""
    devs = jax.devices()
    assert len(devs) >= 8, "conftest pins an 8-device CPU mesh"
    sids = [f"user-{i}" for i in range(200)]
    before = {sid: place_session(sid, devs) for sid in sids}
    gone = devs[3]
    survivors = [d for d in devs if d is not gone]
    after = {sid: place_session(sid, survivors) for sid in sids}
    moved = [sid for sid in sids if after[sid] is not before[sid]]
    # exactly the removed device's sids moved, nothing else
    assert moved == [sid for sid in sids if before[sid] is gone]
    for sid in moved:
        assert after[sid] in survivors
    # the hash spreads: the removed device owned a nontrivial share
    assert 0 < len(moved) < len(sids)


def test_sid_pinned_factor_and_resubmit_route_to_same_lane():
    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    with ServeEngine(max_batch_delay=0.0, lanes="auto") as eng:
        want = eng.placement("user-42")
        s = eng.factor(plan, _mk(1), sid="user-42", timeout=60)
        assert s.device is want and s.sid == "user-42"
        # solve routes by the pinned device; answer matches direct
        b = _rhs(2, 2)
        np.testing.assert_array_equal(
            np.asarray(eng.solve(s, b, timeout=60)),
            np.asarray(s.solve(b)))
    # a fresh engine with the same devices pins user-42 identically
    with ServeEngine(max_batch_delay=0.0, lanes="auto") as eng2:
        assert eng2.placement("user-42") is want


def test_explicit_device_override_wins():
    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    dev = jax.devices()[5]
    s = plan.factor(jnp.asarray(_mk(3)), device=dev, sid="pinme")
    assert s.device is dev
    assert all(list(leaf.devices())[0] is dev for leaf in s._factors)
    with ServeEngine(max_batch_delay=0.0, lanes="auto") as eng:
        s2 = eng.factor(plan, _mk(4), device=dev, timeout=60)
        assert s2.device is dev
        b = _rhs(5)
        np.testing.assert_array_equal(
            np.asarray(eng.solve(s2, b, timeout=60)),
            np.asarray(s2.solve(b)))


# --------------------------------------------------------------------- #
# bitwise parity: fleet vs single lane
# --------------------------------------------------------------------- #


def test_fleet_bitwise_parity_mixed_trace():
    """A mixed solve/factor/update trace through an 8-lane engine gives
    BITWISE the single-lane engine's answers (same staged bytes, same
    executables — CPU host devices agree bit-for-bit)."""
    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    rng = np.random.default_rng(11)
    mats = [_mk(100 + i) for i in range(6)]
    widths = [1, 2, 1, 4, 1, 2]
    answers = {}
    for lanes in (1, "auto"):
        eng = ServeEngine(max_batch_delay=0.01, lanes=lanes)
        # cold-start through the factor lane, sid-pinned so the fleet
        # leg spreads deterministically
        sessions = [eng.factor(plan, mats[i], sid=f"u{i}", timeout=60)
                    for i in range(6)]
        # drift two sessions, then solve a mixed-width trace
        for i in (1, 4):
            U = rng.standard_normal((N, 2)).astype(np.float32) * 0.01
            Vv = rng.standard_normal((N, 2)).astype(np.float32) * 0.01
            sessions[i].update(U, Vv)
        futs = [eng.submit(sessions[i], _rhs(200 + i, widths[i]))
                for i in range(6)]
        out = [np.asarray(f.result(timeout=60)) for f in futs]
        eng.close(timeout=60)
        answers[lanes] = out
        rng = np.random.default_rng(11)  # identical drift both legs
    for a, b in zip(answers[1], answers["auto"]):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------- #
# fault domains: lanes
# --------------------------------------------------------------------- #


def test_poisoned_request_fails_alone_across_lanes():
    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    faults = FaultPlan([FaultSpec("staging", "nan", count=1)])
    with ServeEngine(max_batch_delay=0.02, lanes="auto",
                     health=HealthPolicy(check_output=False),
                     fault_plan=faults) as eng:
        sessions = [eng.factor(plan, _mk(20 + i), sid=f"p{i}",
                               timeout=60) for i in range(4)]
        bs = [_rhs(300 + i) for i in range(8)]
        futs = [eng.submit(sessions[i % 4], bs[i]) for i in range(8)]
        failed, ok = [], []
        for i, f in enumerate(futs):
            try:
                ok.append((i, np.asarray(f.result(timeout=60))))
            except RhsNonFinite:
                failed.append(i)
        assert len(failed) == 1, "exactly the poisoned request fails"
        for i, x in ok:
            np.testing.assert_array_equal(
                x, np.asarray(sessions[i % 4].solve(bs[i])))


def test_lane_thread_death_fails_only_its_lane_then_revives():
    """An injected kill on one lane's dispatcher fails only that lane's
    pending work; the watchdog respawns the lane's workers and BOTH the
    victim lane and the rest of the fleet keep serving (the engine
    never closes)."""
    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    faults = FaultPlan([FaultSpec("dispatch", "kill", count=1)])
    eng = ServeEngine(max_batch_delay=0.0, lanes="auto",
                      watchdog_interval=0.05, fault_plan=faults)
    try:
        # open OUTSIDE the engine (plan.factor, explicit devices): the
        # kill budget must be spent by the victim lane's solve
        # dispatch, not a cold-start round
        sa = plan.factor(jnp.asarray(_mk(31)), device=eng.devices[0])
        sb = plan.factor(jnp.asarray(_mk(32)), device=eng.devices[1])
        lane_a, lane_b = eng.lanes[0], eng.lanes[1]
        # the kill fires on lane_a's dispatcher (only it dispatches)
        f_bad = eng.submit(sa, _rhs(40))
        with pytest.raises(EngineClosed, match="lane"):
            f_bad.result(timeout=30)
        # other lanes never noticed
        b = _rhs(41)
        np.testing.assert_array_equal(
            np.asarray(eng.solve(sb, b, timeout=60)),
            np.asarray(sb.solve(b)))
        # the victim lane revives (watchdog poll) and serves again
        deadline = time.time() + 30
        while time.time() < deadline:
            if lane_a._dispatcher.is_alive() and lane_a.revives >= 1:
                break
            time.sleep(0.02)
        assert lane_a.revives >= 1 and lane_a._dispatcher.is_alive()
        b2 = _rhs(42)
        np.testing.assert_array_equal(
            np.asarray(eng.solve(sa, b2, timeout=60)),
            np.asarray(sa.solve(b2)))
        st = eng.stats()
        assert [ln for ln in st["lanes"] if ln["revives"]], \
            "stats must surface the lane revival"
        h = profiler.serve_stats()["health"]
        assert h["lane_revives"] >= 1 and h["watchdog_trips"] >= 1
    finally:
        eng.close(timeout=60)


# --------------------------------------------------------------------- #
# prewarm: every lane, deduped, zero compiles after
# --------------------------------------------------------------------- #


def test_prewarm_warms_every_lane_and_dedupes():
    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    devs = jax.devices()[:3]
    with ServeEngine(max_batch_delay=0.01, devices=devs,
                     max_coalesce_width=4) as eng:
        sessions = [eng.factor(plan, _mk(50 + i), device=devs[i],
                               timeout=60) for i in range(3)]
        eng.prewarm(sessions[0], widths=(1, 2, 4), factor_batches=(2,))
        for wb in (1, 2, 4):
            for d in devs:
                assert plan.device_warm("solve", wb,
                                        (d.platform, d.id))
        # dedupe: a second prewarm (same plan, another session) skips
        # every (kind, bucket, device) — zero fresh compiles
        c0 = profiler.compile_count()
        eng.prewarm(sessions[1], widths=(1, 2, 4), factor_batches=(2,))
        assert profiler.compile_count() == c0
        # steady state: traffic on every lane compiles nothing
        traces0 = dict(plan.trace_counts)
        futs = [eng.submit(sessions[i % 3], _rhs(400 + i, 1 + i % 2))
                for i in range(12)]
        for f in futs:
            f.result(timeout=60)
        assert profiler.compile_count() == c0, \
            "a lane paid a compile after prewarm"
        assert plan.trace_counts == traces0
        st = eng.stats()
        active = [ln for ln in st["lanes"] if ln["batches"]]
        assert len(active) == 3, "every lane dispatched"
        # uniform round-robin load: each lane served its own sessions'
        # requests, no more and no fewer
        assert [ln["coalesced_requests"] for ln in st["lanes"]] \
            == [4, 4, 4]


# --------------------------------------------------------------------- #
# telemetry
# --------------------------------------------------------------------- #


def test_lane_telemetry_in_stats_counters_and_serve_stats():
    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    devs = jax.devices()[:2]
    with ServeEngine(max_batch_delay=0.005, devices=devs) as eng:
        ss = [eng.factor(plan, _mk(60 + i), device=devs[i], timeout=60)
              for i in range(2)]
        for i in range(6):
            eng.solve(ss[i % 2], _rhs(500 + i), timeout=60)
        cnt = eng.counters()
        rows = cnt["lanes"]
        assert [r["lane"] for r in rows] == [0, 1]
        assert all("latency_p50_ms" not in r for r in rows), \
            "counters() must stay sort/percentile-free"
        assert sum(r["batches"] for r in rows) == cnt["batches"]
        st = eng.stats()
        for r in st["lanes"]:
            assert r["batches"] >= 1 and 0.0 <= r["occupancy"] <= 1.0
            assert r["coalesced_mean"] >= 1.0
            assert r["device"] is not None
        merged = profiler.serve_stats()["engine"]
        assert merged["lanes"] >= 2
        assert merged["lane_batches_max"] >= merged["lane_batches_min"]


def test_set_knobs_lane_scope():
    with ServeEngine(max_batch_delay=0.002,
                     devices=jax.devices()[:2]) as eng:
        k = eng.set_knobs(lane=1, max_batch_delay=0.01)
        assert k["lane_delays"] == {1: 0.01}
        assert eng.lanes[1].delay == 0.01
        assert eng.lanes[0].delay == 0.002  # untouched
        assert eng.max_batch_delay == 0.002
        with pytest.raises(ValueError, match="out of range"):
            eng.set_knobs(lane=7, max_batch_delay=0.01)
        with pytest.raises(ValueError, match="exactly one knob"):
            eng.set_knobs(lane=0, max_batch_delay=0.01, max_pending=64)
        with pytest.raises(ValueError, match="exactly one knob"):
            eng.set_knobs(lane=0)


def test_controller_tunes_lane_delay_independently():
    from conflux_tpu.control import AdaptiveController

    serve.clear_plans()
    eng = ServeEngine(max_batch_delay=0.001, devices=jax.devices()[:2])
    try:
        ctl = AdaptiveController(interval=60.0).attach(eng)
        d = AdaptiveController.blank_delta()
        # lane 1 under-coalesces with a building queue for two windows
        rows = [
            {"lane": 0, "batches": 10, "coalesced_requests": 40,
             "queue_depth": 0, "delay": 0.001, "dead": False},
            {"lane": 1, "batches": 10, "coalesced_requests": 10,
             "queue_depth": 4, "delay": 0.001, "dead": False},
        ]
        base = eng.counters()

        def counters(rows=rows):
            out = dict(base)
            out["lanes"] = [dict(r) for r in rows]
            return out

        eng.counters = counters  # scripted per-lane telemetry
        ctl._decide_lane_delays(eng, d, d["engine"])  # window 1: baseline
        rows[0]["batches"] = 20
        rows[0]["coalesced_requests"] = 80
        rows[1]["batches"] = 20
        rows[1]["coalesced_requests"] = 20
        ctl._decide_lane_delays(eng, d, d["engine"])  # pressure 1
        rows[0]["batches"] = 30
        rows[0]["coalesced_requests"] = 120
        rows[1]["batches"] = 30
        rows[1]["coalesced_requests"] = 30
        ctl._decide_lane_delays(eng, d, d["engine"])  # pressure 2: widen
        k = eng.knobs()
        assert 1 in k["lane_delays"] and k["lane_delays"][1] > 0.001
        assert 0 not in k["lane_delays"], "lane 0 stays on the default"
    finally:
        del eng.counters
        eng.close(timeout=60)


# --------------------------------------------------------------------- #
# structured mesh rejection: the genuine residue only (DESIGN §32)
# --------------------------------------------------------------------- #


def test_mesh_plan_unsupported_is_residue_only():
    """The factor lane now SERVES mesh plans; `MeshPlanUnsupported` is
    reserved for the genuine residue — migrating sharded state off its
    mesh. A 4-device mesh leaves devices 4..7 as provable outsiders."""
    serve.clear_plans()
    mesh4 = jax.sharding.Mesh(
        np.asarray(jax.devices()[:4], dtype=object), ("b",))
    mplan = serve.FactorPlan.create((8, N, N), jnp.float32, v=V,
                                    mesh=mesh4)
    A = np.zeros((8, N, N), np.float32) + np.eye(N, dtype=np.float32)
    outside = jax.devices()[7]
    h0 = resilience.health_stats().get("mesh_plan_unsupported", 0)
    with ServeEngine(max_batch_delay=0.0) as eng:
        # the demoted site: submit_factor serves the mesh plan
        s = eng.factor(mplan, A)
        assert s.plan is mplan and s.plan.mesh is not None
        # residue: an explicit pin OUTSIDE the plan's mesh
        with pytest.raises(MeshPlanUnsupported) as ei:
            eng.submit_factor(mplan, A, device=outside)
        assert isinstance(ei.value, ValueError)  # legacy callers OK
        assert ei.value.surface == "factor_lane"
        # an IN-mesh pin is a placement no-op, not an error
        assert eng.factor(mplan, A, device=jax.devices()[0]).plan \
            is mplan
    with pytest.raises(MeshPlanUnsupported) as ei:
        mplan.factor(A, device=outside)
    assert ei.value.surface == "factor"
    with pytest.raises(MeshPlanUnsupported) as ei:
        s.to_device(outside)
    assert ei.value.surface == "to_device"
    assert s.to_device(jax.devices()[1]) is s  # in-mesh: no-op
    h1 = resilience.health_stats()["mesh_plan_unsupported"]
    assert h1 >= h0 + 3
    assert "mesh_plan_unsupported" in profiler.serve_stats()["health"]


# --------------------------------------------------------------------- #
# tier: per-device caps
# --------------------------------------------------------------------- #


def test_tier_per_device_caps_isolate_hot_device():
    from conflux_tpu.tier import ResidentSet

    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    d0, d1 = jax.devices()[0], jax.devices()[1]
    cold = [plan.factor(jnp.asarray(_mk(70 + i)), device=d0,
                        sid=f"c{i}") for i in range(2)]
    hot = [plan.factor(jnp.asarray(_mk(80 + i)), device=d1,
                       sid=f"h{i}") for i in range(5)]
    rs = ResidentSet(max_sessions_per_device=2, evict_batch=1)
    rs.adopt(*cold)
    rs.adopt(*hot)
    # the hot device's pressure spilled ITS overflow only
    assert all(s.tier == "device" for s in cold), \
        "cold device residents must not pay for the hot device"
    resident_hot = [s for s in hot if s.tier == "device"]
    assert len(resident_hot) <= 2
    per_dev = rs.stats()["per_device"]
    for _dk, g in per_dev.items():
        assert g["sessions"] <= 2
    # revival on the hot device still bounded, cold side untouched
    spilled = [s for s in hot if s.tier != "device"]
    x = np.asarray(spilled[0].solve(_rhs(90)))  # transparent revival
    assert np.isfinite(x).all()
    assert all(s.tier == "device" for s in cold)
    per_dev = rs.stats()["per_device"]
    for _dk, g in per_dev.items():
        assert g["sessions"] <= 2


def test_checkpoint_restores_sid_for_deterministic_replacement(tmp_path):
    from conflux_tpu import tier

    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    s = plan.factor(jnp.asarray(_mk(95)), sid="user-7")
    tier.save_fleet(str(tmp_path / "ck"), [s])
    (r,) = tier.load_fleet(str(tmp_path / "ck"))
    assert r.sid == "user-7"
    devs = jax.devices()
    assert place_session(r.sid, devs) is place_session("user-7", devs)


# --------------------------------------------------------------------- #
# cold-start pool: load balancing + close drains it
# --------------------------------------------------------------------- #


def test_pooled_cold_start_all_resolve_and_close_drains():
    serve.clear_plans()
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    eng = ServeEngine(max_batch_delay=0.02, lanes="auto",
                      max_factor_batch=4)
    futs = [eng.submit_factor(plan, _mk(600 + i)) for i in range(10)]
    eng.close(timeout=120)  # close answers queued pool work
    sessions = [f.result(timeout=0) for f in futs]
    lane_devs = {str(d) for d in eng.devices}
    for i, s in enumerate(sessions):
        assert str(s.device) in lane_devs
        b = _rhs(700 + i)
        np.testing.assert_array_equal(np.asarray(s.solve(b)),
                                      np.asarray(s.solve(b)))
