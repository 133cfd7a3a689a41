"""Gang-resident session stacking tests: the ISSUE 10 contracts.

- Gang-stacked answers are allclose to solo dispatch, and BITWISE
  invariant to the stack bucket / pad contents (slot i of a gang
  dispatch == slot of a hand-built stacked dispatch at another bucket).
- Drifted (pending-Woodbury) and checked (health-guarded) sessions ride
  the stacked path — the two old exclusion holes — with the per-reason
  exclusion counters at literal zero.
- The stacked state is RESIDENT: steady-state windows re-stack nothing
  and compile nothing; session mutations re-sync their slot lazily via
  the version counter.
- Slot lifecycle: spill frees the slot (reused by the next adoptee),
  revival re-adopts bitwise, `stack_cap` overflow falls back solo and
  is counted, a sick slot re-dispatches solo while its gang-mates
  settle in place.
- Per-lane `max_pending` slices shed a hot lane's overflow without
  starving the fleet; per-lane shed counts surface in the lane rows.
- The adaptive controller steers `stack_sessions`/`max_stack` from
  windowed opportunity telemetry, prewarm-gated.
- Concurrency: adopt/update/solve hammering from client threads keeps
  every future resolved and every answer correct.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conflux_tpu import profiler, serve
from conflux_tpu.batched import (
    grow_stack_tree,
    stack_trees,
    unstack_tree,
    write_slot_tree,
)
from conflux_tpu.control import AdaptiveController
from conflux_tpu.engine import EngineSaturated, ServeEngine
from conflux_tpu.gang import SessionGang
from conflux_tpu.resilience import HealthPolicy
from conflux_tpu.tier import ResidentSet

N, V = 32, 16


def _fleet(n, seed=0, policy=None):
    rng = np.random.default_rng(seed)
    A = (rng.standard_normal((n, N, N)) / np.sqrt(N)
         + 2.0 * np.eye(N)).astype(np.float32)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    return plan, [plan.factor(jnp.asarray(A[i]), policy=policy)
                  for i in range(n)], A


def _rhs(n, seed=1, width=1):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((N, width)).astype(np.float32)
            for _ in range(n)]


def _gang_of(eng, plan):
    return eng.lanes[0]._gangs.get(id(plan))


# --------------------------------------------------------------------- #
# primitives: the slot round-trip contract
# --------------------------------------------------------------------- #


def test_write_slot_roundtrip_bitwise():
    """write_slot_tree -> unstack_tree round-trips the written bits,
    and grow_stack_tree keeps old slots bitwise while padding with
    slot 0 (or zeros)."""
    rng = np.random.default_rng(7)
    trees = [(jnp.asarray(rng.standard_normal((N, N)).astype(np.float32)),
              jnp.asarray(rng.integers(0, N, N).astype(np.int32)))
             for _ in range(3)]
    stack = stack_trees([trees[0], trees[1]])
    stack = write_slot_tree(stack, trees[2], 1)
    back = unstack_tree(stack, 2)
    for a, b in zip(back[0], trees[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(back[1], trees[2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    grown = grow_stack_tree(stack, 4)
    gb = unstack_tree(grown, 4)
    for a, b in zip(gb[1], trees[2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(gb[3], back[0]):  # pad slots self-reference slot 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    zgrown = grow_stack_tree(stack[0], 4, fill="zero")
    assert float(jnp.abs(zgrown[2:]).sum()) == 0.0


# --------------------------------------------------------------------- #
# numerics: allclose to solo, bitwise within a bucket
# --------------------------------------------------------------------- #


def test_gang_matches_direct_and_bitwise_within_bucket():
    serve.clear_plans()
    plan, fleet, _A = _fleet(5, seed=11)
    bs = _rhs(5, seed=12, width=1)
    direct = [np.asarray(s.solve(b)) for s, b in zip(fleet, bs)]
    eng = ServeEngine(max_batch_delay=60.0, stack_sessions=True,
                      max_stack=8)
    futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
    eng.close(timeout=120)  # one window: close flushes the batch
    res = [np.asarray(f.result(60)) for f in futs]
    for r, d in zip(res, direct):
        np.testing.assert_allclose(r, d, rtol=2e-5, atol=1e-6)
    st = eng.stats()
    assert st["gang_batches"] == 1
    assert st["batches"] == 1
    assert st["gang"]["sessions"] == 5
    assert st["gang"]["capacity_slots"] == 8  # rank_bucket(5)
    # bitwise within a bucket: slot results equal a hand-built stacked
    # dispatch at a DIFFERENT bucket with different pad contents
    with fleet[0]._lock, fleet[3]._lock:
        F = stack_trees([fleet[3]._factors, fleet[0]._factors])
    buf = np.zeros((2, N, 1), np.float32)
    buf[0] = bs[3]
    ref = np.asarray(plan._stacked_solve_fn(2, 1)(F, None, buf))[0]
    np.testing.assert_array_equal(res[3], ref)


@pytest.mark.parametrize("health", [None, HealthPolicy()],
                         ids=["plain", "checked"])
def test_gang_resident_steady_state_no_restack_no_compile(health):
    """Second and later windows re-sync nothing (version counters
    unchanged), rebuild nothing, and compile nothing — no plan trace
    and no XLA compile, on the checked (fused per-slot verdict) gang
    too."""
    serve.clear_plans()
    plan, fleet, _A = _fleet(4, seed=21)
    bs = _rhs(4, seed=22)
    eng = ServeEngine(max_batch_delay=0.05, stack_sessions=True,
                      max_stack=4, health=health)
    try:
        for f in [eng.submit(s, b) for s, b in zip(fleet, bs)]:
            f.result(60)
        g = _gang_of(eng, plan)
        st0 = g.stats()
        traces0 = dict(plan.trace_counts)
        compiles0 = profiler.compile_count()
        for _ in range(3):
            for f in [eng.submit(s, b) for s, b in zip(fleet, bs)]:
                f.result(60)
        st1 = g.stats()
        compiles = profiler.compile_count() - compiles0
    finally:
        eng.close(timeout=120)
    assert plan.trace_counts == traces0, "steady-state window compiled"
    assert compiles == 0, f"{compiles} XLA compiles in the steady state"
    assert st1["adopts"] == st0["adopts"]
    assert st1["rebuilds"] == st0["rebuilds"]
    assert st1["refreshes"] == st0["refreshes"] == 0
    assert eng.stats()["gang_batches"] >= 4


# --------------------------------------------------------------------- #
# the closed exclusion holes: drifted + checked sessions stack
# --------------------------------------------------------------------- #


def test_gang_drifted_and_checked_sessions_stack():
    serve.clear_plans()
    plan, fleet, _A = _fleet(4, seed=31)
    rng = np.random.default_rng(32)
    U = (0.01 * rng.standard_normal((N, 3))).astype(np.float32)
    Vm = (0.01 * rng.standard_normal((N, 3))).astype(np.float32)
    fleet[0].update(U, Vm)
    fleet[2].update(2 * U, Vm)
    bs = _rhs(4, seed=33, width=2)
    direct = [np.asarray(s.solve(b)) for s, b in zip(fleet, bs)]
    eng = ServeEngine(max_batch_delay=60.0, stack_sessions=True,
                      max_stack=4, health=HealthPolicy())
    futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
    eng.close(timeout=120)  # one window: close flushes the batch
    res = [np.asarray(f.result(60)) for f in futs]
    for i, (r, d) in enumerate(zip(res, direct)):
        np.testing.assert_allclose(r, d, rtol=5e-5, atol=1e-6,
                                   err_msg=f"session {i}")
    st = eng.stats()
    excl = st["stack_exclusions"]
    assert excl["upd_pending"] == 0, "drifted sessions must stack now"
    assert excl["checked"] == 0, "checked sessions must stack now"
    assert excl["mesh"] == 0
    assert st["gang_batches"] == 1, "the whole window rode one dispatch"
    g = _gang_of(eng, plan)
    assert g.stats()["rank_bucket"] == 4  # rank_bucket(3)
    assert g.stats()["checked"]


def test_gang_refresh_after_mutation():
    """update()/refactor() bump the session version; the next stacked
    window re-syncs ONLY that slot and answers track the new state."""
    serve.clear_plans()
    plan, fleet, _A = _fleet(3, seed=41)
    bs = _rhs(3, seed=42)
    eng = ServeEngine(max_batch_delay=0.05, stack_sessions=True,
                      max_stack=4)
    try:
        for f in [eng.submit(s, b) for s, b in zip(fleet, bs)]:
            f.result(60)
        g = _gang_of(eng, plan)
        r0 = g.stats()["refreshes"]
        rng = np.random.default_rng(43)
        U = (0.05 * rng.standard_normal((N, 2))).astype(np.float32)
        fleet[1].update(U, U)
        direct = [np.asarray(s.solve(b)) for s, b in zip(fleet, bs)]
        futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
        res = [np.asarray(f.result(60)) for f in futs]
        assert g.stats()["refreshes"] == r0 + 1
        for r, d in zip(res, direct):
            np.testing.assert_allclose(r, d, rtol=5e-5, atol=1e-6)
        # refactor absorbs the drift; the slot re-syncs again and the
        # gang returns to the PLAIN stacked program path
        fleet[1].refactor()
        direct = [np.asarray(s.solve(b)) for s, b in zip(fleet, bs)]
        futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
        res = [np.asarray(f.result(60)) for f in futs]
        assert g.stats()["refreshes"] == r0 + 2
        for r, d in zip(res, direct):
            np.testing.assert_allclose(r, d, rtol=5e-5, atol=1e-6)
    finally:
        eng.close(timeout=120)


# --------------------------------------------------------------------- #
# slot lifecycle: spill frees, revival re-adopts, cap excludes
# --------------------------------------------------------------------- #


def test_gang_slot_reuse_after_spill_and_revive_bitwise():
    serve.clear_plans()
    plan, fleet, _A = _fleet(4, seed=51)
    bs = _rhs(4, seed=52)
    rs = ResidentSet(max_sessions=16)
    eng = ServeEngine(max_batch_delay=0.05, stack_sessions=True,
                      max_stack=4, residency=rs)
    try:
        rs.adopt(*fleet)
        futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
        before = [np.asarray(f.result(60)) for f in futs]
        g = _gang_of(eng, plan)
        assert g.members == 4 and g.cap == 4
        slot1 = fleet[1]._gang_slot
        assert rs.spill(fleet[1]) == 1
        assert fleet[1].tier == "host"
        assert fleet[1]._gang is None, "spill must free the gang slot"
        assert g.members == 3
        # a NEW session reuses the freed slot — capacity does not grow
        extra = plan.factor(jnp.asarray(_A[0]))
        futs = [eng.submit(s, bs[0])
                for s in (fleet[0], fleet[2], extra)]
        for f in futs:
            f.result(60)
        assert g.cap == 4
        assert extra._gang_slot == slot1, "freed slot not reused"
        # free the slot again (spill the stand-in) so the revival can
        # land straight back into it at the SAME stack bucket
        rs.adopt(extra)
        assert rs.spill(extra) == 1
        assert extra._gang is None
        assert g.members == 3
        # revival re-adopts (grouped revival lands straight in a slot)
        assert rs.revive_many([fleet[1]]) == 1
        assert fleet[1].tier == "device"
        assert fleet[1]._gang is g and fleet[1]._gang_slot == slot1, \
            "grouped revival did not land straight into the gang slot"
        futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
        after = [np.asarray(f.result(60)) for f in futs]
        # revived state is bitwise (h2d restore) and the stacked
        # program is pad/bucket-invariant within the SAME bucket, so
        # the answers replay exactly
        np.testing.assert_array_equal(after[1], before[1])
    finally:
        eng.close(timeout=120)


def test_gang_stack_cap_exclusion_counted():
    serve.clear_plans()
    plan, fleet, _A = _fleet(3, seed=61)
    bs = _rhs(3, seed=62)
    direct = [np.asarray(s.solve(b)) for s, b in zip(fleet, bs)]
    eng = ServeEngine(max_batch_delay=60.0, stack_sessions=True,
                      max_stack=2)
    futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
    eng.close(timeout=120)  # one window: close flushes the batch
    res = [np.asarray(f.result(60)) for f in futs]
    for r, d in zip(res, direct):
        np.testing.assert_allclose(r, d, rtol=2e-5, atol=1e-6)
    st = eng.stats()
    assert st["stack_exclusions"]["stack_cap"] >= 1
    assert st["gang"]["sessions"] == 2


def test_gang_sick_slot_isolated_gangmates_settle():
    """A slot whose factors went bad fails its per-slot verdict; its
    request recovers through the SOLO escalation ladder (refactor from
    the clean base) while gang-mates settle from the same dispatch."""
    serve.clear_plans()
    plan, fleet, _A = _fleet(3, seed=71)
    bs = _rhs(3, seed=72)
    eng = ServeEngine(max_batch_delay=0.05, stack_sessions=True,
                      max_stack=4, health=HealthPolicy())
    try:
        for f in [eng.submit(s, b) for s, b in zip(fleet, bs)]:
            f.result(60)
        direct = [np.asarray(s.solve(b)) for s, b in zip(fleet, bs)]
        with fleet[1]._lock:  # corrupt the resident factors in place
            bad = tuple(jnp.full_like(leaf, jnp.nan)
                        if jnp.issubdtype(leaf.dtype, jnp.floating)
                        else leaf for leaf in fleet[1]._factors)
            fleet[1]._factors = bad
            fleet[1]._gang_ver += 1
        from conflux_tpu import resilience as res_mod

        h0 = res_mod.health_stats()
        futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
        res = [np.asarray(f.result(120)) for f in futs]
        h1 = res_mod.health_stats()
    finally:
        eng.close(timeout=120)
    for i, (r, d) in enumerate(zip(res, direct)):
        np.testing.assert_allclose(r, d, rtol=5e-5, atol=1e-6,
                                   err_msg=f"session {i}")
    assert h1["gang_unhealthy_slots"] > h0.get("gang_unhealthy_slots", 0)
    assert h1["refactor_escalations"] > h0.get("refactor_escalations", 0)


# --------------------------------------------------------------------- #
# per-lane pending slices
# --------------------------------------------------------------------- #


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >=2 devices")
def test_lane_pending_slice_sheds_hot_lane_only():
    serve.clear_plans()
    plan, fleet, _A = _fleet(2, seed=81)
    eng = ServeEngine(max_batch_delay=60.0, lanes=2, max_pending=64,
                      max_lane_pending=2)
    b = np.ones((N, 1), np.float32)
    try:
        s0 = fleet[0]
        s0.sid = "hot"
        lane = eng._lane_for(s0)
        futs = [eng.submit(s0, b) for _ in range(2)]
        with pytest.raises(EngineSaturated, match="max_lane_pending"):
            eng.submit(s0, b)
        # the OTHER lane still admits
        other = fleet[1]
        other_dev = [ln.device for ln in eng.lanes
                     if ln is not lane][0]
        other.to_device(other_dev)
        f2 = eng.submit(other, b)
        rows = {r["lane"]: r for r in eng.stats()["lanes"]}
        assert rows[lane.index]["sheds"] == 1
        assert rows[lane.index]["pending"] == 2
        futs.append(f2)
    finally:
        eng.close(timeout=120)
    for f in futs:
        assert f.result(60) is not None
    assert eng.knobs()["max_lane_pending"] == 2


# --------------------------------------------------------------------- #
# controller steering
# --------------------------------------------------------------------- #


class _FakeWindow:
    def __init__(self, deltas):
        self.deltas = list(deltas)

    def delta(self):
        if len(self.deltas) > 1:
            return self.deltas.pop(0)
        return self.deltas[0]


def test_controller_steers_stacking_prewarm_gated():
    serve.clear_plans()
    plan, fleet, _A = _fleet(2, seed=91)
    eng = ServeEngine(max_batch_delay=0.0)
    ctl = AdaptiveController(slo_p99_ms=25.0, interval=60.0,
                             stack_after=2, unstack_after=2)
    ctl.attach(eng)
    try:
        b = np.ones((N, 1), np.float32)
        eng.solve(fleet[0], b, timeout=60)  # registers active targets
        opp = AdaptiveController.blank_delta()
        opp["engine"]["gang_opportunity"] = 4
        opp["engine"]["batches"] = 4
        opp["bucket_hits"] = {1: 4}
        ctl._window = _FakeWindow([opp])
        assert not eng.stack_sessions
        ctl.step()          # pressure 1
        ctl.step()          # pressure 2 -> background prewarm launched
        pre = ctl._stack_prewarm
        assert pre is not None
        target, wb, thread = pre
        thread.join(120)
        assert plan.bucket_ready(stack=(target, wb))
        ctl.step()          # gate passes -> knob flips
        assert eng.stack_sessions
        assert eng.max_stack == target == 4  # rank_bucket(4) capped
        # idle windows with zero stacked batches disable it again
        idle = AdaptiveController.blank_delta()
        idle["engine"]["batches"] = 3
        idle["engine"]["gang_batches"] = 0
        ctl._window = _FakeWindow([idle])
        ctl.step()
        ctl.step()
        assert not eng.stack_sessions
        log = [d["knob"] for d in ctl.stats()["decisions_log"]]
        assert "stack_sessions" in log
    finally:
        eng.close(timeout=120)


# --------------------------------------------------------------------- #
# concurrency: adopt/update/solve hammer
# --------------------------------------------------------------------- #


def test_gang_concurrent_adopt_update_solve_hammer():
    serve.clear_plans()
    plan, fleet, _A = _fleet(6, seed=101)
    eng = ServeEngine(max_batch_delay=0.001, stack_sessions=True,
                      max_stack=8, max_pending=4096)
    rng = np.random.default_rng(102)
    bs = _rhs(6, seed=103)
    errors: list = []
    stop = threading.Event()

    def submitter(idx):
        try:
            for _ in range(30):
                f = eng.submit(fleet[idx], bs[idx])
                f.result(120)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def mutator():
        try:
            k = 0
            while not stop.is_set() and k < 10:
                s = fleet[k % len(fleet)]
                U = (0.01 * rng.standard_normal((N, 2))
                     ).astype(np.float32)
                s.update(U, U, replace=True)
                if k % 3 == 0:
                    s.refactor()
                k += 1
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(len(fleet))]
    threads.append(threading.Thread(target=mutator))
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        stop.set()
        assert not any(t.is_alive() for t in threads), "hammer wedged"
        assert not errors, errors
        # quiesced oracle: every session answers correctly afterwards
        direct = [np.asarray(s.solve(b)) for s, b in zip(fleet, bs)]
        futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
        for f, d in zip(futs, direct):
            np.testing.assert_allclose(np.asarray(f.result(120)), d,
                                       rtol=5e-5, atol=1e-6)
    finally:
        stop.set()
        eng.close(timeout=120)


def test_gang_set_knobs_validation_and_roundtrip():
    serve.clear_plans()
    with ServeEngine(max_batch_delay=0.0) as eng:
        k = eng.set_knobs(stack_sessions=True, max_stack=4,
                          max_lane_pending=16)
        assert k["stack_sessions"] and k["max_stack"] == 4
        assert k["max_lane_pending"] == 16
        assert eng.knobs() == k
        with pytest.raises(ValueError, match="max_stack"):
            eng.set_knobs(max_stack=0)
        with pytest.raises(ValueError, match="max_lane_pending"):
            eng.set_knobs(max_lane_pending=0)
        with pytest.raises(ValueError, match="lane"):
            eng.set_knobs(lane=0, max_batch_delay=0.001,
                          stack_sessions=True)


def test_unganged_session_unchanged_and_gang_detach_on_to_device():
    """stack_sessions=False engines never create gangs (the PR 9
    byte-identical contract's structural half), and `to_device` on a
    ganged session releases its slot."""
    serve.clear_plans()
    plan, fleet, _A = _fleet(2, seed=111)
    bs = _rhs(2, seed=112)
    eng = ServeEngine(max_batch_delay=60.0)
    futs = [eng.submit(s, b) for s, b in zip(fleet, bs)]
    eng.close(timeout=120)
    for f in futs:
        f.result(60)
    assert not eng.lanes[0]._gangs
    st = eng.stats()
    assert st["gang_batches"] == 0
    assert st["gang_opportunity"] >= 1  # the controller's signal
    eng2 = ServeEngine(max_batch_delay=60.0, stack_sessions=True,
                       max_stack=4)
    futs = [eng2.submit(s, b) for s, b in zip(fleet, bs)]
    eng2.close(timeout=120)
    for f in futs:
        f.result(60)
    g = _gang_of(eng2, plan)
    assert g.members == 2
    fleet[0].to_device(jax.devices()[0])
    assert fleet[0]._gang is None
    assert g.members == 1


def test_gang_module_refuses_batched_plans():
    serve.clear_plans()
    bplan = serve.FactorPlan.create((4, N, N), jnp.float32, v=V)
    with pytest.raises(AssertionError, match="single-system"):
        bplan._stacked_solve_health_fn(2, 1)
    with pytest.raises(AssertionError, match="single-system"):
        bplan._stacked_update_solve_fn(2, 2, 1, 0)
    g = SessionGang(bplan, None)  # construction is fine; dispatch never
    assert g.members == 0
