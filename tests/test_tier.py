"""Tiered session residency tests: the ISSUE 7 contracts (DESIGN §23).

- The leaf codec round-trips every state dtype BITWISE through the
  io.py disk format (views/casts are lossless by construction).
- Spill -> transparent revive is BITWISE on the plain and checked solve
  paths, with and without Woodbury drift state, from the host AND disk
  tiers; `stack_host_trees` batched restores match per-leaf ones.
- The device tier stays bounded: LRU eviction under count and byte
  caps, high-water never above the cap, spilled sessions report
  `nbytes == 0` while their records account host/disk bytes.
- Stale-drift revival re-factorizes — coalescing through the engine's
  factor lane when client threads storm — and absorbs the drift like a
  DriftPolicy refactor.
- checkpoint()/restore() round-trips a mixed fleet bitwise (counters,
  drift state, probe rows included) at a drain barrier, lazily through
  a residency or eagerly without one.
- Fault sites spill/revive/disk_write/disk_read fail ONLY the owning
  session with structured errors (`SessionSpilled`, `RestoreCorrupt`,
  `InjectedFault`); a spill crash leaves the session resident, a
  revive crash leaves it fully spilled, a corrupt record pins its
  error.
- deadline= x revival: a request expiring while its session faults in
  releases its admission slot and never leaves the session
  half-resident.
- Counters/gauges surface through `profiler.serve_stats()['tier']` and
  `engine.stats()['tier']`.
"""

import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conflux_tpu import profiler, serve, tier
from conflux_tpu.engine import EngineSaturated, ServeEngine
from conflux_tpu.resilience import (
    DeadlineExceeded,
    FaultPlan,
    FaultSpec,
    HealthPolicy,
    InjectedFault,
    RestoreCorrupt,
    SessionSpilled,
)
from conflux_tpu.tier import ResidentSet, _decode_leaf, _encode_leaf

N, V = 32, 16


def _plan(**kw):
    return serve.FactorPlan.create((N, N), jnp.float32, v=V, **kw)


def _mk(rng, n=N):
    return (rng.standard_normal((n, n)) / np.sqrt(n)
            + 2.0 * np.eye(n)).astype(np.float32)


def _fleet(plan, count, seed=0, drift_rank=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        A = _mk(rng)
        s = plan.factor(jnp.asarray(A))
        A64 = A.astype(np.float64)
        if drift_rank:
            U = (0.01 * rng.standard_normal((N, drift_rank))
                 ).astype(np.float32)
            Vm = (0.01 * rng.standard_normal((N, drift_rank))
                  ).astype(np.float32)
            s.update(U, Vm)
            A64 = A64 + U.astype(np.float64) @ Vm.astype(np.float64).T
        out.append((s, A64))
    return out


# --------------------------------------------------------------------- #
# the codec
# --------------------------------------------------------------------- #


def test_leaf_codec_bitwise_all_dtypes():
    rng = np.random.default_rng(0)
    leaves = [
        rng.standard_normal((3, 5)).astype(np.float32),
        rng.standard_normal((2, 3, 4)),  # float64
        rng.integers(-(2 ** 30), 2 ** 30, size=(7,)).astype(np.int32),
        rng.integers(-(2 ** 60), 2 ** 60, size=(4, 2)),  # int64
        (rng.standard_normal((3, 3))
         + 1j * rng.standard_normal((3, 3))).astype(np.complex64),
        jnp.asarray(rng.standard_normal((4, 4)),
                    jnp.bfloat16).__array__(),
    ]
    for a in leaves:
        enc, meta = _encode_leaf(a)
        dec = _decode_leaf(enc, meta)
        assert dec.dtype == a.dtype and dec.shape == a.shape
        assert np.array_equal(
            dec.view(np.uint8) if dec.dtype.kind not in "fiu"
            else dec, a.view(np.uint8) if a.dtype.kind not in "fiu"
            else a), a.dtype


def test_disk_record_roundtrip_and_crc(tmp_path):
    rng = np.random.default_rng(1)
    leaves = {"f0": rng.standard_normal((4, 4)).astype(np.float32),
              "A0": rng.standard_normal((4, 4)).astype(np.float32)}
    meta = {"n_factors": 1, "keep_A": False, "has_probe": False,
            "upd": None, "owns_base": False, "last_cond": None,
            "counters": {"factorizations": 1, "solves": 0,
                         "updates": 0, "refactors": 0}}
    d = str(tmp_path / "rec")
    tier._write_record(d, leaves, meta)
    back, meta2 = tier._read_record(d)
    assert meta2 == meta
    for k in leaves:
        assert np.array_equal(back[k], leaves[k])
    # flip a payload byte: the CRC must catch it, with evidence
    with open(str(tmp_path / "rec" / "f0.bin"), "r+b") as f:
        f.seek(30)
        f.write(b"\xff")
    with pytest.raises(RestoreCorrupt) as ei:
        tier._read_record(d)
    assert ei.value.evidence["leaf"] == "f0"
    assert "expected_crc" in ei.value.evidence


# --------------------------------------------------------------------- #
# nbytes accounting
# --------------------------------------------------------------------- #


def test_nbytes_accounting():
    plan = _plan()
    rng = np.random.default_rng(2)
    s, _ = _fleet(plan, 1, seed=2)[0]
    base = s.nbytes
    # trsm single plan: (LU, perm) + A0 (A aliases A0 only when
    # refine > 0, and refine=0 here keeps _A None)
    itemsize = 4
    assert base >= 2 * N * N * itemsize
    U = rng.standard_normal((N, 2)).astype(np.float32)
    s.update(U, U)
    grown = s.nbytes
    assert grown > base  # Up/Vp/Y/Cinv joined the footprint
    rs = ResidentSet()
    rs.adopt(s)
    rs.spill(s)
    assert s.nbytes == 0
    assert s._spill.nbytes > 0
    assert rs.stats()["host_bytes"] == s._spill.nbytes


def test_nbytes_in_engine_stats():
    plan = _plan()
    s, _ = _fleet(plan, 1, seed=3)[0]
    rs = ResidentSet(max_sessions=4)
    rs.adopt(s)
    eng = ServeEngine(max_batch_delay=0.0, residency=rs)
    try:
        st = eng.stats()["tier"]
        assert st["resident_sessions"] == 1
        assert st["device_bytes"] == s.nbytes
    finally:
        eng.close()


# --------------------------------------------------------------------- #
# spill / revive: bitwise transparency
# --------------------------------------------------------------------- #


def test_spill_revive_bitwise_plain_and_checked():
    plan = _plan()
    rng = np.random.default_rng(4)
    s, _ = _fleet(plan, 1, seed=4)[0]
    b = rng.standard_normal((N, 3)).astype(np.float32)
    x0 = np.asarray(s.solve(b))
    xc0, v0 = s.solve_checked(b)
    xc0 = np.asarray(xc0)
    rs = ResidentSet()
    rs.adopt(s)
    assert rs.spill(s) == 1
    assert s.tier == "host" and s._factors is None
    x1 = np.asarray(s.solve(b))  # transparent fault-in
    assert s.tier == "device"
    assert np.array_equal(x0, x1)
    rs.spill(s)
    xc1, v1 = s.solve_checked(b)
    assert np.array_equal(xc0, np.asarray(xc1))
    assert np.array_equal(np.asarray(v0), np.asarray(v1))


def test_spill_revive_bitwise_with_drift():
    plan = _plan()
    rng = np.random.default_rng(5)
    s, _ = _fleet(plan, 1, seed=5, drift_rank=2)[0]
    b = rng.standard_normal((N, 2)).astype(np.float32)
    x0 = np.asarray(s.solve(b))
    rs = ResidentSet()
    rs.adopt(s)
    rs.spill(s)
    assert np.array_equal(x0, np.asarray(s.solve(b)))
    assert s.update_rank == 2  # the Woodbury state came back whole


def test_disk_tier_revive_bitwise(tmp_path):
    plan = _plan()
    rng = np.random.default_rng(6)
    s, _ = _fleet(plan, 1, seed=6, drift_rank=1)[0]
    b = rng.standard_normal((N, 1)).astype(np.float32)
    x0 = np.asarray(s.solve(b))
    h0 = tier.tier_stats()
    rs = ResidentSet(disk_dir=str(tmp_path))
    rs.adopt(s)
    rs.spill(s)
    assert rs.demote(s) == 1
    assert s.tier == "disk"
    assert rs.stats()["disk_bytes"] > 0
    assert np.array_equal(x0, np.asarray(s.solve(b)))
    h1 = tier.tier_stats()
    assert h1["spills_disk"] - h0.get("spills_disk", 0) == 1
    assert h1["revives_disk"] - h0.get("revives_disk", 0) == 1
    assert h1["disk_bytes_written"] > h0.get("disk_bytes_written", 0)
    assert h1["disk_bytes_read"] > h0.get("disk_bytes_read", 0)


def test_update_and_refactor_on_spilled_session():
    """update()/refactor() fault a spilled session in first — every
    state-touching entry revives, not just solve."""
    plan = _plan()
    rng = np.random.default_rng(7)
    s, _ = _fleet(plan, 1, seed=7)[0]
    rs = ResidentSet()
    rs.adopt(s)
    rs.spill(s)
    U = (0.01 * rng.standard_normal((N, 1))).astype(np.float32)
    s.update(U, U)
    assert s.tier == "device" and s.update_rank == 1
    rs.spill(s)
    s.refactor()
    assert s.tier == "device" and s.refactors >= 1


def test_revive_many_stacked_bitwise():
    plan = _plan()
    fleet = _fleet(plan, 4, seed=8)
    rng = np.random.default_rng(8)
    b = rng.standard_normal((N, 2)).astype(np.float32)
    want = [np.asarray(s.solve(b)) for s, _ in fleet]
    rs = ResidentSet()
    rs.adopt(*[s for s, _ in fleet])
    rs.spill(*[s for s, _ in fleet])
    assert rs.revive_many([s for s, _ in fleet]) == 4
    for (s, _), w in zip(fleet, want):
        assert s.tier == "device"
        assert np.array_equal(w, np.asarray(s.solve(b)))


# --------------------------------------------------------------------- #
# capacity: LRU under count/byte caps, bounded high-water
# --------------------------------------------------------------------- #


def test_lru_eviction_count_cap():
    plan = _plan()
    rs = ResidentSet(max_sessions=2, evict_batch=1)
    fleet = _fleet(plan, 5, seed=9)
    for s, _ in fleet:
        rs.adopt(s)
    st = rs.stats()
    assert st["resident_sessions"] <= 2
    assert st["resident_high_water"] <= 2
    assert st["managed_sessions"] == 5
    # the two most recently adopted survive; the LRU spilled
    assert fleet[0][0].tier == "host"
    assert fleet[-1][0].tier == "device"
    # touching a spilled one revicts the now-coldest resident
    rng = np.random.default_rng(9)
    b = rng.standard_normal((N,)).astype(np.float32)
    fleet[0][0].solve(b)
    assert fleet[0][0].tier == "device"
    assert rs.stats()["resident_sessions"] <= 2


@pytest.mark.parametrize("precision", [None, "bf16_ir"],
                         ids=["native", "bf16_ir"])
def test_byte_cap_bounds_high_water(precision):
    """Churn twice through a fleet of twice the byte cap: the high-water
    stays under the cap (for precision-tier sessions too, whose bf16
    factors are smaller), the churn compiles nothing, and every answer
    after spill/revive is the untiered session's, bitwise."""
    plan = _plan()
    rng = np.random.default_rng(10)
    fleet = [plan.factor(jnp.asarray(_mk(rng)), precision=precision)
             for _ in range(4)]
    b = rng.standard_normal((N,)).astype(np.float32)
    want = [np.asarray(s.solve(b)) for s in fleet]  # before any spill
    traces0 = dict(plan.trace_counts)
    per = fleet[0].nbytes
    cap = 2 * per
    rs = ResidentSet(max_bytes=cap, evict_batch=1)
    for s in fleet:
        rs.adopt(s)
    for s, x in list(zip(fleet, want)) * 2:  # churn through the fleet
        assert np.array_equal(np.asarray(s.solve(b)), x)
    assert plan.trace_counts == traces0, "spill/revive churn compiled"
    st = rs.stats()
    assert st["device_bytes"] <= cap
    assert st["device_bytes_high_water"] <= cap, st
    h = tier.tier_stats()
    assert h["spills_host"] > 0 and h["revives_h2d"] > 0


def test_host_cap_demotes_to_disk(tmp_path):
    plan = _plan()
    fleet = _fleet(plan, 5, seed=11)
    rs = ResidentSet(max_sessions=1, host_max_sessions=2,
                     disk_dir=str(tmp_path), evict_batch=1)
    for s, _ in fleet:
        rs.adopt(s)
    st = rs.stats()
    assert st["resident_sessions"] <= 1
    assert st["host_sessions"] <= 2
    assert st["disk_sessions"] >= 2
    total = (st["resident_sessions"] + st["host_sessions"]
             + st["disk_sessions"] + st["corrupt_sessions"])
    assert total == st["managed_sessions"] == 5  # conservation


# --------------------------------------------------------------------- #
# stale-drift revival through the factor lane
# --------------------------------------------------------------------- #


def test_revive_refactor_direct():
    plan = _plan()
    fleet = _fleet(plan, 1, seed=12, drift_rank=2)
    s, A64 = fleet[0]
    rs = ResidentSet(revive_refactor_rank=2)
    rs.adopt(s)
    rs.spill(s)
    rng = np.random.default_rng(12)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    h0 = tier.tier_stats()
    x = np.asarray(s.solve(b))
    h1 = tier.tier_stats()
    assert h1["revives_refactor"] - h0.get("revives_refactor", 0) == 1
    assert s.update_rank == 0 and s.refactors == 1  # drift absorbed
    want = np.linalg.solve(A64, b.astype(np.float64))
    assert (np.linalg.norm(x - want) / np.linalg.norm(want)) < 1e-4


def test_revive_refactor_coalesces_through_factor_lane():
    plan = _plan()
    fleet = _fleet(plan, 3, seed=13, drift_rank=1)
    rs = ResidentSet(revive_refactor_rank=1)
    eng = ServeEngine(max_batch_delay=0.05, residency=rs)
    rng = np.random.default_rng(13)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    try:
        rs.adopt(*[s for s, _ in fleet])
        rs.spill(*[s for s, _ in fleet])
        errs = []

        def touch(s):
            try:
                s.solve(b)
            except Exception as e:  # noqa: BLE001 — recorded, asserted
                errs.append(e)

        ts = [threading.Thread(target=touch, args=(s,))
              for s, _ in fleet]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not errs, errs
        st = eng.stats()
        # the storm coalesced: fewer factor dispatches than sessions
        assert st["factor_batches"] < 3
        assert st["factor_coalesced_requests"] == 3
        for s, A64 in fleet:
            assert s.update_rank == 0 and s.refactors == 1
            x = np.asarray(s.solve(b))
            want = np.linalg.solve(A64, b.astype(np.float64))
            assert (np.linalg.norm(x - want)
                    / np.linalg.norm(want)) < 1e-4
    finally:
        eng.close()


# --------------------------------------------------------------------- #
# checkpoint / restore
# --------------------------------------------------------------------- #


def test_checkpoint_restore_bitwise(tmp_path):
    plan = _plan(refine=1)
    fleet = _fleet(plan, 2, seed=14) + _fleet(plan, 1, seed=15,
                                              drift_rank=2)
    sessions = [s for s, _ in fleet]
    rng = np.random.default_rng(14)
    b = rng.standard_normal((N, 2)).astype(np.float32)
    want_plain = [np.asarray(s.solve(b)) for s in sessions]
    want_checked = [tuple(np.asarray(a) for a in s.solve_checked(b))
                    for s in sessions]
    counters = [(s.factorizations, s.solves, s.updates, s.refactors)
                for s in sessions]
    tier.save_fleet(str(tmp_path / "ck"), sessions)
    # simulate the process dying: drop every cached plan/program
    serve.clear_plans()
    restored = tier.load_fleet(str(tmp_path / "ck"))
    for i, r in enumerate(restored):
        assert (r.factorizations, r.solves, r.updates,
                r.refactors) == counters[i]
        assert np.array_equal(want_plain[i], np.asarray(r.solve(b)))
        xc, v = r.solve_checked(b)
        assert np.array_equal(want_checked[i][0], np.asarray(xc))
        assert np.array_equal(want_checked[i][1], np.asarray(v))
    assert restored[2].update_rank == 2  # drift state survived


def test_engine_checkpoint_drain_barrier_and_lazy_restore(tmp_path):
    plan = _plan()
    fleet = _fleet(plan, 3, seed=16)
    rs = ResidentSet(max_sessions=2)
    rs.adopt(*[s for s, _ in fleet])
    eng = ServeEngine(max_batch_delay=0.002, residency=rs)
    rng = np.random.default_rng(16)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    want = [np.asarray(s.solve(b)) for s, _ in fleet]
    try:
        # checkpoint races live traffic: the barrier drains first
        futs = [eng.submit(fleet[i % 3][0], b) for i in range(9)]
        eng.checkpoint(str(tmp_path / "ck"))
        for f in futs:
            f.result(60)
        # restore through a residency: sessions come back HOST-tier
        # (lazy) and fault in on first touch
        rs2 = ResidentSet(max_sessions=2)
        eng2 = ServeEngine(max_batch_delay=0.0, residency=rs2)
        try:
            restored = eng2.restore(str(tmp_path / "ck"))
            assert all(r.tier == "host" for r in restored)
            for i, r in enumerate(restored):
                x = eng2.solve(r, b, timeout=60)
                assert np.array_equal(want[i], x)
            assert rs2.stats()["resident_sessions"] <= 2
        finally:
            eng2.close()
    finally:
        eng.close()


def test_mesh_plan_key_roundtrips_fleet_codec(tmp_path):
    """ISSUE 17 satellite: a mesh plan's identity — device ids, axis
    names, device-array shape — rides the fleet.json codec, and the
    restored plan rebuilds its mesh (hence its out_shardings, which key
    on `mesh_cache_key`) EXACTLY. The restored session answers bitwise
    and its factors land sharded back across the mesh."""
    from conflux_tpu.batched import batch_mesh

    serve.clear_plans()
    mesh = batch_mesh()
    plan = serve.FactorPlan.create((8, N, N), jnp.float32, v=V,
                                   mesh=mesh)
    key0 = plan.key
    rng = np.random.default_rng(17)
    A = np.stack([_mk(rng) for _ in range(8)])
    s = plan.factor(jnp.asarray(A))
    b = rng.standard_normal((8, N)).astype(np.float32)
    x0 = np.asarray(s.solve(jnp.asarray(b)))
    tier.save_fleet(str(tmp_path / "ck"), [s], names=["m"])
    serve.clear_plans()  # a cold process: the codec must carry it all
    (back,) = tier.load_fleet(str(tmp_path / "ck"))
    assert back.plan.key == key0
    assert back.plan.key.mesh_key == key0.mesh_key
    m2 = back.plan.mesh
    assert [d.id for d in m2.devices.flat] \
        == [d.id for d in mesh.devices.flat]
    assert m2.axis_names == mesh.axis_names
    assert m2.devices.shape == mesh.devices.shape
    np.testing.assert_array_equal(
        x0, np.asarray(back.solve(jnp.asarray(b))))
    f0 = jax.tree_util.tree_leaves(back._factors)[0]
    assert len(f0.sharding.device_set) == 8
    # the registry aliases: an equal key resolves to the live plan
    assert serve.FactorPlan.from_key(back.plan.key) is back.plan


# --------------------------------------------------------------------- #
# fault injection: blast radius is one session
# --------------------------------------------------------------------- #


def test_spill_fault_leaves_session_resident():
    plan = _plan()
    s, _ = _fleet(plan, 1, seed=18)[0]
    rs = ResidentSet(fault_plan=FaultPlan(
        [FaultSpec("spill", "crash", count=1)]))
    rs.adopt(s)
    h0 = tier.tier_stats()
    assert rs.spill(s) == 0  # the crash aborted the spill
    assert s.tier == "device"  # fail-safe: still resident
    assert (tier.tier_stats()["spill_faults"]
            - h0.get("spill_faults", 0)) == 1
    rng = np.random.default_rng(18)
    s.solve(rng.standard_normal((N,)).astype(np.float32))
    assert rs.spill(s) == 1  # budget spent: the next spill works


def test_revive_fault_structured_and_record_intact():
    plan = _plan()
    s, _ = _fleet(plan, 1, seed=19)[0]
    rng = np.random.default_rng(19)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    x0 = np.asarray(s.solve(b))
    rs = ResidentSet(fault_plan=FaultPlan(
        [FaultSpec("revive", "crash", count=1)]))
    rs.adopt(s)
    rs.spill(s)
    with pytest.raises(InjectedFault):
        s.solve(b)
    assert s.tier == "host"  # fully spilled, record intact
    assert np.array_equal(x0, np.asarray(s.solve(b)))  # retry revives


def test_revive_fault_fails_only_owner_in_engine():
    """A revive crash on one session's dispatch fails only that
    session's request; co-submitted requests against healthy sessions
    answer normally (blast-radius isolation through the engine)."""
    plan = _plan()
    (sick, _), (ok, _) = _fleet(plan, 2, seed=20)
    rng = np.random.default_rng(20)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    x_ok = np.asarray(ok.solve(b))
    faults = FaultPlan([FaultSpec("revive", "crash", count=2)])
    rs = ResidentSet(fault_plan=faults)
    eng = ServeEngine(max_batch_delay=0.01, residency=rs)
    try:
        rs.adopt(sick, ok)
        rs.spill(sick)
        f_sick = eng.submit(sick, b)
        f_ok = eng.submit(ok, b)
        assert np.array_equal(x_ok, f_ok.result(60))
        with pytest.raises(InjectedFault):
            f_sick.result(60)
        assert sick.tier == "host"
    finally:
        eng.close()


def test_disk_corruption_restorecorrupt_only_owner(tmp_path):
    plan = _plan()
    (bad, _), (good, _) = _fleet(plan, 2, seed=21)
    rng = np.random.default_rng(21)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    x_good = np.asarray(good.solve(b))
    faults = FaultPlan([FaultSpec("disk_write", "nan", count=1)])
    rs = ResidentSet(disk_dir=str(tmp_path), fault_plan=faults)
    rs.adopt(bad, good)
    rs.spill(bad, good)
    rs.demote(bad)   # this write corrupts (the injected 'nan')
    rs.demote(good)  # budget spent: a clean record
    with pytest.raises(RestoreCorrupt) as ei:
        bad.solve(b)
    assert "expected_crc" in ei.value.evidence
    assert bad.tier == "corrupt"
    h = tier.tier_stats()
    assert h["restore_corrupt"] >= 1
    # the error is pinned: every later touch re-raises it
    with pytest.raises(RestoreCorrupt):
        bad.solve(b)
    # the sibling is untouched, bitwise
    assert np.array_equal(x_good, np.asarray(good.solve(b)))
    st = rs.stats()
    assert st["corrupt_sessions"] == 1
    assert (st["resident_sessions"] + st["host_sessions"]
            + st["disk_sessions"] + st["corrupt_sessions"]) == 2


def test_disk_read_fault_then_recovers(tmp_path):
    plan = _plan()
    s, _ = _fleet(plan, 1, seed=22)[0]
    rng = np.random.default_rng(22)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    x0 = np.asarray(s.solve(b))
    faults = FaultPlan([FaultSpec("disk_read", "crash", count=1)])
    rs = ResidentSet(disk_dir=str(tmp_path), fault_plan=faults)
    rs.adopt(s)
    rs.spill(s)
    rs.demote(s)
    with pytest.raises(InjectedFault):
        s.solve(b)
    assert s.tier == "disk"  # record intact on disk
    assert np.array_equal(x0, np.asarray(s.solve(b)))


# --------------------------------------------------------------------- #
# deadline x revival + backpressure
# --------------------------------------------------------------------- #


def test_deadline_expiring_during_fault_in_releases_slot():
    plan = _plan()
    s, _ = _fleet(plan, 1, seed=23)[0]
    rng = np.random.default_rng(23)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    x0 = np.asarray(s.solve(b))
    rs = ResidentSet(max_concurrent_revives=1)
    eng = ServeEngine(max_batch_delay=0.0, residency=rs)
    try:
        rs.adopt(s)
        rs.spill(s)
        assert rs._revive_sem.acquire(timeout=1)  # saturate the lane
        try:
            fut = eng.submit(s, b, deadline=0.1)
            with pytest.raises((SessionSpilled, DeadlineExceeded)):
                fut.result(30)
            # the admission slot is released and the session is FULLY
            # spilled — never half-resident
            assert eng.stats()["pending"] == 0
            assert s.tier == "host" and s._factors is None
            assert tier.tier_stats()["revive_rejects"] >= 1
        finally:
            rs._revive_sem.release()
        # the lane freed: the same session revives and answers bitwise
        assert np.array_equal(x0, eng.solve(s, b, timeout=60))
    finally:
        eng.close()


def test_direct_fault_in_timeout_structured():
    plan = _plan()
    s, _ = _fleet(plan, 1, seed=24)[0]
    rs = ResidentSet(max_concurrent_revives=1)
    rs.adopt(s)
    rs.spill(s)
    assert rs._revive_sem.acquire(timeout=1)
    try:
        with pytest.raises(SessionSpilled):
            rs.fault_in(s, timeout=0.05)
        assert s.tier == "host"
    finally:
        rs._revive_sem.release()
    rs.fault_in(s)
    assert s.tier == "device"


# --------------------------------------------------------------------- #
# review regressions: barrier x revival, concurrent checkpoints/adopts,
# corrupt-record accounting, revive_many partial progress
# --------------------------------------------------------------------- #


def test_factor_lane_sheds_at_drain_barrier():
    """A factor submission during a checkpoint drain SHEDS instead of
    waiting: a stale-drift revival holds its session RLock while
    submitting, and save_fleet needs that lock — waiting would wedge
    the engine forever (review-caught deadlock)."""
    plan = _plan()
    rng = np.random.default_rng(32)
    A = _mk(rng)
    eng = ServeEngine(max_batch_delay=0.0)
    try:
        with eng._lock:
            eng._draining = True
        try:
            t0 = time.perf_counter()
            with pytest.raises(EngineSaturated):
                eng.submit_factor(plan, A)
            assert time.perf_counter() - t0 < 5.0  # shed, not waited
        finally:
            with eng._lock:
                eng._draining = False
                eng._not_full.notify_all()
        # the barrier cleared: the factor lane flows again
        s = eng.factor(plan, A, timeout=60)
        assert np.asarray(
            s.solve(np.zeros(N, np.float32))).shape == (N,)
    finally:
        eng.close()


def test_checkpoint_vs_stale_revival_no_deadlock(tmp_path, monkeypatch):
    """checkpoint() racing a client-thread stale-drift revival: the
    client holds the session RLock and submits to the factor lane while
    the drain barrier is up; the submission sheds, the revival falls
    back to the direct factor path, and save_fleet then gets the lock —
    both sides complete."""
    plan = _plan()
    fleet = _fleet(plan, 2, seed=33, drift_rank=1)
    rs = ResidentSet(revive_refactor_rank=1)
    eng = ServeEngine(max_batch_delay=0.0, residency=rs)
    rng = np.random.default_rng(33)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    in_barrier = threading.Event()
    client_done = threading.Event()
    real_save = tier.save_fleet

    def slow_save(path, sessions, names=None, **kw):
        in_barrier.set()
        client_done.wait(30)  # hold the barrier across the revival
        return real_save(path, sessions, names, **kw)

    monkeypatch.setattr(tier, "save_fleet", slow_save)
    try:
        rs.adopt(*[s for s, _ in fleet])
        rs.spill(*[s for s, _ in fleet])
        errs, xs = [], []

        def ckpt():
            try:
                eng.checkpoint(str(tmp_path / "ck"))
            except Exception as e:  # noqa: BLE001 — recorded, asserted
                errs.append(e)

        def touch():
            try:
                xs.append(np.asarray(fleet[0][0].solve(b)))
            except Exception as e:  # noqa: BLE001 — recorded, asserted
                errs.append(e)

        ct = threading.Thread(target=ckpt, daemon=True)
        ct.start()
        assert in_barrier.wait(30)
        tt = threading.Thread(target=touch, daemon=True)
        tt.start()
        tt.join(30)
        revived = not tt.is_alive()
        client_done.set()
        ct.join(60)
        assert revived, "revival deadlocked against the drain barrier"
        assert not ct.is_alive(), "checkpoint deadlocked"
        assert not errs, errs
        want = np.linalg.solve(fleet[0][1], b.astype(np.float64))
        assert (np.linalg.norm(xs[0] - want)
                / np.linalg.norm(want)) < 1e-4
        assert fleet[0][0].refactors == 1  # the direct fallback ran
    finally:
        client_done.set()
        eng.close()


def test_concurrent_checkpoints_serialize(tmp_path, monkeypatch):
    """Two concurrent checkpoint() calls take their own complete drain
    barriers (the snapshots never overlap), both land restorable
    records, and admission reopens afterwards."""
    plan = _plan()
    fleet = _fleet(plan, 2, seed=34)
    rs = ResidentSet()
    eng = ServeEngine(max_batch_delay=0.0, residency=rs)
    rng = np.random.default_rng(34)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    real_save = tier.save_fleet
    alock = threading.Lock()
    active, peak = [0], [0]

    def counted_save(path, sessions, names=None, **kw):
        with alock:
            active[0] += 1
            peak[0] = max(peak[0], active[0])
        try:
            time.sleep(0.05)
            return real_save(path, sessions, names, **kw)
        finally:
            with alock:
                active[0] -= 1

    monkeypatch.setattr(tier, "save_fleet", counted_save)
    try:
        rs.adopt(*[s for s, _ in fleet])
        want = [np.asarray(s.solve(b)) for s, _ in fleet]
        errs = []

        def ck(d):
            try:
                eng.checkpoint(str(d))
            except Exception as e:  # noqa: BLE001 — recorded, asserted
                errs.append(e)

        ts = [threading.Thread(target=ck, args=(tmp_path / f"ck{i}",),
                               daemon=True) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts)
        assert not errs, errs
        assert peak[0] == 1, "snapshots overlapped under one barrier"
        with eng._lock:
            assert not eng._draining  # the barrier fully cleared
        assert np.array_equal(want[0],
                              eng.solve(fleet[0][0], b, timeout=60))
        for i in range(2):
            restored = tier.load_fleet(str(tmp_path / f"ck{i}"))
            for j, r in enumerate(restored):
                assert np.array_equal(want[j], np.asarray(r.solve(b)))
    finally:
        eng.close()


def test_concurrent_adopt_touch_churn_consistent():
    """Concurrent re-adopts and touches under count pressure: adopt()
    used to size its eviction wave while HOLDING the adoptee's session
    lock, letting two adopts pick each other's adoptee as a victim
    (lock cycle) or a re-adoption spill its own adoptee mid-adopt.
    The hammer asserts liveness and resident<->record consistency."""
    plan = _plan()
    fleet = _fleet(plan, 3, seed=35)
    rs = ResidentSet(max_sessions=1, evict_batch=1)
    rs.adopt(*[s for s, _ in fleet])
    rng = np.random.default_rng(35)
    b = rng.standard_normal((N,)).astype(np.float32)
    stop = time.perf_counter() + 2.0
    errs = []

    def churn(s):
        try:
            while time.perf_counter() < stop:
                rs.adopt(s)
                s.solve(b)
        except Exception as e:  # noqa: BLE001 — recorded, asserted
            errs.append(e)

    ts = [threading.Thread(target=churn, args=(s,), daemon=True)
          for s, _ in fleet]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert not any(t.is_alive() for t in ts), "adopt churn deadlocked"
    assert not errs, errs
    st = rs.stats()
    assert (st["resident_sessions"] + st["host_sessions"]
            + st["disk_sessions"] + st["corrupt_sessions"]) == 3
    with rs._lock:
        states = {id(s): rs._state.get(id(s)) for s, _ in fleet}
    for s, _ in fleet:
        if states[id(s)] == "resident":
            assert s._spill is None  # never resident WITH a record
        elif states[id(s)] in ("host", "disk"):
            assert s._spill is not None
    # the fleet still answers correctly after the storm
    for s, A64 in fleet:
        x = np.asarray(s.solve(b))
        want = np.linalg.solve(A64, b.astype(np.float64))
        assert (np.linalg.norm(x - want) / np.linalg.norm(want)) < 1e-4


def test_corrupt_record_retires_gauges_and_disk_space(tmp_path):
    plan = _plan()
    s, _ = _fleet(plan, 1, seed=36)[0]
    rng = np.random.default_rng(36)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    faults = FaultPlan([FaultSpec("disk_write", "nan", count=1)])
    rs = ResidentSet(disk_dir=str(tmp_path), fault_plan=faults)
    rs.adopt(s)
    rs.spill(s)
    rs.demote(s)
    rec_path = s._spill.path
    assert rs.stats()["disk_bytes"] > 0
    with pytest.raises(RestoreCorrupt) as e1:
        s.solve(b)
    # the dead record stops counting against the disk tier and its
    # directory is reclaimed (a CRC failure is permanent)
    assert rs.stats()["disk_bytes"] == 0
    assert not os.path.exists(rec_path)
    # later touches raise a FRESH copy of the pinned error — the one
    # instance is never re-raised (and traceback-mutated) across
    # threads — chained to the original with the same evidence
    with pytest.raises(RestoreCorrupt) as e2:
        s.solve(b)
    assert e2.value is not e1.value
    assert e2.value.__cause__ is e1.value
    assert e2.value.evidence == e1.value.evidence


def test_fault_in_reports_noop_and_revive_many_counts_real_work():
    plan = _plan()
    fleet = _fleet(plan, 3, seed=37)
    rs = ResidentSet()
    rs.adopt(*[s for s, _ in fleet])
    assert rs.fault_in(fleet[0][0]) is False  # resident: a no-op
    rs.spill(*[s for s, _ in fleet])
    assert rs.fault_in(fleet[0][0]) is True
    assert rs.fault_in(fleet[0][0]) is False  # already back
    # only the two still-spilled sessions count as revived
    assert rs.revive_many([s for s, _ in fleet]) == 2
    assert all(s.tier == "device" for s, _ in fleet)


def test_revive_many_respects_device_caps():
    """The stacked group path lands a whole chunk in one h2d — an
    uncapped group overshot max_sessions with nothing left to evict
    (caught driving the warm-restart surface). Groups now chunk to the
    caps: later chunks LRU-evict earlier ones, the high-water stays
    bounded, and every revived answer is still bitwise."""
    plan = _plan()
    fleet = _fleet(plan, 6, seed=39)
    rng = np.random.default_rng(39)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    want = [np.asarray(s.solve(b)) for s, _ in fleet]
    rs = ResidentSet(max_sessions=3)
    rs.adopt(*[s for s, _ in fleet])
    rs.spill(*[s for s, _ in fleet])
    assert rs.revive_many([s for s, _ in fleet]) == 6
    st = rs.stats()
    assert st["resident_sessions"] <= 3, st
    assert st["resident_high_water"] <= 3, st
    for (s, _), w in zip(fleet, want):
        assert np.array_equal(w, np.asarray(s.solve(b)))
    assert rs.stats()["resident_high_water"] <= 3


def test_revive_many_partial_progress_under_backpressure():
    """A saturated revive lane skips sessions (records intact,
    `revive_rejects` bumped) instead of aborting the whole batch with
    the first SessionSpilled; the count reports what actually landed."""
    plan = _plan()
    fleet = _fleet(plan, 2, seed=38, drift_rank=1)  # drifted: rest path
    rs = ResidentSet(max_concurrent_revives=1)
    rs.adopt(*[s for s, _ in fleet])
    rs.spill(*[s for s, _ in fleet])
    h0 = tier.tier_stats()
    assert rs._revive_sem.acquire(timeout=1)  # saturate the lane
    try:
        assert rs.revive_many([s for s, _ in fleet], timeout=0.05) == 0
        assert all(s.tier == "host" for s, _ in fleet)
        assert (tier.tier_stats()["revive_rejects"]
                - h0.get("revive_rejects", 0)) >= 2
    finally:
        rs._revive_sem.release()
    # the lane freed: the same call revives everyone
    assert rs.revive_many([s for s, _ in fleet]) == 2
    assert all(s.tier == "device" for s, _ in fleet)


# --------------------------------------------------------------------- #
# observability
# --------------------------------------------------------------------- #


def test_tier_counters_in_serve_stats(tmp_path):
    plan = _plan()
    s, _ = _fleet(plan, 1, seed=25)[0]
    rng = np.random.default_rng(25)
    b = rng.standard_normal((N, 1)).astype(np.float32)
    rs = ResidentSet(disk_dir=str(tmp_path))
    rs.adopt(s)
    rs.spill(s)
    s.solve(b)
    st = profiler.serve_stats()["tier"]
    assert st["spills_host"] >= 1
    assert st["revives_h2d"] >= 1
    assert st["fault_in_p50_ms"] > 0
    assert st["managed_sessions"] >= 1
    assert st["device_bytes_high_water"] > 0
    # clear() resets the counters; the manager's gauges survive
    profiler.clear()
    st2 = profiler.serve_stats()["tier"]
    assert st2["spills_host"] == 0 and st2["revives_h2d"] == 0
    assert st2["managed_sessions"] >= 1
