"""Multi-tenant QoS tests: the ISSUE 15 acceptance contracts.

- `qos=None` engines are byte-identical to the pre-QoS engine (no
  'qos' key anywhere, bitwise answers, no ledger allocated).
- `QosClass` validates its fields and round-trips the fabric wire
  encoding; `collect_delay` resolves override > tier override > tier
  default.
- `FairShareLedger` is work-conserving below contention, sheds an
  over-share tenant while contended, and the deficit-round-robin
  credit readmits priority-0 traffic at the weighted drain fraction.
- Engine throttling raises `TenantThrottled` with the structured
  attrs (`retry_after`/`tenant`/`qos_class`) and counts per class in
  the health ledger; `EngineSaturated` carries the same attrs.
- Per-class counters/percentiles surface in `counters()['qos']` /
  `stats()['qos']` / `serve_stats()['qos']`, and per-class
  `StatsWindow(engine, qos_class=...)` deltas sum to the cumulative
  per-class counts under concurrent writers (the §24 hammer, extended
  to N coexisting windows).
- The persistent operating point (`control.save_operating_point` /
  `load_operating_point`) round-trips, rejects malformed rows, and
  re-seeds a `persist=True` controller at attach.
- The fabric carries `qos=` to the owning host and returns
  `TenantThrottled` with attrs intact across the wire encoding.
"""

import json
import os
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from conflux_tpu import control, profiler, qos, resilience, serve
from conflux_tpu.engine import EngineSaturated, ServeEngine
from conflux_tpu.qos import (
    FairShareLedger,
    QosClass,
    class_from_wire,
    collect_delay,
)
from conflux_tpu.resilience import TenantThrottled

N, V = 32, 16


def _session(seed=0, v=V):
    rng = np.random.default_rng(seed)
    A = (rng.standard_normal((N, N)) / np.sqrt(N)
         + 2.0 * np.eye(N)).astype(np.float32)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=v)
    return plan, plan.factor(jnp.asarray(A))


# --------------------------------------------------------------------------- #
# QosClass: validation, wire encoding, collect-delay resolution
# --------------------------------------------------------------------------- #


def test_qos_class_validation():
    c = QosClass(tenant="gold", tier="latency", slo=0.025, weight=3.0)
    assert c.key == "gold/latency"
    with pytest.raises(ValueError, match="tier"):
        QosClass(tier="interactive")
    with pytest.raises(ValueError, match="tenant"):
        QosClass(tenant="")
    with pytest.raises(ValueError, match="'/'"):
        QosClass(tenant="a/b")
    with pytest.raises(ValueError, match="slo"):
        QosClass(slo=0.0)
    with pytest.raises(ValueError, match="weight"):
        QosClass(weight=0.0)
    with pytest.raises(ValueError, match="collect_delay"):
        QosClass(collect_delay=-1e-3)


def test_qos_class_wire_round_trip():
    c = QosClass(tenant="gold", tier="latency", priority=-1,
                 slo=0.025, weight=2.5, collect_delay=0.001)
    assert class_from_wire(c.to_wire()) == c
    assert class_from_wire(None) is None
    assert class_from_wire(c) is c  # already-built classes pass through
    # wire dicts with missing keys fall back to the defaults
    assert class_from_wire({"tenant": "t"}) == QosClass(tenant="t")


def test_collect_delay_resolution():
    eng_delay = 0.002
    # tier defaults: latency dispatches now, throughput rides the
    # engine window, batch stretches it (clamped at the ceiling)
    assert collect_delay(None, eng_delay, {}) == eng_delay
    assert collect_delay(QosClass(tier="latency"), eng_delay, {}) == 0.0
    assert collect_delay(QosClass(tier="throughput"),
                         eng_delay, {}) == eng_delay
    assert collect_delay(QosClass(tier="batch"), eng_delay, {}) == \
        pytest.approx(eng_delay * qos.BATCH_STRETCH)
    assert collect_delay(QosClass(tier="batch"), 1.0, {}) == \
        qos.MAX_TIER_DELAY
    # the controller's per-tier override trumps the default...
    assert collect_delay(QosClass(tier="batch"), eng_delay,
                         {"batch": 0.016}) == 0.016
    # ...and the request's own override trumps everything (clamped)
    c = QosClass(tier="batch", collect_delay=0.001)
    assert collect_delay(c, eng_delay, {"batch": 0.016}) == 0.001
    assert collect_delay(QosClass(collect_delay=1.0), eng_delay,
                         {}) == qos.MAX_TIER_DELAY


# --------------------------------------------------------------------------- #
# FairShareLedger math (pure, no engine)
# --------------------------------------------------------------------------- #


def test_ledger_work_conserving_below_contention():
    led = FairShareLedger(contention=0.5)
    bulk = QosClass(tenant="bulk", tier="batch")
    # an idle engine admits everything, share or no share
    for pend in range(7):
        assert led.try_admit(bulk, pend, 16) is None


def test_ledger_sheds_over_share_when_contended():
    led = FairShareLedger(contention=0.5)
    gold = QosClass(tenant="gold", weight=1.0)
    bulk = QosClass(tenant="bulk", weight=1.0, priority=1)
    led.note(gold)
    led.note(bulk)
    # equal weights, max_pending=8: share is 4 each
    assert led.share("bulk", 8) == 4.0
    assert led.frac("bulk") == 0.5
    for _ in range(4):  # fill bulk to its share (engine uncontended)
        assert led.try_admit(bulk, 0, 8) is None
    # contended + at share + background priority: shed, with the
    # over-share backlog as the hint basis
    over = led.try_admit(bulk, 4, 8)
    assert over == pytest.approx(1.0)
    # the under-share tenant still admits while contended
    assert led.try_admit(gold, 4, 8) is None
    st = led.stats(8)
    assert st["bulk"]["throttled"] == 1 and st["bulk"]["pending"] == 4
    assert st["gold"]["admitted"] == 1


def test_ledger_deficit_readmits_priority_zero():
    led = FairShareLedger(contention=0.25)
    gold = QosClass(tenant="gold", weight=1.0)
    bulk = QosClass(tenant="bulk", weight=1.0, priority=1)
    bulk0 = QosClass(tenant="bulk", weight=1.0, priority=0)
    for _ in range(4):
        assert led.try_admit(bulk, 0, 8) is None
    assert led.try_admit(gold, 4, 8) is None
    # at the share line while contended: background bulk sheds
    assert led.try_admit(bulk, 5, 8) is not None
    # releases distribute credit by weight; after enough quanta the
    # tenant's PRIORITY-0 traffic readmits while still over share
    for _ in range(4):
        led.release(bulk)
        led.try_admit(bulk, 5, 8)  # pending returns to the share line
    assert led.try_admit(bulk0, 8, 8) is None
    # ...but only by spending credit: the next one sheds again
    led._deficit["bulk"] = 0.0
    assert led.try_admit(bulk0, 8, 8) is not None


def test_ledger_release_never_goes_negative():
    led = FairShareLedger()
    c = QosClass(tenant="t")
    led.note(c)
    led.release(c)
    assert led.stats(8)["t"]["pending"] == 0


# --------------------------------------------------------------------------- #
# qos=None stays byte-identical
# --------------------------------------------------------------------------- #


def test_qos_none_engine_bitwise_identical():
    serve.clear_plans()
    plan, s = _session(seed=11)
    b = jnp.asarray(np.ones(N, np.float32))
    with ServeEngine(max_batch_delay=0.0) as eng:
        plain = np.asarray(eng.solve(s, b))
        traces0 = dict(plan.trace_counts)
        # no classified traffic ever: no state, no dict keys
        assert eng._qos is None
        assert "qos" not in eng.counters()
        assert "qos" not in eng.stats()
        assert "qos_contention" not in eng.knobs()
    with ServeEngine(max_batch_delay=0.0) as eng:
        tagged = np.asarray(eng.solve(
            s, b, qos=QosClass(tenant="gold", tier="throughput")))
        assert eng._qos is not None
        assert "qos" in eng.counters()
    np.testing.assert_array_equal(plain, tagged)
    # classified traffic rides the very same programs: nothing compiled
    assert plan.trace_counts == traces0


def test_qos_type_validation_on_submit():
    serve.clear_plans()
    _, s = _session(seed=12)
    b = jnp.asarray(np.ones(N, np.float32))
    with ServeEngine(max_batch_delay=0.0) as eng:
        with pytest.raises(TypeError, match="QosClass"):
            eng.submit(s, b, qos={"tenant": "gold"})


# --------------------------------------------------------------------------- #
# engine throttling: structured errors, counters, health ledger
# --------------------------------------------------------------------------- #


def test_engine_throttles_over_share_tenant():
    serve.clear_plans()
    resilience.clear_health()
    _, s = _session(seed=13)
    b = jnp.asarray(np.ones(N, np.float32))
    gold = QosClass(tenant="gold", tier="throughput", weight=1.0)
    bulk = QosClass(tenant="bulk", tier="throughput", weight=1.0,
                    priority=1)
    # a huge window parks the dispatcher, so pending grows
    # deterministically; shares are 2 each at max_pending=4
    eng = ServeEngine(max_batch_delay=60.0, max_pending=4)
    futs = [eng.submit(s, b, qos=gold),
            eng.submit(s, b, qos=bulk),
            eng.submit(s, b, qos=bulk)]
    with pytest.raises(TenantThrottled) as ei:
        eng.submit(s, b, qos=bulk)
    assert ei.value.tenant == "bulk"
    assert ei.value.qos_class == "bulk/throughput"
    assert ei.value.retry_after > 0.0
    # the under-share tenant still admits past the contention line
    futs.append(eng.submit(s, b, qos=gold))
    # the GLOBAL bound still backstops everything, attrs included
    with pytest.raises(EngineSaturated) as ei2:
        eng.submit(s, b, qos=gold)
    assert ei2.value.tenant == "gold"
    assert ei2.value.qos_class == "gold/throughput"
    c = eng.counters()["qos"]
    assert c["classes"]["bulk/throughput"]["throttled"] == 1
    assert c["classes"]["gold/throughput"]["requests"] == 2
    assert c["tenants"]["bulk"]["pending"] == 2
    h = resilience.health_stats()
    assert h["tenant_throttled"] == 1
    assert h["tenant_throttled[bulk/throughput]"] == 1
    eng.close(timeout=60)  # releases the parked batch
    for f in futs:
        f.result(timeout=60)
    # every ledger slot came back when its request settled
    assert all(r["pending"] == 0
               for r in eng.counters()["qos"]["tenants"].values())
    resilience.clear_health()


def test_latency_class_pulls_in_the_window():
    """A latency-class arrival resolves a ~0 collect delay, so it
    drains promptly even under a parked-dispatcher window."""
    serve.clear_plans()
    _, s = _session(seed=14)
    b = jnp.asarray(np.ones(N, np.float32))
    with ServeEngine(max_batch_delay=60.0) as eng:
        f = eng.submit(s, b, qos=QosClass(tenant="gold",
                                          tier="latency", slo=1.0))
        f.result(timeout=60)  # would park for 60s without the tier cut
        row = eng.stats()["qos"]["classes"]["gold/latency"]
        assert row["completed"] == 1
        assert row["latency_samples"] == 1
        assert row["slo_attainment_pct"] == 100.0


def test_qos_knobs_round_trip():
    serve.clear_plans()
    _, s = _session(seed=15)
    b = jnp.asarray(np.ones(N, np.float32))
    with ServeEngine(max_batch_delay=0.0) as eng:
        with pytest.raises(ValueError, match="qos_contention"):
            eng.set_knobs(qos_contention=0.0)
        with pytest.raises(ValueError, match="qos_tier_delay"):
            eng.set_knobs(qos_tier_delay={"interactive": 0.001})
        eng.set_knobs(qos_contention=0.25,
                      qos_tier_delay={"batch": 0.008})
        k = eng.knobs()
        assert k["qos_contention"] == 0.25
        assert k["qos_tier_delay"] == {"batch": 0.008}
        eng.set_knobs(qos_tier_delay={"batch": None})  # None clears
        assert eng.knobs()["qos_tier_delay"] == {}
        # the knobs still drive a live ledger
        np.asarray(eng.solve(s, b, qos=QosClass(tenant="t")))
        assert eng.counters()["qos"]["contention"] == 0.25


# --------------------------------------------------------------------------- #
# per-class windows: the §24 hammer extended to N coexisting windows
# --------------------------------------------------------------------------- #


def test_per_class_stats_windows_coexist_under_hammer():
    """N per-class StatsWindows + the engine-wide window taken WHILE
    concurrent per-class writers drive traffic: every window's deltas
    sum to exactly its class's cumulative counts, and the engine-wide
    window is untouched by the per-class ones."""
    serve.clear_plans()
    _, s = _session(seed=16)
    b = jnp.asarray(np.ones(N, np.float32))
    classes = [QosClass(tenant=f"t{i}", tier="throughput")
               for i in range(3)]
    PER = 40
    with ServeEngine(max_batch_delay=0.0) as eng:
        wall = profiler.StatsWindow(eng)
        per = {c.key: profiler.StatsWindow(eng, qos_class=c.key)
               for c in classes}
        sums = {c.key: 0 for c in classes}
        lats = {c.key: 0 for c in classes}
        stop = threading.Event()

        def writer(c):
            for _ in range(PER):
                eng.solve(s, b, qos=c)

        def taker():
            while not stop.is_set():
                for k, w in per.items():
                    d = w.delta()["engine"]
                    sums[k] += d["qos_completed"]
                    lats[k] += d["latency_samples"]

        ts = [threading.Thread(target=writer, args=(c,))
              for c in classes]
        tk = threading.Thread(target=taker)
        tk.start()
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
            assert not t.is_alive(), "qos writer wedged"
        stop.set()
        tk.join(timeout=120)
        for k, w in per.items():  # the tail windows
            d = w.delta()["engine"]
            sums[k] += d["qos_completed"]
            lats[k] += d["latency_samples"]
        assert sums == {c.key: PER for c in classes}
        assert lats == {c.key: PER for c in classes}
        # the engine-wide window saw every request exactly once
        d = wall.delta()["engine"]
        assert d["completed"] == PER * len(classes)
        assert d["latency_samples"] == PER * len(classes)
        # cumulative consumers unchanged by any of the windowing
        rows = eng.counters()["qos"]["classes"]
        assert all(rows[c.key]["completed"] == PER for c in classes)


def test_qos_latency_window_unknown_key_is_empty():
    serve.clear_plans()
    with ServeEngine(max_batch_delay=0.0) as eng:
        # windows may open ahead of traffic: unknown keys read empty
        assert eng.qos_latency_window("nobody/latency") == (0, [])
        assert eng.qos_latency_samples() == {}
        w = profiler.StatsWindow(eng, qos_class="nobody/latency")
        d = w.delta()["engine"]
        assert d["qos_requests"] == 0 and d["latency_samples"] == 0


def test_serve_stats_merges_qos_across_engines():
    serve.clear_plans()
    _, s = _session(seed=17)
    b = jnp.asarray(np.ones(N, np.float32))
    with ServeEngine(max_batch_delay=0.0) as eng:
        eng.solve(s, b, qos=QosClass(tenant="gold", tier="latency",
                                     slo=1.0))
        agg = profiler.serve_stats()["qos"]
        assert agg["engines"] >= 1
        row = agg["classes"]["gold/latency"]
        assert row["completed"] >= 1
        assert row["slo_attainment_pct"] == 100.0
        assert agg["tenants"]["gold"]["admitted"] >= 1


# --------------------------------------------------------------------------- #
# persistent operating point
# --------------------------------------------------------------------------- #


def test_operating_point_round_trip(tmp_path, monkeypatch):
    path = str(tmp_path / "op.json")
    monkeypatch.setenv("CONFLUX_TPU_OPERATING_POINT", path)
    assert control.operating_point_path() == path
    assert control.load_operating_point("r1") == {}
    control.save_operating_point("r1", {
        "max_batch_delay": 0.004, "max_pending": 256,
        "qos_contention": 0.3, "qos_tier_delay": {"batch": 0.01},
        "drain_rate": 120.0, "max_coalesce_width": 64})
    row = control.load_operating_point("r1")
    # only the compile-safe seed knobs persist — never bucket caps
    assert row == {"max_batch_delay": 0.004, "max_pending": 256,
                   "qos_contention": 0.3,
                   "qos_tier_delay": {"batch": 0.01}}
    # a second regime coexists; re-saving r1 replaces only r1
    control.save_operating_point("r2", {"max_pending": 64})
    control.save_operating_point("r1", {"max_pending": 128})
    assert control.load_operating_point("r1") == {"max_pending": 128}
    assert control.load_operating_point("r2") == {"max_pending": 64}
    doc = json.loads(open(path).read())
    assert doc["version"] == control._OP_VERSION
    assert len(doc["rows"]) == 2


def test_operating_point_rejects_malformed(tmp_path, monkeypatch):
    path = str(tmp_path / "op.json")
    monkeypatch.setenv("CONFLUX_TPU_OPERATING_POINT", path)
    # corrupt file: load is {} and save starts fresh
    with open(path, "w") as f:
        f.write("{not json")
    assert control.load_operating_point("r") == {}
    control.save_operating_point("r", {"max_pending": 64})
    assert control.load_operating_point("r") == {"max_pending": 64}
    # hand-edited rows with unknown knobs or bad shapes are dropped
    doc = json.loads(open(path).read())
    doc["rows"].append({"regime": "bad", "knobs": {"max_stack": 8},
                        "updated": "now"})
    doc["rows"].append({"regime": "worse",
                        "knobs": {"qos_tier_delay": {"oops": 1.0}},
                        "updated": "now"})
    with open(path, "w") as f:
        json.dump(doc, f)
    assert control.load_operating_point("bad") == {}
    assert control.load_operating_point("worse") == {}
    assert control.load_operating_point("r") == {"max_pending": 64}


def test_controller_reseeds_and_persists(tmp_path, monkeypatch):
    path = str(tmp_path / "op.json")
    monkeypatch.setenv("CONFLUX_TPU_OPERATING_POINT", path)
    serve.clear_plans()
    control.save_operating_point("slo25-l1", {
        "max_batch_delay": 0.004, "max_pending": 128,
        "qos_contention": 0.3})
    ctl = control.AdaptiveController(persist=True, interval=60.0)
    eng = ServeEngine(max_batch_delay=0.0, controller=ctl)
    try:
        assert ctl._regime == "slo25-l1"
        k = eng.knobs()
        assert k["max_batch_delay"] == 0.004
        assert k["max_pending"] == 128
        assert k["qos_contention"] == 0.3
        st = ctl.stats()
        assert st["persist"]["enabled"]
        assert st["persist"]["reseeded"]["max_pending"] == 128
        eng.set_knobs(max_pending=96)
    finally:
        eng.close()
    # close() dumped the final vector back to the same regime row
    assert control.load_operating_point("slo25-l1")["max_pending"] == 96


def test_controller_default_regime_never_persists_without_optin(
        tmp_path, monkeypatch):
    path = str(tmp_path / "op.json")
    monkeypatch.setenv("CONFLUX_TPU_OPERATING_POINT", path)
    serve.clear_plans()
    ctl = control.AdaptiveController(interval=60.0)  # persist=False
    eng = ServeEngine(max_batch_delay=0.0, controller=ctl)
    try:
        assert ctl.stats()["persist"] == {"enabled": False}
    finally:
        eng.close()
    assert not os.path.exists(path)


def test_controller_steers_qos_contention_down_under_slo_pressure():
    """Two scripted hot windows (a latency class p99 inside headroom
    of its SLO) halve qos_contention; the decision is recorded."""
    serve.clear_plans()
    _, s = _session(seed=18)
    b = jnp.asarray(np.ones(N, np.float32))
    ctl = control.AdaptiveController(interval=60.0,
                                     min_window_samples=1)
    eng = ServeEngine(max_batch_delay=0.0, controller=ctl)
    try:
        slow = QosClass(tenant="gold", tier="latency", slo=1e-9)
        for _ in range(3):  # every sample blows a 1ns SLO
            eng.solve(s, b, qos=slow)
        before = eng.knobs()["qos_contention"]
        for _ in range(3):
            eng.solve(s, b, qos=slow)
            ctl.step()
        after = eng.knobs()["qos_contention"]
        assert after < before
        assert any(d["knob"] == "qos_contention"
                   for d in ctl.stats()["decisions_log"])
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# fabric passthrough
# --------------------------------------------------------------------------- #


def test_fabric_wire_round_trip_tenant_throttled():
    from conflux_tpu.fabric import _encode_exc, _raise_wire

    e = TenantThrottled("over", retry_after=0.07, tenant="bulk",
                        qos_class="bulk/batch")
    enc = _encode_exc(e)
    assert enc["etype"] == "TenantThrottled"
    with pytest.raises(TenantThrottled) as ei:
        _raise_wire(enc)
    assert ei.value.retry_after == 0.07
    assert ei.value.tenant == "bulk"
    assert ei.value.qos_class == "bulk/batch"
    e2 = EngineSaturated("full", retry_after=0.1, tenant="t",
                         qos_class="t/latency")
    with pytest.raises(EngineSaturated) as ei2:
        _raise_wire(_encode_exc(e2))
    assert ei2.value.tenant == "t"
    assert ei2.value.qos_class == "t/latency"


def test_fabric_local_host_carries_qos(tmp_path):
    from conflux_tpu.fabric import LocalHost, ServeFabric

    serve.clear_plans()
    rng = np.random.default_rng(19)
    A = (rng.standard_normal((N, N)) / np.sqrt(N)
         + 2.0 * np.eye(N)).astype(np.float32)
    plan = serve.FactorPlan.create((N, N), jnp.float32, v=V)
    hosts = [LocalHost("h0", str(tmp_path / "h0"),
                       engine_kwargs=dict(max_batch_delay=0.0))]
    fab = ServeFabric(hosts)
    try:
        fab.start()
        fab.open("s0", plan.spec(), A)
        gold = QosClass(tenant="gold", tier="latency", slo=1.0)
        b = np.ones((N,), np.float32)
        plain = np.asarray(fab.solve("s0", b))
        tagged = np.asarray(fab.solve("s0", b, qos=gold))
        np.testing.assert_array_equal(plain, tagged)
        with pytest.raises(TypeError, match="QosClass"):
            fab.solve("s0", b, qos={"tenant": "gold"})
        # the heartbeat payload grows flat per-tier drain counters
        ping = hosts[0].ping()
        assert ping["counters"]["qos_latency_solves"] == 1
        core = hosts[0].core
        row = core.eng.counters()["qos"]["classes"]["gold/latency"]
        assert row["completed"] == 1
    finally:
        fab.close()


def test_host_load_estimator_folds_tier_rates():
    est = control.HostLoadEstimator()
    est.feed("h0", {"seconds": 2.0, "solves": 10, "pending": 1,
                    "qos_latency_solves": 4, "qos_batch_solves": 6})
    st = est.stats()["h0"]
    assert st["qos_drain_per_s"] == {"batch": 3.0, "latency": 2.0}
    est.forget("h0")
    assert "h0" not in est.stats()
