"""Multi-host serve fabric (ISSUE 13): federated engines with
checkpoint-backed fail-over and live session migration.

- Routing is DETERMINISTIC rendezvous hashing: the owner map is a pure
  function of (sid, live host set), and a host-set change remaps ONLY
  the removed host's sessions.
- The kill-one-host drill: a dead host is detected by heartbeat, its
  fleet revives on the survivors from the last checkpoint, revived
  sessions solve BITWISE identically, and recovery time is measured
  and bounded.
- In-flight / routed requests against a dead host fail with a
  STRUCTURED HostUnavailable (retry_after riding the measured drain
  rate) — never a hang.
- Live migration hands a session across hosts at a drain barrier;
  migrated sessions (drift updates included) solve bitwise.
- Degraded-mode admission: below min_live live hosts, `open` refuses
  with FleetDegraded while existing sessions keep solving.
- Heartbeat hysteresis: misses walk alive -> suspect -> dead with the
  configured thresholds, and a recovered probe walks suspect back to
  alive.

All tests run the single-process LocalHost fabric (deterministic,
lockcheck-able); the two-process ProcessHost path is exercised by
scripts/fabric_drill.py (CI job).
"""

import os
import time

import numpy as np
import pytest

from conflux_tpu import fabric, profiler, resilience
from conflux_tpu.engine import rendezvous
from conflux_tpu.fabric import FabricPolicy, LocalHost, ServeFabric
from conflux_tpu.resilience import (
    FaultPlan,
    FaultSpec,
    FleetDegraded,
    HostUnavailable,
    InjectedFault,
)
from conflux_tpu.serve import FactorPlan

N, V = 24, 8


def _mk(seed, n=N):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, n)) / np.sqrt(n)
            + 2.0 * np.eye(n)).astype(np.float32)


def _rhs(seed, w=1):
    b = np.random.default_rng(1000 + seed).standard_normal(
        (N, w) if w > 1 else (N,))
    return b.astype(np.float32)


def _plan():
    return FactorPlan.create((N, N), "float32", v=V)


def _fab(tmp_path, n=3, fault_plan=None, **pol):
    kw = dict(heartbeat_interval=0.05, heartbeat_timeout=1.0,
              suspect_after=2, dead_after=4)
    kw.update(pol)
    return fabric.local_fabric(
        n, str(tmp_path), policy=FabricPolicy(**kw),
        fault_plan=fault_plan,
        engine_kwargs={"max_batch_delay": 0.0})


def _wait_dead(fab, hid, timeout=20.0):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout:
        if fab.host_state(hid) == "dead":
            return time.perf_counter() - t0
        time.sleep(0.02)
    raise AssertionError(f"host {hid} never declared dead")


def _wait_recovery(fab, hid, timeout=20.0):
    """The recovery record lands after the dead flip; poll for it."""
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout:
        for rec in reversed(fab.stats()["recoveries"]):
            if rec["host"] == hid:
                return rec
        time.sleep(0.02)
    raise AssertionError(f"no recovery record for {hid}")


# --------------------------------------------------------------------------- #
# routing
# --------------------------------------------------------------------------- #


def test_router_determinism(tmp_path):
    """Placement is a pure function of (sid, live host ids): the owner
    map matches bare rendezvous() and reproduces across fabrics."""
    sids = [f"user-{i}" for i in range(12)]
    with _fab(tmp_path / "a") as fa:
        hosts = sorted(fa._hosts)
        for s in sids:
            fa.open(s, _plan(), _mk(1))
        owners_a = {s: fa.owner_of(s) for s in sids}
    assert owners_a == {s: rendezvous(s, hosts) for s in sids}
    with _fab(tmp_path / "b") as fb:
        for s in sids:
            fb.open(s, _plan(), _mk(1))
        assert {s: fb.owner_of(s) for s in sids} == owners_a


def test_rendezvous_remap_only_removed_host():
    """The HRW property the fail-over story rides: dropping one host
    moves ONLY that host's sids; every other mapping is unchanged."""
    hosts = ["h0", "h1", "h2", "h3"]
    sids = [f"s{i}" for i in range(200)]
    before = {s: rendezvous(s, hosts) for s in sids}
    survivors = [h for h in hosts if h != "h2"]
    after = {s: rendezvous(s, survivors) for s in sids}
    for s in sids:
        if before[s] == "h2":
            assert after[s] in survivors
        else:
            assert after[s] == before[s]
    # and the dead host owned a nontrivial share (the hash spreads)
    assert sum(1 for s in sids if before[s] == "h2") > 10


def test_open_duplicate_sid_refused(tmp_path):
    with _fab(tmp_path, n=2) as fab:
        fab.open("dup", _plan(), _mk(2))
        with pytest.raises(ValueError, match="already open"):
            fab.open("dup", _plan(), _mk(2))
        with pytest.raises(KeyError, match="unknown sid"):
            fab.solve("never-opened", _rhs(0))


# --------------------------------------------------------------------------- #
# the kill-one-host drill
# --------------------------------------------------------------------------- #


def test_kill_one_host_failover_bitwise_and_bounded(tmp_path):
    """The tentpole drill: kill the host owning sessions; detection +
    fail-over re-home its fleet on survivors from the last checkpoint;
    every session (including revived ones) solves BITWISE as before;
    recovery time is measured and bounded; the census is conserved.
    Before the kill, every fabric answer is the bare session's bits
    and within reach of the f64 oracle."""
    with _fab(tmp_path) as fab:
        ref, rhs = {}, {}
        for i in range(9):
            sid = f"drill-{i}"
            A = _mk(10 + i)
            fab.open(sid, _plan(), A)
            rhs[sid] = _rhs(i, w=2)
            ref[sid] = np.asarray(fab.solve(sid, rhs[sid]))
            bare = _plan().factor(A).solve(rhs[sid])
            assert np.array_equal(ref[sid], np.asarray(bare)), sid
            x64 = np.linalg.solve(A.astype(np.float64),
                                  rhs[sid].astype(np.float64))
            assert np.max(np.abs(ref[sid] - x64)) < 1e-3, sid
        victim = fab.owner_of("drill-0")
        moved = [s for s in ref if fab.owner_of(s) == victim]
        stay = {s: fab.owner_of(s) for s in ref
                if fab.owner_of(s) != victim}
        assert moved, "victim owned nothing — hash degenerated"
        fab._hosts[victim].kill()
        _wait_dead(fab, victim)
        # bounded recovery: the fail-over measured itself
        rec = _wait_recovery(fab, victim)
        assert rec["adopted"] == len(moved)
        assert rec["lost"] == 0
        assert rec["seconds"] < 30.0
        st = fab.stats()
        assert st["sessions"] == 9 and st["lost_sessions"] == 0
        # every session answers bitwise; survivors never moved
        for sid in ref:
            assert np.array_equal(
                np.asarray(fab.solve(sid, rhs[sid])), ref[sid]), sid
        for sid, h in stay.items():
            assert fab.owner_of(sid) == h
        for sid in moved:
            assert fab.owner_of(sid) != victim
            assert fab.host_state(fab.owner_of(sid)) == "alive"


def test_dead_host_requests_fail_structured_not_hang(tmp_path):
    """With detection disabled (huge heartbeat interval), a request
    routed at a killed host surfaces HostUnavailable immediately —
    the transport tear maps to a structured error, never a hang."""
    with _fab(tmp_path, n=2, heartbeat_interval=60.0) as fab:
        fab.open("s", _plan(), _mk(3))
        hid = fab.owner_of("s")
        fab._hosts[hid].kill()
        t0 = time.perf_counter()
        with pytest.raises(HostUnavailable) as ei:
            fab.solve("s", _rhs(3))
        assert time.perf_counter() - t0 < 10.0
        assert ei.value.retry_after >= 0.0
        assert ei.value.host == hid
        assert resilience.health_stats()["host_unavailable"] >= 1


@pytest.mark.parametrize("how", ["never_checkpointed", "disk_died"])
def test_never_checkpointed_session_reported_lost(tmp_path, how):
    """A killed host's sessions are unrecoverable when they never
    reached a checkpoint (durable_open off, no background
    checkpointing) or when, with one replica, the checkpoint directory
    died with the host (host-local disk): the fabric says so
    (structured, with the reason), conserves the count in stats, and
    lets the sid be reopened — re-factored, answering the f64
    oracle."""
    kw = {"durable_open": False} if how == "never_checkpointed" else {}
    with _fab(tmp_path, n=2, **kw) as fab:
        A = _mk(4)
        fab.open("gone", _plan(), A)
        victim = fab.owner_of("gone")
        fab._hosts[victim].kill()
        if how == "disk_died":
            ck = fab._hosts[victim].ckpt_dir
            os.rename(ck, ck + ".dead")
        _wait_dead(fab, victim)
        assert _wait_recovery(fab, victim)["lost"] == 1
        assert fab.stats()["lost_sessions"] == 1
        with pytest.raises(HostUnavailable, match="lost"):
            fab.solve("gone", _rhs(4))
        # a lost sid may be reopened (fresh state, back in service)
        fab.open("gone", _plan(), A)
        x = np.asarray(fab.solve("gone", _rhs(4)))
        x64 = np.linalg.solve(A.astype(np.float64),
                              _rhs(4).astype(np.float64))
        assert np.max(np.abs(x - x64)) < 1e-3
        assert fab.stats()["lost_sessions"] == 0


def test_failover_bounded_staleness_of_updates(tmp_path):
    """Background checkpointing bounds fail-over staleness: drift
    updates checkpointed before the kill survive it (the revived
    session solves bitwise WITH the update applied)."""
    with _fab(tmp_path, n=2, checkpoint_interval=0.1) as fab:
        fab.open("drift", _plan(), _mk(5))
        rng = np.random.default_rng(5)
        U = rng.standard_normal((N, 2)).astype(np.float32) * 0.1
        Vm = rng.standard_normal((N, 2)).astype(np.float32) * 0.1
        fab.update("drift", U, Vm)
        want = np.asarray(fab.solve("drift", _rhs(5)))
        victim = fab.owner_of("drift")
        # wait for two FULL background rounds started after the update
        base = fab.stats()["checkpoint_rounds"]
        deadline = time.perf_counter() + 20.0
        while time.perf_counter() < deadline:
            if fab.stats()["checkpoint_rounds"] >= base + 2:
                break
            time.sleep(0.05)
        assert fab.stats()["checkpoint_rounds"] >= base + 2
        assert fabric.latest_checkpoint(
            fab._hosts[victim].ckpt_dir) is not None
        fab._hosts[victim].kill()
        _wait_dead(fab, victim)
        # 'dead' flips before the synchronous fail-over lands: ride the
        # structured in-flight window on its own retry hints (the
        # fabric_drill pattern), bounded — a genuinely lost session
        # still surfaces as the final HostUnavailable
        deadline = time.perf_counter() + 20.0
        while True:
            try:
                got = np.asarray(fab.solve("drift", _rhs(5)))
                break
            except HostUnavailable as e:
                if time.perf_counter() > deadline:
                    raise
                time.sleep(min(max(e.retry_after, 0.01), 0.25))
        assert np.array_equal(got, want)


# --------------------------------------------------------------------------- #
# live migration
# --------------------------------------------------------------------------- #


def test_migration_bitwise_with_drift(tmp_path):
    """A migrated session — drift updates and all — answers bitwise on
    its new host; ownership flips; the source forgets it."""
    with _fab(tmp_path) as fab:
        fab.open("mig", _plan(), _mk(6))
        rng = np.random.default_rng(6)
        U = rng.standard_normal((N, 2)).astype(np.float32) * 0.1
        Vm = rng.standard_normal((N, 2)).astype(np.float32) * 0.1
        fab.update("mig", U, Vm)
        b = _rhs(6, w=3)
        want = np.asarray(fab.solve("mig", b))
        src = fab.owner_of("mig")
        tgt = fab.migrate("mig")
        assert tgt != src and fab.owner_of("mig") == tgt
        assert np.array_equal(np.asarray(fab.solve("mig", b)), want)
        # the source host no longer has the session
        with pytest.raises(KeyError):
            fab._hosts[src].solve("mig", b)
        assert resilience.health_stats()["sessions_migrated"] >= 1


def test_migration_crash_leaves_source_intact(tmp_path):
    """An injected crash at the hand-off barrier (record written, not
    yet adopted) aborts the migration with the session still owned by
    — and solving bitwise on — the source."""
    plan = FaultPlan([FaultSpec(site="migrate", kind="crash", count=1)])
    with _fab(tmp_path, fault_plan=plan) as fab:
        fab.open("crash", _plan(), _mk(7))
        b = _rhs(7)
        want = np.asarray(fab.solve("crash", b))
        src = fab.owner_of("crash")
        with pytest.raises(InjectedFault):
            fab.migrate("crash")
        assert fab.owner_of("crash") == src
        assert np.array_equal(np.asarray(fab.solve("crash", b)), want)
        # fault budget consumed: the retry goes through
        tgt = fab.migrate("crash")
        assert fab.owner_of("crash") == tgt != src
        assert np.array_equal(np.asarray(fab.solve("crash", b)), want)


def test_migrate_picks_least_loaded_target(tmp_path):
    # heartbeats off (huge interval) so the manual load feeds below
    # aren't overwritten by real probe deltas mid-test
    with _fab(tmp_path, heartbeat_interval=60.0) as fab:
        fab.open("ll", _plan(), _mk(8))
        src = fab.owner_of("ll")
        others = [h for h in sorted(fab._hosts) if h != src]
        # seed the load estimator: others[0] busy, others[1] idle
        fab.load.feed(others[0], {"solves": 0, "seconds": 1.0,
                                  "pending": 50})
        fab.load.feed(others[1], {"solves": 100, "seconds": 1.0,
                                  "pending": 0})
        assert fab.migrate("ll") == others[1]


# --------------------------------------------------------------------------- #
# degraded admission + retry hints
# --------------------------------------------------------------------------- #


def test_degraded_admission_below_min_live(tmp_path):
    """Below min_live, `open` refuses with FleetDegraded (structured,
    counted) while existing sessions keep answering on survivors."""
    with _fab(tmp_path, n=2, min_live=2) as fab:
        fab.open("pre", _plan(), _mk(9))
        victim = [h for h in sorted(fab._hosts)
                  if h != fab.owner_of("pre")][0]
        fab._hosts[victim].kill()
        _wait_dead(fab, victim)
        with pytest.raises(FleetDegraded) as ei:
            fab.open("post", _plan(), _mk(9))
        assert ei.value.live == 1 and ei.value.total == 2
        assert ei.value.retry_after >= 0.0
        fab.solve("pre", _rhs(9))  # survivors still serve
        assert resilience.health_stats()["fleet_degraded"] >= 1


def test_retry_after_rides_measured_drain_rate(tmp_path):
    """The HostUnavailable retry hint comes from the load estimator's
    smoothed drain rates (clamped to the policy band)."""
    with _fab(tmp_path, n=2, heartbeat_interval=60.0) as fab:
        # seed measured rates: the fleet drains 20 solves/s
        for hid in sorted(fab._hosts):
            fab.load.feed(hid, {"solves": 10, "seconds": 1.0,
                                "pending": 0})
        hint = fab._retry_hint(backlog=10)
        assert hint == pytest.approx(10 / 20.0, rel=0.01)
        pol = fab.policy
        assert pol.retry_floor <= hint <= pol.retry_ceil


def test_route_fault_maps_to_host_unavailable(tmp_path):
    with _fab(tmp_path, n=2) as fab:
        fab.open("r", _plan(), _mk(11))
        # arm the fault AFTER open (open routes too and would eat it)
        fab._faults = FaultPlan(
            [FaultSpec(site="route", kind="crash", count=1)])
        with pytest.raises(HostUnavailable):
            fab.solve("r", _rhs(11))
        fab.solve("r", _rhs(11))  # budget consumed; traffic resumes


# --------------------------------------------------------------------------- #
# heartbeat hysteresis
# --------------------------------------------------------------------------- #


def test_heartbeat_hysteresis_suspect_then_recover(tmp_path):
    """Two injected probe failures walk the host alive -> suspect
    (below dead_after it is NOT declared dead and loses nothing); the
    next healthy probe walks it back to alive with misses reset."""
    base = resilience.health_stats()
    plan = FaultPlan([FaultSpec(site="heartbeat", kind="crash",
                                count=2)])
    with _fab(tmp_path, n=1, fault_plan=plan, suspect_after=2,
              dead_after=6) as fab:
        fab.open("hys", _plan(), _mk(12))
        # the suspect transition bumps a monotone counter — poll that
        # (the suspect WINDOW itself is one heartbeat wide and a state
        # poll could miss it)
        deadline = time.perf_counter() + 20.0
        while time.perf_counter() < deadline:
            h = resilience.health_stats()
            if h["hosts_suspected"] > base["hosts_suspected"]:
                break
            time.sleep(0.02)
        h = resilience.health_stats()
        assert h["hosts_suspected"] > base["hosts_suspected"], \
            "host never reached suspect"
        assert h["heartbeat_misses"] >= base["heartbeat_misses"] + 2
        # fault budget spent: the next healthy probe walks it back
        deadline = time.perf_counter() + 20.0
        while time.perf_counter() < deadline:
            if fab.host_state("h0") == "alive":
                break
            time.sleep(0.02)
        assert fab.host_state("h0") == "alive"
        # suspect never escalated: no fail-over ran, sessions in place
        assert fab.stats()["recoveries"] == []
        fab.solve("hys", _rhs(12))


def test_host_kill_fault_site_drives_failover(tmp_path):
    """The seeded host_kill fault kills a whole host from inside the
    heartbeat loop; detection + fail-over then run end-to-end. The
    fault is armed once the sessions are open, so the kill cannot land
    mid-admission."""
    plan = FaultPlan([FaultSpec(site="host_kill", kind="kill",
                                count=0)])
    with _fab(tmp_path, fault_plan=plan, durable_open=True) as fab:
        ref, rhs = {}, {}
        for i in range(4):
            sid = f"hk-{i}"
            fab.open(sid, _plan(), _mk(20 + i))
            rhs[sid] = _rhs(20 + i)
            ref[sid] = np.asarray(fab.solve(sid, rhs[sid]))
        plan.specs[0].count = 1  # arm: the next heartbeat kills a host
        deadline = time.perf_counter() + 20.0
        while time.perf_counter() < deadline:
            if any(fab.host_state(h) == "dead"
                   for h in sorted(fab._hosts)):
                break
            time.sleep(0.02)
        dead = [h for h in sorted(fab._hosts)
                if fab.host_state(h) == "dead"]
        assert len(dead) == 1
        assert _wait_recovery(fab, dead[0])["lost"] == 0
        for sid in ref:
            assert np.array_equal(
                np.asarray(fab.solve(sid, rhs[sid])), ref[sid]), sid
        assert resilience.health_stats()["host_failovers"] >= 1


# --------------------------------------------------------------------------- #
# telemetry surfaces
# --------------------------------------------------------------------------- #


def test_fabric_stats_merge_into_serve_stats(tmp_path):
    with _fab(tmp_path, n=2) as fab:
        fab.open("tel", _plan(), _mk(13))
        ss = profiler.serve_stats()
        fs = ss["fabric"]
        assert fs["fabrics"] >= 1
        assert fs["hosts"] >= 2
        assert fs["sessions"] >= 1
        for k in ("host_unavailable", "fleet_degraded",
                  "heartbeat_misses", "hosts_died", "host_failovers",
                  "sessions_failed_over", "sessions_migrated"):
            assert k in ss["health"]
    # closed fabrics drop out of the aggregate census
    assert fab._closed
    assert fab not in [f for f in list(fabric._FABRICS)
                       if not f._closed]


def test_host_load_estimator_window_plumbing(tmp_path):
    """Heartbeats feed CounterWindow deltas into the estimator: after
    traffic, the owning host reports a positive drain rate."""
    with _fab(tmp_path, n=2) as fab:
        fab.open("load", _plan(), _mk(14))
        hid = fab.owner_of("load")
        for i in range(10):
            fab.solve("load", _rhs(30 + i))
        deadline = time.perf_counter() + 10.0
        while time.perf_counter() < deadline:
            rates = fab.load.stats()
            if rates.get(hid, {}).get("drain_per_s", 0.0) > 0.0:
                break
            time.sleep(0.05)
        assert fab.load.stats()[hid]["drain_per_s"] > 0.0


def test_checkpoint_generations_pruned(tmp_path):
    # compact_every=1: every generation self-contained, so the prune
    # bound is exactly checkpoint_keep (the pre-§35 contract; delta
    # chains are covered in tests/test_scale.py)
    with _fab(tmp_path, n=1, checkpoint_keep=2,
              checkpoint_compact_every=1) as fab:
        fab.open("gen", _plan(), _mk(15))
        for _ in range(4):
            fab.checkpoint_all()
        ckpt_dir = fab._hosts["h0"].ckpt_dir
        gens = [d for d in os.listdir(ckpt_dir)
                if d.startswith("fleet-")]
        assert len(gens) <= 2
        snap = fabric.latest_checkpoint(ckpt_dir)
        assert snap is not None
        assert fabric.checkpoint_sids(snap) == {
            "gen": fabric.record_name("gen")}


# --------------------------------------------------------------------------- #
# ProcessHost request plumbing (ISSUE 16): timeout composition + wire
# --------------------------------------------------------------------------- #


class _NeverReplies:
    """A Connection stand-in that accepts sends and never answers —
    the shape of a wedged (not dead) worker."""

    def __init__(self):
        self.sent = []

    def send(self, msg):
        self.sent.append(msg)

    def close(self):
        pass


def test_processhost_per_op_timeout_is_transport_shaped(tmp_path):
    """Regression (ISSUE 16): on Python 3.10
    `concurrent.futures.TimeoutError` is NOT the builtin TimeoutError
    (and not an OSError), so ProcessHost._call's old `except
    TimeoutError` never caught it — the pending entry leaked and the
    raw futures timeout escaped `_TRANSPORT_ERRORS`, reaching callers
    unstructured. Now a slow op under a tight per-op timeout raises
    the BUILTIN TimeoutError (OSError-shaped, so the front maps it to
    HostUnavailable), pops its pending entry, and the per-op timeout
    beats call_timeout."""
    h = fabric.ProcessHost("hx", str(tmp_path / "hx"),
                           call_timeout=30.0, wire="pickle")
    h._conn = _NeverReplies()
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError) as ei:
        h._call("stats", timeout=0.15)
    dt = time.perf_counter() - t0
    assert isinstance(ei.value, fabric._TRANSPORT_ERRORS)
    assert 0.1 < dt < 5.0          # the per-op timeout won, not 30s
    assert h._pending == {}        # no pending-entry leak
    assert h._conn.sent            # the op really went out


def test_processhost_call_timeout_fallback(tmp_path):
    """timeout=None composes predictably: the handle's call_timeout
    applies, through the same single `_deadline` rule as per-op
    timeouts."""
    h = fabric.ProcessHost("hy", str(tmp_path / "hy"),
                           call_timeout=0.15, wire="pickle")
    h._conn = _NeverReplies()
    with pytest.raises(TimeoutError):
        h._call("stats")
    assert h._pending == {}


def test_processhost_rejects_unknown_wire():
    with pytest.raises(ValueError, match="wire"):
        fabric.ProcessHost("hz", "/tmp/unused-hz", wire="carrier-pigeon")


def test_processhost_second_chip_worker_refused(tmp_path, monkeypatch):
    """One process per chip: with a live worker already free to claim
    the TPU, a second such worker raises at start() before it spawns;
    workers pinned to the CPU never claim it."""
    class Live:
        host_id = "h0"

        class _proc:
            @staticmethod
            def poll():
                return None

    monkeypatch.setattr(fabric, "_chip_workers", [Live()])
    h = fabric.ProcessHost("h1", str(tmp_path / "h1"), env={})
    with pytest.raises(RuntimeError, match="one process per chip"):
        h.start()
    assert h._proc is None and not (tmp_path / "h1").exists()
    assert not fabric._may_claim_chip({"JAX_PLATFORMS": "cpu"})
    assert fabric._may_claim_chip({"JAX_PLATFORMS": "tpu,cpu"})


class _AlwaysFullWire:
    """A WireClient stand-in whose request ring never drains — the
    shape of a torn pipe with replies that will never land. Optionally
    fails the owning host mid-pacing, like the recv thread observing
    the pipe EOF while echo_many retries."""

    def __init__(self, host=None, fail_on_call=None):
        self._host = host
        self._fail_on_call = fail_on_call
        self.calls = 0
        self.failed_with = None

    def payload_fits(self, nbytes):
        return True

    def submit_many(self, entries):
        self.calls += 1
        if self.calls == self._fail_on_call:
            self._host._fail(ConnectionError("pipe torn mid-pacing"))
        raise fabric.wire_mod.RingFull("ring full", retry_after=1e-3)

    def fail(self, exc):
        self.failed_with = exc


def test_processhost_fail_also_fails_wire_client(tmp_path):
    """Regression (ISSUE 16 review): `_fail` (a torn pipe) must also
    fail the shm wire client — otherwise the ring-backpressure retry
    loops keep pacing against a ring no reply will ever drain."""
    h = fabric.ProcessHost("hw", str(tmp_path / "hw"))
    w = _AlwaysFullWire()
    h._wire = w
    h._fail(ConnectionError("torn"))
    assert isinstance(w.failed_with, ConnectionError)


def test_echo_many_ring_full_pacing_observes_death(tmp_path):
    """Regression (ISSUE 16 review): echo_many's RingFull pacing loop
    re-checks host death each lap — a pipe torn mid-burst raises a
    structured ConnectionError instead of spinning forever, and the
    unsent tail's pending entries are reclaimed."""
    h = fabric.ProcessHost("hv", str(tmp_path / "hv"))
    h._wire = _AlwaysFullWire(host=h, fail_on_call=2)
    with pytest.raises(ConnectionError, match="pacing|torn"):
        h.echo_many([np.ones(8, np.float32)] * 3)
    assert h._pending == {}


def test_echo_many_ring_full_pacing_bounded_by_op_timeout(tmp_path):
    """A ring that stays full with the host still alive cannot pace
    past the op timeout: echo_many raises the builtin TimeoutError
    (transport-shaped) and reclaims the unsent pending entries."""
    h = fabric.ProcessHost("hu", str(tmp_path / "hu"),
                           call_timeout=0.15)
    h._wire = _AlwaysFullWire()
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError, match="stayed full"):
        h.echo_many([np.ones(8, np.float32)] * 3)
    assert time.perf_counter() - t0 < 5.0
    assert h._pending == {}


def test_wire_corrupt_codec_rehydrates_kind_and_host():
    """Regression (ISSUE 16 review): a worker-side WireCorrupt (corrupt
    REQUEST record) crosses the pickle control plane intact — the front
    re-raises the ConnectionError-shaped type with kind/host, not a
    generic RuntimeError."""
    e = resilience.WireCorrupt("request record torn",
                               kind="stale_generation", host="h9")
    with pytest.raises(resilience.WireCorrupt) as ei:
        fabric._raise_wire(fabric._encode_exc(e))
    assert ei.value.kind == "stale_generation"
    assert ei.value.host == "h9"
    assert isinstance(ei.value, ConnectionError)
