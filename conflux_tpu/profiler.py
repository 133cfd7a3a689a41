"""Scoped-region profiler — the role of the vendored semiprof
(`libs/semiprof/include/semiprof/semiprof.hpp:38-52`) and the PE/PL/PP/PC
macro shims (`src/conflux/lu/profiler.hpp`, `cholesky/CholeskyProfiler.h`).

Host side: `region(name)` is both a context manager and a decorator; it
accumulates host wall time and call counts, and emits a
`jax.profiler.TraceAnnotation`, so the region lands on the trace's host
planes on the clock the device events are put on. `report()` prints a
semiprof-style table sorted by total time; `clear()` resets.

Device side: the jitted programs name their phases with `jax.named_scope`
in the reference's step vocabulary (`step0_reduce` ... `step8_perm`,
`reduceA11` ..., `solve_fwd` ..., `conflux_opt.hpp:635,777,1346`), and each
shard_map program carries its own name (`jit_lu_factor`, `jit_lu_solve`,
...). `scope_map(hlo_text)` credits every instruction of a compiled
program to a phase; `phase_table(trace_dir, hlo_text)` joins it with a
`trace(logdir)` capture (`jax.profiler.trace`, read back with
`jax.profiler.ProfileData`) into a per-phase device-time table.
"""

from __future__ import annotations

import contextlib
import functools
import re
import threading
import time
from collections import defaultdict
from typing import NamedTuple

import jax

# the region tables are written from every serve-engine worker thread
# (dispatcher, drain, watchdog) plus the caller: the += below is a
# read-modify-write, so unlocked it silently loses updates (a conflint
# CFX-LOCK finding; regression test in tests/test_analysis.py)
_PROF_LOCK = threading.Lock()
_times: dict[str, float] = defaultdict(float)    # guarded-by: _PROF_LOCK
_counts: dict[str, int] = defaultdict(int)       # guarded-by: _PROF_LOCK

# set by conflux_tpu.analysis.lockcheck while a watch() is active: the
# hook observes which locks are held when a dispatch region is entered.
# One attribute read per region when inactive.
_dispatch_hook = None


# ---------------------------------------------------------------------- #
# XLA compile counter (the per-DEVICE zero-compile gate's instrument)
# ---------------------------------------------------------------------- #
#
# One jitted program traces ONCE per shape signature but compiles one
# executable PER DEVICE it dispatches on — the plan-level trace counters
# (`FactorPlan.trace_counts`) therefore cannot see a cold lane paying a
# first-dispatch compile on its own device. jax's monitoring stream
# reports every backend compile; counting it here gives tests and
# benches the exact "zero compiles after prewarm, on every lane" gate.

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = 0  # guarded-by: _PROF_LOCK


def _count_compile(event, duration, **kwargs) -> None:
    global _compiles
    if event == _COMPILE_EVENT:
        with _PROF_LOCK:
            _compiles += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


def compile_count() -> int:
    """Total XLA backend compiles this process has paid (all devices,
    all programs — monotone; window it by differencing)."""
    with _PROF_LOCK:
        return _compiles


@contextlib.contextmanager
def region(name: str):
    """Profiled host region: `with profiler.region('serve.solve'): ...`.
    Counts the call and its wall time, and emits a host span of the same
    name into an active `jax.profiler` trace. It writes nothing into the
    metadata of programs traced inside it."""
    hook = _dispatch_hook
    if hook is not None:
        hook(name)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    dt = time.perf_counter() - t0
    with _PROF_LOCK:
        _times[name] += dt
        _counts[name] += 1


def profiled(name: str):
    """Decorator form of :func:`region`."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with region(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def _snapshot() -> tuple[dict, dict]:
    """Consistent copy of the region tables (readers never iterate the
    live dicts while a worker thread is inserting)."""
    with _PROF_LOCK:
        return dict(_times), dict(_counts)


def report() -> str:
    """semiprof-style table (reference README.md:120-165 output shape)."""
    times, counts = _snapshot()
    lines = [f"{'REGION':<32}{'CALLS':>8}{'THREAD':>12}{'WALL':>12}{'%':>8}"]
    total = sum(times.values()) or 1.0
    for name, t in sorted(times.items(), key=lambda kv: -kv[1]):
        lines.append(
            f"{name:<32}{counts[name]:>8}{t:>12.3f}{t:>12.3f}{100 * t / total:>8.1f}"
        )
    out = "\n".join(lines)
    print(out)
    return out


def clear() -> None:
    with _PROF_LOCK:
        _times.clear()
        _counts.clear()
    # the resilience and tier outcome counters are global like the
    # region tables, so they reset together (engine counters and the
    # ResidentSet gauges live on their objects and survive — see
    # serve_stats)
    from conflux_tpu import resilience, tier

    resilience.clear_health()
    tier.clear_tier()


def timings() -> dict[str, tuple[int, float]]:
    times, counts = _snapshot()
    return {k: (counts[k], times[k]) for k in times}


def trace(logdir: str):
    """Device-level tracing: `with profiler.trace('/tmp/trace'): ...`"""
    return jax.profiler.trace(logdir)


# --------------------------------------------------------------------------- #
# Serving-phase counters (the amortization view)
# --------------------------------------------------------------------------- #

# the serve layer (conflux_tpu/serve.py) wraps its call sites in
# region("serve.<phase>"), so bench/ops read amortization ratios here
# without instrumenting anything themselves
SERVE_PHASES = ("factor", "solve", "update", "refactor")

# live ServeEngines (conflux_tpu/engine.py) register here (weakly — an
# engine dies with its owner) so serve_stats() can fold queue/coalescing/
# latency counters in next to the per-phase wall times. Unlocked, two
# concurrent _live_engines() calls could both .remove() the same dead
# ref (ValueError) — another conflint CFX-LOCK find.
_ENGINE_REFS: list = []  # guarded-by: _PROF_LOCK


def register_engine(engine) -> None:
    """Called by ServeEngine.__init__; weak so engines are collectable."""
    import weakref

    ref = weakref.ref(engine)
    with _PROF_LOCK:
        _ENGINE_REFS.append(ref)


def _live_engines() -> list:
    """Snapshot the live engines, pruning dead refs. Only the registry
    walk holds the lock — callers talk to the engines (their own locks)
    outside it, so profiler-lock -> engine-lock never nests."""
    alive = []
    with _PROF_LOCK:
        dead = []
        for ref in _ENGINE_REFS:
            e = ref()
            (alive if e is not None else dead).append(e if e is not None
                                                      else ref)
        for ref in dead:
            _ENGINE_REFS.remove(ref)
    return alive


def engine_stats() -> dict:
    """Aggregate ServeEngine counters across live engines: queue depth
    high-water mark (max), batches dispatched / requests / sheds (sums),
    mean coalesced batch size (request-weighted), and p50/p95/p99 request
    latency over the engines' merged rolling windows — plus the factor
    lane's cold-start counters (factor batches, mean coalesced factor
    batch size, pad-waste ratio, session-open latency percentiles),
    merged the same way. Zeroes when no engine is alive."""
    engines = _live_engines()
    out = {"engines": len(engines), "requests": 0, "completed": 0,
           "shed": 0, "batches": 0, "queue_peak": 0,
           "coalesced_mean": 0.0, "latency_p50_ms": 0.0,
           "latency_p95_ms": 0.0, "latency_p99_ms": 0.0,
           "factor_requests": 0, "factor_batches": 0,
           "factor_coalesced_mean": 0.0, "factor_pad_waste": 0.0,
           "factor_latency_p50_ms": 0.0, "factor_latency_p95_ms": 0.0,
           "factor_latency_p99_ms": 0.0,
           "lanes": 0, "lane_batches_max": 0, "lane_batches_min": 0,
           "lane_occupancy_max": 0.0, "lane_sheds": 0,
           "gang_batches": 0, "gang_coalesced_mean": 0.0,
           "gang_sessions": 0, "gang_opportunity": 0,
           "stack_exclusions": {}}
    coalesced = 0
    fcoalesced = fslots = fpad = 0
    gcoalesced = 0
    samples: list = []
    fsamples: list = []
    for e in engines:
        s = e.stats()
        out["requests"] += s["requests"]
        out["completed"] += s["completed"]
        out["shed"] += s["shed"]
        out["batches"] += s["batches"]
        out["queue_peak"] = max(out["queue_peak"], s["queue_peak"])
        coalesced += s["coalesced_requests"]
        out["factor_requests"] += s["factor_requests"]
        out["factor_batches"] += s["factor_batches"]
        fcoalesced += s["factor_coalesced_requests"]
        fslots += s["factor_slots"]
        fpad += s["factor_pad_slots"]
        samples.extend(e.latency_samples())
        fsamples.extend(e.factor_latency_samples())
        # gang-stacked serving (PR 10): stacked dispatch counters, gang
        # population, and the per-reason exclusion trace, fleet-merged
        out["gang_batches"] += s.get("gang_batches", 0)
        gcoalesced += s.get("gang_coalesced_requests", 0)
        out["gang_sessions"] += s.get("gang", {}).get("sessions", 0)
        out["gang_opportunity"] += s.get("gang_opportunity", 0)
        for k, v in s.get("stack_exclusions", {}).items():
            out["stack_exclusions"][k] = \
                out["stack_exclusions"].get(k, 0) + v
        # per-lane fleet view (PR 9): lane count and the dispatch-balance
        # extremes across every engine's lanes — the one-glance answer
        # to "is one device starving while another drowns"
        for ln in s.get("lanes", ()):
            out["lanes"] += 1
            b = ln.get("batches", 0) + ln.get("factor_batches", 0)
            out["lane_batches_max"] = max(out["lane_batches_max"], b)
            out["lane_batches_min"] = (b if out["lanes"] == 1
                                       else min(out["lane_batches_min"], b))
            out["lane_occupancy_max"] = max(out["lane_occupancy_max"],
                                            ln.get("occupancy", 0.0))
            out["lane_sheds"] += ln.get("sheds", 0)
    if out["batches"]:
        out["coalesced_mean"] = coalesced / out["batches"]
    if out["gang_batches"]:
        out["gang_coalesced_mean"] = gcoalesced / out["gang_batches"]
    if out["factor_batches"]:
        out["factor_coalesced_mean"] = fcoalesced / out["factor_batches"]
    if fslots:
        out["factor_pad_waste"] = fpad / fslots
    if samples or fsamples:
        from conflux_tpu.engine import _percentile

        for xs, prefix in ((samples, "latency"),
                           (fsamples, "factor_latency")):
            if not xs:
                continue
            xs.sort()
            for pct in (50, 95, 99):
                out[f"{prefix}_p{pct}_ms"] = 1e3 * _percentile(xs, pct)
    return out


def qos_stats() -> dict:
    """Merged multi-tenant QoS rows across live engines (DESIGN §30):
    per-class counters summed, per-class latency percentiles and SLO
    attainment recomputed over the engines' merged per-class rolling
    windows, and the per-tenant ledger totals. Engines that never saw
    classified traffic contribute nothing; with none, the dict is
    empty shells — the `serve_stats()['qos']` surface."""
    engines = _live_engines()
    out: dict = {"engines": 0, "classes": {}, "tenants": {}}
    samples: dict = {}
    for e in engines:
        q = e.counters().get("qos")
        if not q:
            continue
        out["engines"] += 1
        for k, row in q["classes"].items():
            dst = out["classes"].setdefault(k, {
                "tenant": row["tenant"], "tier": row["tier"],
                "priority": row["priority"], "weight": row["weight"],
                "slo_ms": row["slo_ms"], "requests": 0,
                "completed": 0, "failed": 0, "throttled": 0})
            for c in ("requests", "completed", "failed", "throttled"):
                dst[c] += row[c]
        for t, row in q["tenants"].items():
            dst = out["tenants"].setdefault(t, {
                "weight": row["weight"], "pending": 0, "admitted": 0,
                "throttled": 0})
            dst["pending"] += row["pending"]
            dst["admitted"] += row["admitted"]
            dst["throttled"] += row["throttled"]
        for k, xs in e.qos_latency_samples().items():
            samples.setdefault(k, []).extend(xs)
    if samples:
        from conflux_tpu.engine import _percentile

        for k, xs in samples.items():
            row = out["classes"].get(k)
            if row is None or not xs:
                continue
            xs.sort()
            row["latency_samples"] = len(xs)
            for pct in (50, 95, 99):
                row[f"latency_p{pct}_ms"] = 1e3 * _percentile(xs, pct)
            slo_ms = row.get("slo_ms")
            if slo_ms is not None:
                within = sum(1 for x in xs if 1e3 * x <= slo_ms)
                row["slo_attainment_pct"] = round(
                    100.0 * within / len(xs), 2)
    return out


def serve_stats() -> dict:
    """Per-phase serving counters from the `serve.*` regions.

    Returns {phase: {'count', 'wall_s'}} for factor / solve / update /
    refactor plus two derived amortization ratios: 'solves_per_factor'
    (how many substitutions each O(N^3) factorization amortized over —
    the serving win) and 'updates_per_refactor' (how many O(N^2 k)
    refreshes each drift-policy refactorization amortized over). Phases
    never entered report zero; `clear()` resets alongside everything
    else. An 'engine' sub-dict carries the ServeEngine counters
    (:func:`engine_stats`) — those live on the engines themselves, so
    `clear()` does not reset them. A 'health' sub-dict carries the
    resilience outcome counters (`conflux_tpu.resilience.health_stats`:
    guard trips, staging isolations, survivor re-dispatches, escalation
    rungs, deadline evictions, quarantine transitions, watchdog trips,
    injected faults) — global like the region tables, so `clear()`
    resets them too. Reliability and throughput read off ONE surface.
    """
    times, counts = _snapshot()
    out: dict = {}
    for ph in SERVE_PHASES:
        key = f"serve.{ph}"
        out[ph] = {"count": counts.get(key, 0),
                   "wall_s": times.get(key, 0.0)}
    factors = out["factor"]["count"] + out["refactor"]["count"]
    out["solves_per_factor"] = (out["solve"]["count"] / factors
                                if factors else 0.0)
    refac = out["refactor"]["count"]
    out["updates_per_refactor"] = (out["update"]["count"] / refac
                                   if refac else float("inf")
                                   if out["update"]["count"] else 0.0)
    out["engine"] = engine_stats()
    from conflux_tpu import resilience, tier

    out["health"] = resilience.health_stats()
    # the tier sub-dict: spill/revive counters + fault-in latency
    # percentiles (global, reset by clear()) and the per-tier
    # population/byte gauges merged across live ResidentSets (live on
    # the managers, surviving clear() like engine counters)
    out["tier"] = tier.tier_stats()
    # the fabric sub-dict: host census + fail-over/migration gauges
    # merged across live ServeFabric fronts (DESIGN §28); like engine
    # counters these live on the fabrics and survive clear(). The
    # fabric EVENT counters (host_unavailable, heartbeat_misses,
    # hosts_died, sessions_failed_over, ...) ride the 'health' dict
    from conflux_tpu import fabric

    out["fabric"] = fabric.fabric_stats()
    # the qos sub-dict: per-class/per-tenant counters, percentiles and
    # SLO attainment merged across live engines (DESIGN §30); like
    # engine counters these live on the engines and survive clear().
    # The THROTTLE event counters (tenant_throttled, per-class
    # tenant_throttled[t/tier] / engine_saturated[t/tier]) ride the
    # 'health' dict
    out["qos"] = qos_stats()
    return out


# --------------------------------------------------------------------------- #
# Windowed telemetry (rolling deltas — the adaptive controller's input)
# --------------------------------------------------------------------------- #

# engine.stats() keys that are monotone counters (windowed by
# differencing); everything else in the engine dict is a gauge or a
# derived ratio and passes through / is recomputed over the window
_ENGINE_COUNTERS = (
    "requests", "completed", "failed", "shed", "batches",
    "coalesced_requests", "width_capped", "factor_requests",
    "factor_batches", "factor_coalesced_requests", "factor_slots",
    "factor_pad_slots", "gang_batches", "gang_coalesced_requests",
    "gang_opportunity",
)
# the extra per-class counters a qos_class=-scoped StatsWindow windows
# (sourced from counters()['qos']['classes'][key], DESIGN §30)
_QOS_WINDOW_COUNTERS = (
    "qos_requests", "qos_completed", "qos_failed", "qos_throttled",
)
# tier.tier_stats() keys that are NOT counters: per-manager population/
# byte gauges and the latency percentiles (recomputed cumulatively)
_TIER_GAUGES = frozenset({
    "managed_sessions", "resident_sessions", "host_sessions",
    "disk_sessions", "corrupt_sessions", "device_bytes",
    "device_bytes_high_water", "resident_high_water", "host_bytes",
    "disk_bytes", "fault_in_p50_ms", "fault_in_p95_ms",
    "fault_in_p99_ms",
})


def _diff(cur: dict, prev: dict, keys=None) -> dict:
    """Per-key counter deltas with reset detection — the `clear()`
    contract: a counter that went BACKWARDS mid-window was reset, so
    the window reports the post-clear count (everything that landed
    after the reset) instead of a negative. Counts that landed between
    the previous window and the reset are lost with the reset itself —
    window continuity cannot survive a cumulative reset, but the delta
    stays non-negative and cumulative consumers (serve_stats) are
    untouched either way."""
    if keys is None:
        keys = [k for k, v in cur.items() if isinstance(v, (int, float))]
    out = {}
    for k in keys:
        c, p = cur.get(k, 0), prev.get(k, 0)
        out[k] = c - p if c >= p else c
    return out


class StatsWindow:
    """Rolling-window deltas of the serving telemetry.

    Construction snapshots the cumulative counters; each `delta()` call
    returns what changed since the PREVIOUS `delta()` (or construction)
    and advances the window. Counters are differenced (clamped at zero
    across `clear()` — see `_diff`); population/byte gauges pass
    through; latency percentiles are recomputed over ONLY the samples
    that completed inside the window, via per-engine sample-sequence
    tokens (`ServeEngine.latency_window`), not the engines' cumulative
    rolling windows. Nothing here is destructive: any number of windows
    coexist with each other and with every cumulative consumer.

    `engine=None` windows the merged `serve_stats()` surface across all
    live engines; passing a specific engine windows that engine's own
    counters (what `conflux_tpu.control.AdaptiveController` consumes).

    `qos_class=` ('tenant/tier', DESIGN §30) scopes the LATENCY half of
    the window to one QoS class: samples come from the engines'
    per-class rings (`ServeEngine.qos_latency_window` — so the
    percentiles are the class's own tail, not the blended one) and the
    delta grows `qos_requests`/`qos_completed`/`qos_failed`/
    `qos_throttled` counters for the class; the engine-wide counters
    still ride along. Any number of class windows coexist with each
    other, with the controller's own window, and with every cumulative
    consumer — the §24 non-destructive contract, per class.
    """

    def __init__(self, engine=None, qos_class: str | None = None):
        import weakref

        self._engine = None if engine is None else weakref.ref(engine)
        self._qos_class = qos_class
        # per-engine latency sample-sequence tokens, weakly keyed so a
        # dead engine drops its token with itself
        self._tokens: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        self._prev: dict | None = None
        self._t_prev = time.perf_counter()
        self.delta()  # prime the baseline snapshot

    def _engines(self) -> list:
        if self._engine is not None:
            e = self._engine()
            return [] if e is None else [e]
        return _live_engines()

    def _snapshot(self) -> tuple[dict, list, list]:
        """(cumulative snapshot, window latency samples, window factor
        latency samples)."""
        engines = self._engines()
        eng = {k: 0 for k in _ENGINE_COUNTERS}
        if self._qos_class is not None:
            eng.update({k: 0 for k in _QOS_WINDOW_COUNTERS})
        eng["pending"] = 0
        bucket_hits: dict[int, int] = {}
        fbucket_hits: dict[int, int] = {}
        lats: list = []
        flats: list = []
        for e in engines:
            # counters() skips stats()'s percentile sorts — the window
            # computes its own percentiles from the token-windowed
            # samples below, so the cumulative ones would be wasted work
            s = e.counters() if hasattr(e, "counters") else e.stats()
            for k in _ENGINE_COUNTERS:
                eng[k] += s.get(k, 0)
            eng["pending"] += s["pending"]
            for w, n in s.get("bucket_hits", {}).items():
                bucket_hits[w] = bucket_hits.get(w, 0) + n
            for bb, n in s.get("factor_bucket_hits", {}).items():
                fbucket_hits[bb] = fbucket_hits.get(bb, 0) + n
            tok, ftok = self._tokens.get(e, (None, None))
            if self._qos_class is None:
                tok, new = e.latency_window(tok)
            else:
                # the class's OWN ring: this window's percentiles are
                # the class tail, not the engine-blended one
                tok, new = e.qos_latency_window(self._qos_class, tok)
                row = (s.get("qos") or {}).get("classes", {}).get(
                    self._qos_class, {})
                for c in ("requests", "completed", "failed",
                          "throttled"):
                    eng[f"qos_{c}"] += row.get(c, 0)
            ftok, fnew = e.factor_latency_window(ftok)
            self._tokens[e] = (tok, ftok)
            lats.extend(new)
            flats.extend(fnew)
        times, counts = _snapshot()
        cur = {
            "engine": eng,
            "bucket_hits": bucket_hits,
            "factor_bucket_hits": fbucket_hits,
            "phases": {ph: {"count": counts.get(f"serve.{ph}", 0),
                            "wall_s": times.get(f"serve.{ph}", 0.0)}
                       for ph in SERVE_PHASES},
        }
        from conflux_tpu import resilience, tier

        cur["health"] = resilience.health_stats()
        t = tier.tier_stats()
        cur["tier"] = {k: v for k, v in t.items()
                       if k not in _TIER_GAUGES}
        cur["tier_gauges"] = {k: t[k] for k in _TIER_GAUGES if k in t}
        return cur, lats, flats

    def delta(self) -> dict:
        """The windowed telemetry since the last call; advances the
        window."""
        now = time.perf_counter()
        cur, lats, flats = self._snapshot()
        prev = self._prev
        if prev is None:
            prev = {"engine": {}, "bucket_hits": {},
                    "factor_bucket_hits": {},
                    "phases": {ph: {} for ph in SERVE_PHASES},
                    "health": {}, "tier": {}}
        dt = max(1e-9, now - self._t_prev)
        keys = (_ENGINE_COUNTERS if self._qos_class is None
                else _ENGINE_COUNTERS + _QOS_WINDOW_COUNTERS)
        eng = _diff(cur["engine"], prev["engine"], keys)
        eng["pending"] = cur["engine"]["pending"]
        # queue growth over the window: admissions minus resolutions.
        # Positive = the backlog is building (arrivals outpace drain)
        eng["backlog_delta"] = (eng["requests"] - eng["completed"]
                                - eng["failed"])
        eng["arrival_per_s"] = eng["requests"] / dt
        eng["drain_per_s"] = eng["completed"] / dt
        eng["coalesced_mean"] = (eng["coalesced_requests"] / eng["batches"]
                                 if eng["batches"] else 0.0)
        eng["factor_coalesced_mean"] = (
            eng["factor_coalesced_requests"] / eng["factor_batches"]
            if eng["factor_batches"] else 0.0)
        lats.sort()
        flats.sort()
        from conflux_tpu.engine import _percentile

        for xs, prefix in ((lats, "latency"), (flats, "factor_latency")):
            for pct in (50, 95, 99):
                eng[f"{prefix}_p{pct}_ms"] = 1e3 * _percentile(xs, pct)
        eng["latency_samples"] = len(lats)
        eng["factor_latency_samples"] = len(flats)
        out = {
            "seconds": dt,
            "engine": eng,
            "bucket_hits": _diff(cur["bucket_hits"],
                                 prev["bucket_hits"]),
            "factor_bucket_hits": _diff(cur["factor_bucket_hits"],
                                        prev["factor_bucket_hits"]),
            "phases": {ph: _diff(cur["phases"][ph],
                                 prev["phases"].get(ph, {}),
                                 ("count", "wall_s"))
                       for ph in SERVE_PHASES},
            "health": _diff(cur["health"], prev["health"]),
            "tier": _diff(cur["tier"], prev["tier"]),
            "tier_gauges": cur.get("tier_gauges", {}),
        }
        self._prev = cur
        self._t_prev = now
        return out


class CounterWindow:
    """Reset-aware rolling deltas over an arbitrary monotone-counter
    dict — the cross-process sibling of :class:`StatsWindow`.

    StatsWindow reads THIS process's profiler/engine globals; a serve
    fabric front (`conflux_tpu.fabric`, DESIGN §28) cannot — each
    engine host is its own process, and its counters arrive serialized
    in heartbeat payloads. The front keeps one CounterWindow per host
    and `feed()`s it each payload: numeric keys are differenced with
    the same reset-clamp `_diff` applies (a host that restarted or
    `clear()`ed reports its post-reset counts, never negative deltas),
    non-numeric keys pass through untouched, and the returned dict
    carries `seconds` (wall span of the window) so callers derive
    rates. Thread-safe: feed() is atomic under the window's lock (the
    heartbeat thread writes, stats readers may race it)."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self._prev: dict | None = None   # guarded-by: _lock
        self._t_prev = time.perf_counter()  # guarded-by: _lock

    def feed(self, counters: dict, t: float | None = None) -> dict:
        now = time.perf_counter() if t is None else t
        num = {k: v for k, v in counters.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
        with self._lock:
            prev = self._prev if self._prev is not None else {}
            dt = max(1e-9, now - self._t_prev)
            out = _diff(num, prev)
            out.update({k: v for k, v in counters.items() if k not in num})
            out["seconds"] = dt
            self._prev = num
            self._t_prev = now
        return out




# --------------------------------------------------------------------------- #
# Device-side per-phase timing (the reference's per-step semiprof table)
# --------------------------------------------------------------------------- #

# one component of an op_name path that names a phase: the LU loop
# (step0_reduce .. step8_perm), the Cholesky loop (reference vocabulary
# reduceA11/choleskyA00/updateA10/scatterA11/computeA11), the mesh solves
# (solve_perm/solve_fwd/solve_bwd) and the QR loop (qr_*)
PHASE_RE = (r"step\d+_[a-z0-9]+|(?:reduce|cholesky|update|compute|scatter)"
            r"A\d\d|solve_[a-z]+|qr_[a-z_]+")
# the scope a mesh program gives each of its collectives, nested inside
# the phase it serves (comm_panel, comm_election, comm_dots, ...)
COMM_RE = r"comm_[a-z0-9_]+"
UNATTRIBUTED = "(unattributed)"

_INSTR_RE = re.compile(r"\s+(?:ROOT )?%([\w.-]+) = ")
# "<result type> <opcode>(" — the trace prints each op's name in the same
# shape, so (token, result type, opcode) identifies an instruction across
# programs whose tokens collide
_HEAD_RE = re.compile(r"(.*?) ([a-z][\w-]*)\(")
_CALLS_RE = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                       r"false_computation)=%([\w.-]+)")
_CALL_SET_RE = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_TOKEN_RE = re.compile(r"%([\w.-]+)")


class _Instr(NamedTuple):
    comp: str          # the computation it belongs to
    head: tuple        # (token, result type, opcode)
    operands: list     # operand tokens
    called: list       # computations it calls
    op_name: str | None


def _operand_text(text: str, start: int) -> str:
    """The balanced parenthesised operand list opening at text[start]."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return text[start:i]
    return text[start:]


def _instructions(hlo_text: str) -> dict:
    """op token -> _Instr, in program text order, of a compiled program."""
    out: dict = {}
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"(?:ENTRY )?%([\w.-]+) ", line)
            comp = m.group(1) if m and line.rstrip().endswith("{") else None
            continue
        m = _INSTR_RE.match(line)
        if m is None or comp is None:
            continue
        tok, rest = m.group(1), line[m.end():]
        hm = _HEAD_RE.match(rest)
        ops = (_TOKEN_RE.findall(_operand_text(rest, hm.end() - 1))
               if hm else [])
        called = _CALLS_RE.findall(rest)
        for group in _CALL_SET_RE.findall(rest):
            called += _TOKEN_RE.findall(group)
        nm = _OP_NAME_RE.search(rest)
        out[tok] = _Instr(comp, (tok,) + (hm.groups() if hm else ("", "")),
                          ops, called, nm.group(1) if nm else None)
    return out


def _phase_of(op_name: str, phase: re.Pattern) -> str | None:
    """The innermost component of an op_name path that names a phase."""
    found = None
    for part in op_name.split("/"):
        if phase.fullmatch(part):
            found = part
    return found


def scope_map(hlo_text: str, phase_re: str = PHASE_RE) -> dict[str, str]:
    """Op token -> phase for a compiled program's text
    (`jitted.lower(...).compile().as_text()`).

    The rule, in order:
    1. an instruction whose `op_name` metadata names a phase (a path
       component matching `phase_re`, the innermost one) takes it;
    2. an instruction XLA generated takes the phase of its consumers
       within the same computation, transitively. XLA generated it when
       it has no `op_name` at all (copies, the per-row loop a scatter
       lowers to), when its `op_name` is a bare name and no scope path (a
       layout copy of a parameter takes the parameter's name), or when
       it is a clone (`.clone` in its token) whose inherited `op_name`
       names no phase;
    3. failing that, the phase of its operands within the same
       computation;
    4. failing that, the phase of the instruction that calls its
       computation (`calls=`, `body=`, `condition=`, `to_apply=`,
       `branch_computations=`), so a generated loop's body and condition
       take the phase of the loop.
    A step of rules 3 and 4 is followed by rule 2 again, and rule 4 only
    runs where rule 3 finds nothing. Any other instruction whose `op_name`
    names no phase is user code outside every scope: it stays out of the
    map, and the table shows it as unattributed.
    """
    phase = re.compile(phase_re)
    ins = _instructions(hlo_text)
    out: dict[str, str] = {}
    for tok, it in ins.items():
        if it.op_name:
            p = _phase_of(it.op_name, phase)
            if p:
                out[tok] = p
    operands: dict[str, list] = {}
    callers: dict[str, list] = defaultdict(list)
    for tok, it in ins.items():
        operands[tok] = [o for o in it.operands
                         if o in ins and ins[o].comp == it.comp]
        for c in it.called:
            callers[c].append(tok)

    def open_(tok):  # XLA generated, and not credited yet
        op_name = ins[tok].op_name
        return tok not in out and (op_name is None or "/" not in op_name
                                   or ".clone" in tok)

    def to_operands(front):  # rule 2, to a fixed point
        while front:
            nxt = []
            for tok in front:
                for o in operands[tok]:
                    if open_(o):
                        out[o] = out[tok]
                        nxt.append(o)
            front = nxt

    to_operands(list(out))
    while True:
        new = {}
        for tok in ins:  # rule 3, one step
            if open_(tok):
                src = next((o for o in operands[tok] if o in out), None)
                if src is not None:
                    new[tok] = out[src]
        if not new:  # rule 4, one step
            for tok, it in ins.items():
                if open_(tok):
                    src = next((c for c in callers.get(it.comp, ())
                                if c in out), None)
                    if src is not None:
                        new[tok] = out[src]
        if not new:
            return out
        out.update(new)
        to_operands(list(new))


def _load_trace(path: str):
    """`jax.profiler.ProfileData` of the newest `*.xplane.pb` under a
    directory, or of one (optionally gzipped) file."""
    import glob
    import os

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no *.xplane.pb under {path}")
        path = files[-1]
    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _line_events(plane, name: str) -> list:
    """[(name, start ns, end ns)] of a plane's line, by start."""
    for ln in plane.lines:
        if ln.name == name:
            return sorted(((e.name, float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns))
                           for e in ln.events), key=lambda e: e[1])
    return []


def _self_times(evs: list) -> list:
    """Self time of each (start-sorted) event: its duration less that of
    the events directly inside it (a while op spans its body's ops)."""
    own = [e[2] - e[1] for e in evs]
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -own[i]))
    stack: list = []
    for i in order:
        while stack and evs[stack[-1]][2] <= evs[i][1]:
            stack.pop()
        if stack:
            own[stack[-1]] -= evs[i][2] - evs[i][1]
        stack.append(i)
    return own


def _idle_gaps(ops: list, t0: float, t1: float) -> list:
    """[(start, end)] of the intervals inside [t0, t1] no op covers."""
    out, cur = [], t0
    for _, s, e in ops:
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return [g for g in out if g[1] > g[0]]


def _trace_head(name: str) -> tuple:
    m = re.match(r"%([\w.-]+) = ", name)
    if m is None:
        return (name, "", "")
    hm = _HEAD_RE.match(name[m.end():])
    return (m.group(1),) + (hm.groups() if hm else ("", ""))


def phase_table(trace_dir: str, hlo_text: str,
                phase_re: str = PHASE_RE) -> dict[str, tuple[float, int]]:
    """Per-phase device self time {phase: (ms, ops)} of one program in a
    trace, summed over its executions and chips.

    `trace_dir` is what :func:`trace` wrote (or one `.xplane.pb[.gz]`);
    `hlo_text` is `fn.lower(*args).compile().as_text()` of the program
    that ran. Only ops inside that program's own executions on the
    `XLA Modules` line count: an execution is the program's when its
    module name is the program's and nearly all of its first ops are
    among the program's instructions (token, result type, opcode), so
    programs that share a module name, as every shard_map program did
    before they were named, are told apart. Each op is credited its self
    time and its phase by :func:`scope_map`; ops it leaves out are
    '(unattributed)'. An op whose own `op_name` names a collective's scope
    (`COMM_RE`, e.g. `comm_panel`) is also counted in a row
    '<phase>/<scope>', printed under its phase: such rows break a phase
    down and are part of its row, not added to it. Prints the table, then
    the traced window's device busy share and its 5 longest idle gaps,
    each named by the innermost host span of a :func:`region` this
    process has timed that covers it. Raises ValueError for a trace with
    no device ops (a CPU run).
    """
    import bisect

    scope = scope_map(hlo_text, phase_re)
    ins = _instructions(hlo_text)
    heads = {it.head for it in ins.values()}
    comm_re = re.compile(COMM_RE)
    comm = {tok: c for tok, it in ins.items()
            if it.op_name and (c := _phase_of(it.op_name, comm_re))}
    m = re.search(r"^HloModule ([\w.-]+)", hlo_text, re.M)
    module = m.group(1) if m else ""
    pd = _load_trace(trace_dir)
    chips = [p for p in pd.planes if p.name.startswith("/device:")
             and not p.name.startswith("/device:CUSTOM")]
    chips = [p for p in chips if _line_events(p, "XLA Ops")]
    if not chips:
        raise ValueError("trace has no device op events (a CPU run has no "
                         "device plane; the table needs a TPU execution)")
    agg: dict = defaultdict(lambda: [0.0, 0])
    runs = 0
    for plane in chips:
        ops = _line_events(plane, "XLA Ops")
        own = _self_times(ops)
        starts = [e[1] for e in ops]
        for name, t0, t1 in _line_events(plane, "XLA Modules"):
            lo = bisect.bisect_left(starts, t0)
            hi = bisect.bisect_right(starts, t1)
            probe = [_trace_head(ops[i][0])
                     for i in range(lo, min(hi, lo + 64))]
            if (name.split("(")[0] != module or not probe
                    or 10 * sum(h in heads for h in probe) < 9 * len(probe)):
                continue
            runs += 1
            for i in range(lo, hi):
                if ops[i][2] <= t1:
                    tok = _trace_head(ops[i][0])[0]
                    ph = scope.get(tok, UNATTRIBUTED)
                    rows = [ph] + ([f"{ph}/{comm[tok]}"] if tok in comm
                                   else [])
                    for key in rows:
                        agg[key][0] += own[i] / 1e6
                        agg[key][1] += 1
    phases = {k: r for k, r in agg.items() if "/" not in k}
    total = sum(t for t, _ in phases.values()) or 1.0
    lines = [f"{module}: {runs} execution(s) on {len(chips)} chip(s)",
             f"{'PHASE':<24}{'OPS':>8}{'DEVICE ms':>14}{'%':>8}"]

    def by_time(rows):
        return sorted(rows, key=lambda kv: -kv[1][0])

    for ph, (t, n) in by_time(phases.items()):
        lines.append(f"{ph:<24}{n:>8}{t:>14.3f}{100 * t / total:>8.1f}")
        for key, (tc, nc) in by_time((k, r) for k, r in agg.items()
                                     if k.startswith(ph + "/")):
            lines.append(f"  {key[len(ph) + 1:]:<22}{nc:>8}{tc:>14.3f}"
                         f"{100 * tc / total:>8.1f}")
    lines += _window_lines(pd, _line_events(chips[0], "XLA Ops"))
    print("\n".join(lines))
    return {ph: (t, n) for ph, (t, n) in agg.items()}


def _window_lines(pd, ops: list) -> list:
    """The busy share of the traced window (the device's first op to its
    last, widened to the program's host spans) and its 5 longest idle
    gaps, each named by the innermost program span that covers it."""
    spans = set(_snapshot()[0])
    host = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events if e.name in spans]
    t0 = min([ops[0][1]] + [s for _, s, _ in host])
    t1 = max([max(e for _, _, e in ops)] + [e for _, _, e in host])
    gaps = _idle_gaps(ops, t0, t1)
    idle = sum(e - s for s, e in gaps)
    out = [f"device busy {100 * (1 - idle / (t1 - t0)):.3f}% of the traced "
           f"window ({(t1 - t0) / 1e6:.3f} ms); longest idle gaps:"]
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:5]:
        best, key = "(no program span)", None
        for name, hs, he in host:
            cover = min(e, he) - max(s, hs)
            if cover > 0 and (key is None or (cover, hs - he) > key):
                best, key = name, (cover, hs - he)
        out.append(f"  {(e - s) / 1e6:10.3f} ms  {best}")
    return out
