"""Async serve engine: request coalescing, double-buffered dispatch,
plan prewarming, admission control, and the serve-path resilience layer.

The plan/session layer (`conflux_tpu.serve`) makes a *single* session
fast — compile once per traffic shape, factor once per matrix,
substitution-only solves — but every entry point is synchronous and
per-request: a fleet of sessions under open-loop traffic still dispatches
one device program per request, leaves the device idle between host
round-trips, and pays a compile stall on the first request of every new
bucket. The same trade that drives the 2.5D algorithms (a little extra
buffering/replication for far fewer, larger device operations) applies at
the request level, and :class:`ServeEngine` makes it:

- **Coalescing** — requests arriving within a ``max_batch_delay`` window
  are grouped and merged along the axes the compiled programs already
  bucket. Requests against the SAME session concatenate their RHS columns
  into one wider substitution: columns are independent through every
  substitution/GEMM/IR step, so single-system answers are bitwise the
  per-request ones (the bucket-padding argument of `SolveSession.solve`,
  asserted in tests/test_engine.py); batched plans' vmapped GEMM kernel
  changes shape with the coalesced width, so their coalesced answers are
  allclose, bitwise only within a bucket. With ``stack_sessions=True``,
  requests against DIFFERENT sessions of one single-system plan
  additionally ride one vmapped dispatch off a device-resident GANG
  (`conflux_tpu.gang.SessionGang`, DESIGN §26): member sessions hold
  slots in a shared stacked factor pytree living on their lane device,
  so the stacked solve indexes resident state directly — zero
  per-dispatch restacking, zero per-dispatch factor movement. Drifted
  sessions ride a stacked rank-bucketed Woodbury correction and checked
  engines a fused per-slot verdict, so neither falls off the stacked
  path; answers are allclose to, but not bitwise, the per-session
  programs (bitwise within a stack bucket for plain sessions), so it is
  opt-in — and the AdaptiveController can steer it from live telemetry.

- **Double-buffered async dispatch** — a dispatcher thread stages and
  dispatches batch i+1 while a drain thread waits on batch i: the
  dispatched-batch queue is bounded at two entries, so host staging
  overlaps device compute without unbounded in-flight growth, and the hot
  path never calls ``block_until_ready`` (JAX async dispatch carries the
  results; only the drain thread blocks).

- **A factor lane (coalesced cold-start)** — session churn (millions of
  users means sessions open constantly) used to pay one narrow O(N^3)
  dispatch per matrix through the synchronous ``plan.factor``.
  :meth:`ServeEngine.submit_factor` enqueues factorizations instead: the
  dispatcher coalesces same-plan requests inside the same
  ``max_batch_delay`` window into ONE vmapped batched factor dispatch at
  power-of-two batch buckets (host-staged A stacking mirroring the RHS
  staging — one transfer, one prewarmed program; pad slots carry
  identity matrices), and the drain thread slices the stacked factor
  pytree device-side into independent resident
  :class:`~conflux_tpu.serve.SolveSession`s (``batched.unstack_tree``) —
  downstream solve/update/refresh/health behavior is exactly a
  ``plan.factor`` session's, and the answers are BITWISE identical
  (``plan.factor`` rides bucket 1 of the same program family, and the
  vmapped factor body is bucket- and pad-invariant). With a health
  policy, the staged A stack is finite-guarded (a poisoned matrix fails
  its OWN future) and every coalesced factorization carries a fused
  per-slot post-factor verdict (probe-row residual through a probe
  solve, computed in the same dispatch); sick slots re-dispatch solo
  and fail with structured evidence, healthy neighbours are untouched.

- **Prewarming + admission control** — :meth:`ServeEngine.prewarm`
  compiles the declared traffic buckets (widths, stack sizes) before
  traffic lands, so p99 never eats a compile (the persistent XLA cache is
  switched on, so even cold processes deserialize); a bounded pending
  count sheds (``on_full='reject'``, the default, raising
  :class:`EngineSaturated` with an exponential-backoff ``retry_after``
  hint) or backpressures (``on_full='block'``) instead of collapsing
  into unbounded latency.

- **Resilience** (`conflux_tpu.resilience`, DESIGN.md §20) — with
  ``health=HealthPolicy()``: every request's RHS is finite-guarded at
  ``submit()`` and again at staging, so a poisoned request fails its OWN
  future instead of corrupting the coalesced batch; every dispatched
  solve carries a fused finite/spot-residual verdict, and an unhealthy
  batch re-dispatches the innocent survivors individually while the sick
  request climbs the escalation ladder (forced refactor through the
  cached factor program, one iterative-refinement sweep, then a
  structured `SolveUnhealthy`); a session failing the whole ladder
  `quarantine_after` times in a row is quarantined by a circuit breaker
  (fast `SessionQuarantined`, half-open probe after the cooldown).
  Independent of the policy: per-request ``deadline=`` with lazy
  eviction (`DeadlineExceeded` frees the pending slot), a watchdog that
  fails pending work when a worker thread dies instead of queueing
  forever, and ``close(timeout)`` that reports wedged workers and fails
  still-pending futures. `fault_plan=` injects deterministic faults at
  the named sites (tests, `scripts/soak.py --serve`).

Sessions mutate under ``update``/refactor; the engine only ever calls
``session.solve``/``solve_checked`` (under the session's lock, so the
escalation ladder's factor swaps are atomic against the dispatcher). Do
not call ``session.update`` while requests against that session are in
flight — drain first (``engine.close()`` or wait on the futures).

    engine = ServeEngine(max_batch_delay=0.002, health=HealthPolicy())
    engine.prewarm(session, widths=(1, 2, 4))
    futs = [engine.submit(session, b) for b in rhs]     # non-blocking
    xs = [f.result() for f in futs]                     # coalesced device work
    print(engine.stats())                               # p50/p95/p99, batches
    engine.close()                                      # drains in flight
"""

from __future__ import annotations

import dataclasses
import threading
import time
import weakref
import zlib
from collections import deque
from concurrent.futures import Future
from queue import Empty, Full, Queue
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from conflux_tpu import profiler, resilience
from conflux_tpu import qos as qos_mod
from conflux_tpu.batched import _shard_batch, put_tree, stack_trees, \
    unstack_tree
from conflux_tpu.gang import SessionGang
from conflux_tpu.resilience import (
    DeadlineExceeded,
    HealthPolicy,
    MeshPlanUnsupported,
    RhsNonFinite,
    SessionQuarantined,
    SolveUnhealthy,
)
from conflux_tpu import serve
from conflux_tpu.serve import FactorPlan, SolveSession
from conflux_tpu.update import rank_bucket


def _devkey(device):
    """Hashable identity of a jax device (None = the default device).
    Keys the per-device program-warmth registry
    (`FactorPlan._warm_devices`) and the engine's device→lane map."""
    return None if device is None else (device.platform, device.id)


def rendezvous(sid, nodes, key=None):
    """Rendezvous (highest-random-weight) hashing: pick the node whose
    (sid, node-identity) hash is highest. `key(node)` supplies the
    stable identity each node is weighed by (default: the node itself);
    identities must be distinct and survive restarts for placement to.

    The property mod-N hashing lacks, and the reason the serve fabric
    (DESIGN §28) and the lane placer both use this: when the node SET
    changes, only the sids whose winning node vanished move — every
    other sid's per-node weights are untouched, so its argmax is
    untouched. Removing one of N nodes remaps ~1/N of the sids (the
    dead node's own) instead of the ~(N-1)/N a `hash % N` reshuffle
    moves; adding a node steals only the sids it now wins. Ties (a
    ~2^-32 CRC collision) break toward the lexically-largest identity
    so the choice stays a pure function of (sid, node set)."""
    sb = str(sid).encode()
    best = best_ident = None
    best_w = -1
    for n in nodes:
        ident = str(n if key is None else key(n))
        w = zlib.crc32(sb + b"@" + ident.encode())
        if w > best_w or (w == best_w and (best_ident is None
                                           or ident > best_ident)):
            best, best_ident, best_w = n, ident, w
    return best


def rendezvous_ranked(sid, nodes, k=None, key=None):
    """Rendezvous hashing, ranked: the full preference ORDER of `nodes`
    for `sid`, highest weight first (same weights and tie-break as
    :func:`rendezvous`, so `rendezvous_ranked(sid, ns)[0] ==
    rendezvous(sid, ns)`). `k` truncates to the top-k.

    The serve fabric's K-replica placement (DESIGN §34) is built on
    this: rank 0 is the primary, ranks 1..K-1 hold replica records, and
    the no-reshuffle property extends down the list — removing a node
    promotes each sid's next-ranked survivor without disturbing the
    relative order of any other pair, so fail-over re-points to the
    same standby every front would compute independently."""
    sb = str(sid).encode()
    ranked = sorted(
        nodes,
        key=lambda n: (
            zlib.crc32(sb + b"@" + str(n if key is None else key(n)).encode()),
            str(n if key is None else key(n)),
        ),
        reverse=True,
    )
    return ranked if k is None else ranked[:k]


def place_session(sid, devices):
    """Deterministic consistent placement: map a stable session id onto
    one of `devices` by rendezvous hashing over the device identities.
    Equal sids land on equal devices for any fixed device list — across
    engines, and across process restarts (the warm-restart path re-pins
    a restored fleet identically) — and a device-list CHANGE remaps
    only the sids whose device vanished (see :func:`rendezvous`; the
    pre-§28 CRC32 mod-N placer reshuffled ~(N-1)/N of the fleet when a
    lane died). The mesh-sharded serve fleet's placement function
    (DESIGN §25)."""
    if len(devices) == 1:
        return devices[0]
    return rendezvous(sid, devices, key=_devkey)


class EngineSaturated(RuntimeError):
    """submit() refused: the bounded pending set is full (shed policy).
    `retry_after` is an exponential-backoff hint in seconds — it doubles
    with every consecutive shed and resets on the next admission, so a
    retrying client herd spreads out instead of hammering the bound.
    `tenant`/`qos_class` carry the shed attribution when the request
    was QoS-classified (DESIGN §30; None on unclassified traffic), so
    a global-bound shed is auditable per class next to the fair-share
    `TenantThrottled` sheds."""

    def __init__(self, msg: str, retry_after: float = 0.0,
                 tenant: str | None = None,
                 qos_class: str | None = None):
        super().__init__(msg)
        self.retry_after = retry_after
        self.tenant = tenant
        self.qos_class = qos_class


class EngineClosed(RuntimeError):
    """submit() after close(), or pending work failed because the engine
    shut down (wedged close, dead worker thread)."""


@dataclasses.dataclass
class _Request:
    session: Any          # the SolveSession the answer comes from
    b2: Any               # HOST RHS normalized to a trailing width axis
    width: int            # pre-coalescing column count
    squeeze: bool         # drop the width axis in the result
    future: Future        # resolved by the drain thread
    t_submit: float       # perf_counter at admission (latency clock)
    expiry: float | None = None  # perf_counter deadline (lazy eviction)
    carried: bool = False  # deferred once already — never defer again
    lane: Any = None      # the DeviceLane that owns this request
    lane_slot: bool = False  # counted against the lane's pending slice
    qos: Any = None       # QosClass (DESIGN §30) or None
    cost: float = 1.0     # ledger admission weight (qos.request_cost)
    precision: Any = None  # per-request served tier / 'auto' (DESIGN §33)

    __hash__ = object.__hash__


@dataclasses.dataclass
class _FactorRequest:
    """One cold-start request in the factor lane. Shares the generic
    request surface (`future`/`expiry`/`carried`/`t_submit`) with
    :class:`_Request` so pruning, deadline capping, carry-over and
    resolution ownership treat both lanes uniformly."""

    plan: Any             # the FactorPlan whose program factors A
    A: Any                # HOST matrix (numpy), plan-shaped, plan dtype
    policy: Any           # DriftPolicy for the opened session (or None)
    future: Future        # resolves to a device-resident SolveSession
    t_submit: float       # perf_counter at admission (latency clock)
    expiry: float | None = None  # perf_counter deadline (lazy eviction)
    carried: bool = False  # deferred once already — never defer again
    lane: Any = None      # owning lane (None while in the shared pool)
    lane_slot: bool = False  # counted against the lane's pending slice
    pool: bool = False    # admitted into the work-stealing factor pool
    sid: Any = None       # stable session id for the opened session
    device: Any = None    # explicit device pin for the opened session
    qos: Any = None       # QosClass (DESIGN §30) or None
    cost: float = 1.0     # ledger admission weight (qos.request_cost)
    precision: Any = None  # served tier the session opens at (DESIGN §33)

    __hash__ = object.__hash__


@dataclasses.dataclass
class _FactorBatch:
    """A dispatched coalesced factor batch in flight to the drain
    thread: the stacked factor pytree (and, when checked, the stacked
    probe rows + the (2, bucket) per-slot verdict) plus the staged
    device A stack the sessions slice their bases from."""

    plan: Any
    reqs: list            # live requests, packed into slots 0..n-1
    factors: Any          # stacked factor pytree, leading axis = bucket
    wA: Any               # stacked probe rows (checked) or None
    verdict: Any          # (2, bucket) device verdict (checked) or None
    A: Any                # the staged (bucket,)+shape device A stack
    solo: bool = False    # a solo re-dispatch: no second retry
    mesh: bool = False    # a mesh-lane factor: ONE request, no stacking
                          # (factors/A are the sharded batch itself)
    tier: Any = None      # served tier the batch factored at (§33)


@dataclasses.dataclass
class _StackBatch:
    """A dispatched CHECKED gang-stacked batch in flight to the drain
    thread: the stacked answer plus the fused (2, cap) per-slot verdict
    (`update.health_spot_check_slots`) and the slot -> session map the
    drain needs to attribute a sick slot without re-dispatching its
    gang-mates. Unchecked gang dispatches ride the plain drain tuple
    (their verdict is None, like any other batch)."""

    plan: Any
    spec: list            # (request, slot, column-offset) scatter plan
    x: Any                # (cap, N, wb) stacked device answer
    verdict: Any          # (2, cap) device verdict block
    sessions: dict        # slot -> session, live-request slots only


def _normalize_rhs(session, b):
    """Mirror `SolveSession._rhs` on the HOST: returns (b2, squeeze) with
    b2 a numpy array carrying an explicit trailing width axis. Staying in
    numpy keeps admission free of device work — the dispatcher memcpys
    requests into one bucket-width staging buffer per batch, so the
    device sees ONE transfer and ONE prewarmed program regardless of how
    many requests coalesced (a per-batch `concatenate` of varying widths
    would be a fresh XLA compile per width combination)."""
    plan = session.plan
    b = np.asarray(b)
    if plan.batched:
        want = (plan.B, plan.N)
        if b.ndim == 2:
            if b.shape != want:
                raise ValueError(f"rhs {b.shape}, session needs {want}")
            return b[:, :, None], True
        if b.ndim != 3 or b.shape[:2] != want:
            raise ValueError(
                f"rhs {b.shape}, session needs {want} (+ rhs axis)")
        return b, False
    rows = plan.M  # == N for square kinds; QR solves take an M-row rhs
    if b.ndim == 1:
        if b.shape[0] != rows:
            raise ValueError(f"rhs {b.shape}, session needs ({rows},)")
        return b[:, None], True
    if b.ndim != 2 or b.shape[0] != rows:
        raise ValueError(f"rhs {b.shape}, session needs ({rows}, k)")
    return b, False


_STOP = object()
# a lane nudge: "run a dispatch window, there may be pooled factor work"
# — carries no request itself (multi-lane cold-start load balancing)
_WAKE = object()


def _percentile(sorted_vals, pct: float) -> float:
    """Nearest-rank percentile of an ascending list (0.0 when empty)."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(pct / 100.0 * len(sorted_vals) + 0.5)) - 1))
    return sorted_vals[idx]


class DeviceLane:
    """One device's worth of the serve engine: a dispatcher/drain pair,
    their staging buffers and bucket carry-over, and per-lane telemetry
    — the unit the mesh-sharded fleet scales by (DESIGN §25).

    A :class:`ServeEngine` owns one lane per serving device, all behind
    ONE admission front: the engine keeps the bounded pending set,
    deadlines, health guards, knobs, and the resolution-ownership
    `_live` set; each lane owns its input queue, its 2-deep dispatched-
    batch handoff queue, its two worker threads, and its small-remainder
    carry-over. Requests route to the lane that owns their session
    (pinned at open — consistent hash of the session id over the
    engine's devices, `device=` overrides); cold-start factorizations
    load-balance through the engine's shared pool, which any lane with
    a free dispatch round drains (work-stealing). A single-lane engine
    (`lanes=1`, the default, or a one-device host) is EXACTLY the
    pre-fleet engine: `device=None`, no placement, no pool — the same
    code on the same default device, byte-identical behavior.

    Fault domain: a lane. A poisoned request, a crashed dispatch, or a
    dead worker thread fails only work routed to its lane; the per-lane
    watchdog respawns dead lane workers (`lane_revives` budget) while
    the other lanes keep serving. Shared engine state (counters,
    admission) is touched only under the ENGINE's admission lock —
    lane-local counters ride the same lock; `busy_*_s` gauges are
    single-writer by construction (each written only by its own worker
    thread) and read racily by design."""

    def __init__(self, eng: "ServeEngine", index: int, device):
        self.eng = eng
        self.index = index
        self.device = device  # jax.Device, or None = default device
        # per-lane coalescing window override (the adaptive controller's
        # per-lane knob; None = the engine-wide max_batch_delay)
        self.delay_override: float | None = None
        self._inq: Queue = Queue()
        # bounded at 2: the double buffer (see ServeEngine.__init__)
        self._outq: Queue = Queue(maxsize=2)
        # per-lane telemetry — written under the ENGINE lock next to the
        # engine-wide counters (cross-object, so annotated in prose):
        self.batches = 0
        self.coalesced = 0
        self.bucket_hits: dict = {}
        self.factor_batches = 0
        self.factor_coalesced = 0
        self.gang_batches = 0
        self.gang_coalesced = 0
        # per-lane pending slice (max_lane_pending): requests admitted
        # against this lane, and sheds its slice caused — one lane's
        # backlog must not starve admission fleet-wide
        self.pending = 0
        self.sheds = 0
        # the lane's device-resident gangs, one per plan (DESIGN §26);
        # mutation of the DICT is engine-lock guarded, the gangs
        # themselves carry their own RLock
        self._gangs: dict = {}
        # queue high-water: monotone max, racy update by design
        self.queue_hw = 0
        # single-writer busy gauges (dispatcher / drainer respectively)
        self.busy_dispatch_s = 0.0
        self.busy_drain_s = 0.0
        self.t_start = time.perf_counter()
        # per-lane fault-domain state: watchdog revival budget spent,
        # permanently-dead flag (admission routes around a dead lane),
        # (thread name, exc) post-mortem — write-once by the dying
        # worker, racy reads tolerate staleness by design
        self.revives = 0
        self.dead = False
        self._dead: tuple | None = None
        # serializes concurrent trips (dying thread + watchdog poll)
        self._trip_lock = threading.Lock()
        self._dispatcher: threading.Thread | None = None
        self._drainer: threading.Thread | None = None

    @property
    def delay(self) -> float:
        """This lane's coalescing window: its own override when the
        controller set one (`ServeEngine.set_knobs(lane=...)`), else
        the engine-wide `max_batch_delay`."""
        d = self.delay_override
        return self.eng.max_batch_delay if d is None else d

    # hot-path
    def _collect_delay(self, r) -> float:
        """The request's collect delay inside this lane's window:
        exactly `self.delay` for unclassified requests (the qos=None
        path resolves in one attribute check), else the class's tier
        delay (DESIGN §30 — latency rides ~0, batch pads the window
        out). Priority-aware coalescing happens HERE, inside the one
        existing window, not in per-class queues: the window's
        effective deadline is the MIN over its members' class delays."""
        if r.qos is None:
            return self.delay
        st = self.eng._qos
        # racy read of the tier-override dict (a knob, like max_batch_
        # delay itself): a concurrent set_knobs lands on the next window
        return qos_mod.collect_delay(
            r.qos, self.delay, st.tier_delay if st is not None else {})

    # hot-path
    def _carry_delay(self, reqs) -> float:
        """The window to give a carried batch: the MIN of its members'
        collect delays (== `self.delay` when none are classified)."""
        d = self.delay
        for r in reqs:
            if r.qos is not None:
                d = min(d, self._collect_delay(r))
        return d

    def _tname(self, role: str) -> str:
        """Worker thread name: the pre-fleet names on a single-lane
        engine (ops tooling and tests key on them), lane-suffixed on a
        fleet."""
        if len(self.eng._lanes) == 1:
            return f"serve-engine-{role}"
        return f"serve-engine-{role}-L{self.index}"

    def start(self) -> None:
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop,
            name=self._tname("dispatch"), daemon=True)
        self._drainer = threading.Thread(
            target=self._drain_loop,
            name=self._tname("drain"), daemon=True)
        self._dispatcher.start()
        self._drainer.start()

    def revive(self, exclude=None) -> None:
        """Respawn this lane's dead worker threads — the per-lane
        watchdog's recovery action. The queues and carry state survive;
        requests the trip already failed are no longer in the engine's
        `_live` set, so a late re-dispatch of one resolves nothing
        (resolution ownership). `exclude` is the currently-dying thread
        (alive while it runs its own post-mortem, but done the moment
        it returns — replace it too)."""
        self._dead = None
        if self._dispatcher is None or not self._dispatcher.is_alive() \
                or self._dispatcher is exclude:
            self._dispatcher = threading.Thread(
                target=self._dispatch_loop,
                name=self._tname("dispatch"), daemon=True)
            self._dispatcher.start()
        if self._drainer is None or not self._drainer.is_alive() \
                or self._drainer is exclude:
            self._drainer = threading.Thread(
                target=self._drain_loop,
                name=self._tname("drain"), daemon=True)
            self._drainer.start()
        self.revives += 1

    def _to_device(self, host_buf):
        """Commit a host-staged buffer to this lane's device — the ONE
        h2d per coalesced batch. The default-device lane keeps the
        pre-fleet `jnp.asarray` byte-for-byte."""
        if self.device is None:
            return jnp.asarray(host_buf)
        return jax.device_put(host_buf, self.device)

    # ------------------------------------------------------------------ #
    # dispatcher: collect a window, coalesce, dispatch async
    # ------------------------------------------------------------------ #

    # futures-owner (post-mortem wrapper: escapes reach _thread_died)
    def _dispatch_loop(self) -> None:
        try:
            self._dispatch_inner()
        except BaseException as e:  # noqa: BLE001 — post-mortem + watchdog
            self._thread_died(threading.current_thread(), e)

    def _thread_died(self, thread, exc: BaseException) -> None:
        """Post-mortem hook run ON the dying worker thread: record the
        cause and trip the watchdog path immediately (the polling
        watchdog is the backstop for silent deaths). Single-lane
        engines trip the whole engine — exactly the pre-fleet
        behavior; multi-lane engines trip only this lane."""
        self.eng._lane_died(self, thread, exc)

    def _wait_bound(self, reqs, remaining: float) -> float:
        """Cap a collect wait at the soonest request deadline, so lazy
        eviction runs when a deadline passes mid-window instead of after
        the whole `max_batch_delay` (or a blocked slot's whole wait)."""
        exps = [r.expiry for r in reqs if r.expiry is not None]
        if not exps:
            return remaining
        return min(remaining,
                   max(0.0, min(exps) - time.perf_counter()) + 1e-4)

    def _prune_expired(self, reqs) -> list:
        """Lazy deadline eviction: fail expired requests with
        :class:`DeadlineExceeded` (releasing their pending slots — this
        is what un-wedges an `on_full='block'` submitter whose queue is
        full of abandoned work) and return the survivors."""
        now = time.perf_counter()
        live = []
        for r in reqs:
            if r.expiry is not None and now > r.expiry:
                resilience.bump("evictions")
                self.eng._fail([r], DeadlineExceeded(
                    f"deadline passed {now - r.expiry:.3f}s before "
                    "dispatch (lazily evicted; pending slot released)"))
            else:
                live.append(r)
        return live

    # hot-path, futures-owner (the dispatcher loop)
    def _dispatch_inner(self) -> None:
        eng = self.eng
        stop = False
        carry: list = []  # small remainder chunks deferred to this round
        while not stop:
            if carry:
                try:
                    first = self._inq.get(
                        timeout=self._wait_bound(
                            carry, self._carry_delay(carry)))
                except Empty:
                    first = None  # window spent waiting on the carry
            else:
                first = self._inq.get()
            batch = list(carry)
            carry = []
            collect = True
            if first is _STOP:
                stop = True
                collect = False
            elif first is None:
                collect = False
            elif first is not _WAKE:
                batch.append(first)
            if collect:
                # the window's effective deadline is the MIN over its
                # members' class collect delays (== self.delay when
                # nothing is classified — _carry_delay is one attribute
                # check per member on the qos=None path)
                deadline = time.perf_counter() + self._carry_delay(batch)
                while True:
                    batch = self._prune_expired(batch)
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        # the window is over, but anything ALREADY queued
                        # still coalesces (the burst shape: a backlog
                        # should never dispatch one request at a time)
                        try:
                            r = self._inq.get_nowait()
                        except Empty:
                            break
                    else:
                        try:
                            r = self._inq.get(
                                timeout=self._wait_bound(batch, remaining))
                        except Empty:
                            # the wait may have been truncated by a batch
                            # member's deadline — loop: prune, recompute,
                            # and let the remaining<=0 path end the window
                            continue
                    if r is _STOP:
                        stop = True
                        break
                    if r is _WAKE:
                        continue  # pooled work is drawn at dispatch time
                    batch.append(r)
                    if r.qos is not None:
                        # a latency-class arrival pulls the whole
                        # window in; batch-class arrivals never push an
                        # already-set deadline out
                        deadline = min(deadline, time.perf_counter()
                                       + self._collect_delay(r))
                    if len(batch) >= eng.max_pending:
                        break
            if batch:
                batch = self._prune_expired(batch)
            if batch or eng._pool_pending():
                try:
                    resilience.maybe_fault(eng._faults, "dispatch")
                    t0 = time.perf_counter()
                    carry = self._dispatch(
                        batch,
                        may_defer=not stop and not self._inq.empty())
                    self.busy_dispatch_s += time.perf_counter() - t0
                except Exception as e:  # noqa: BLE001 — engine survives
                    eng._fail(batch, e)
        # close-time drain: the carry AND any still-pooled cold starts
        # are answered, not dropped (every lane races to empty the pool;
        # each pooled request is popped exactly once)
        tail = self._prune_expired(carry) + eng._pool_draw(None)
        if tail:
            self._dispatch(tail, may_defer=False)
        self._outq.put(_STOP)

    # hot-path, futures-owner
    def _dispatch(self, batch, may_defer: bool = False) -> list:
        """Group a window's requests and dispatch each group as one
        device program (async — nothing here blocks on device work).
        With `may_defer` (more traffic already queued), each session's
        small remainder chunk is handed back once to ride the next
        window instead of wasting a whole dispatch on a sliver. Factor
        requests ride the same window: lane-pinned ones arrive in the
        batch, and on multi-lane engines each dispatch round also DRAWS
        from the engine's shared cold-start pool (up to one batch
        bucket per round, so a fast lane cannot vacuum the backlog
        while another idles — this is the work-stealing half of the
        factor lane's load balancing). They group per PLAN and coalesce
        into stacked factor dispatches."""
        eng = self.eng
        freqs = [r for r in batch if isinstance(r, _FactorRequest)]
        if len(eng._lanes) > 1:
            freqs += eng._pool_draw(eng.max_factor_batch)
        deferred: list = []
        if freqs:
            deferred += self._dispatch_factors(freqs, may_defer)
            batch = [r for r in batch if not isinstance(r, _FactorRequest)]
        groups: dict[tuple, list[_Request]] = {}
        order = []
        for r in batch:
            # a coalesced chunk shares ONE session.solve call, so the
            # group key carries the request's precision route (§33):
            # same-session requests at different tiers dispatch apart
            key = (id(r.session), r.precision)
            if key not in groups:
                groups[key] = []
                order.append((r.session, r.precision))
            groups[key].append(r)
        stackable: dict[int, list] = {}
        plan_order = []
        opportunity: dict[int, int] = {}
        for session, precision in order:
            reqs = groups[(id(session), precision)]
            plan = session.plan
            # racy read by design (like _revive_for): the served tier
            # is written once at construction
            tiered = (precision is not None
                      or session._served_tier is not None)
            if eng.stack_sessions and not plan.batched \
                    and plan.key.kind != "qr" and not tiered:
                # gang eligibility (DESIGN §26): single-system plans
                # only — a non-batched plan is never mesh-sharded, and
                # drifted (`_upd`) / checked sessions now STACK (the
                # stacked Woodbury + per-slot-verdict programs closed
                # the old exclusion holes). kind='qr' plans and
                # tier-routed requests are COUNTED exclusions (§33):
                # the gang stacks carry neither the (M, N) factor
                # shapes nor per-tier program families.
                pk = id(plan)
                if pk not in stackable:
                    stackable[pk] = []
                    plan_order.append(plan)
                stackable[pk].append((session, reqs))
                continue
            if eng.stack_sessions:
                eng._note_exclusion(
                    "kind" if plan.key.kind == "qr"
                    else "precision" if tiered
                    else "mesh" if plan.mesh is not None else "batched")
            elif not plan.batched:
                # stacking disabled: count the opportunity the window
                # left on the table (the controller's enable signal)
                opportunity[id(plan)] = opportunity.get(id(plan), 0) + 1
            deferred += self._dispatch_session(session, reqs,
                                               may_defer)
        missed = sum(c for c in opportunity.values() if c >= 2)
        if missed:
            with eng._lock:
                eng._gang_opportunity += missed
        for plan in plan_order:
            entries = stackable[id(plan)]
            if len(entries) == 1:
                eng._note_exclusion("singleton")
                deferred += self._dispatch_session(*entries[0], may_defer)
            else:
                self._dispatch_gang(plan, entries)
        if len(eng._lanes) > 1 and eng._pool_pending() \
                and not self.dead:
            # backlog left after this round's draw: keep draining it
            # through THIS lane (burst locality — each device's stream
            # executes serially, so consecutive buckets on one device
            # run back-to-back instead of N devices crunching O(N^3)
            # batches concurrently and thrashing a small core count;
            # measured 20% of churn throughput on the 1-core runner).
            # Lanes serving their own traffic still steal: every
            # dispatch round draws the pool.
            with eng._lock:
                eng._pool_waked = True
            self._inq.put(_WAKE)
        return deferred

    # hot-path
    def _dispatch_session(self, session, reqs,
                          may_defer: bool = False) -> list:
        """Per-session coalescing: concatenate RHS columns up to the
        width cap and run each chunk through `session.solve` (which
        already buckets, pads, shards, and counts). Returns the deferred
        remainder (at most one small chunk, each request deferred at most
        once — the latency cost is bounded by one extra window)."""
        eng = self.eng
        chunks: list[list[_Request]] = []
        chunk: list[_Request] = []
        width = 0
        for r in reqs:
            if chunk and width + r.width > eng.max_coalesce_width:
                chunks.append(chunk)
                chunk, width = [], 0
                with eng._lock:
                    # the width cap split a window's chunk: the
                    # controller's grow-the-bucket-set pressure signal
                    eng._width_capped += 1
            chunk.append(r)
            width += r.width
        deferred: list = []
        if chunk:
            if (may_defer and width <= eng.max_coalesce_width // 2
                    and not any(r.carried for r in chunk)):
                for r in chunk:
                    r.carried = True
                deferred = chunk
            else:
                chunks.append(chunk)
        for c in chunks:
            self._run_chunk(session, c)
        return deferred

    # hot-path
    def _admit_stage(self, reqs) -> list:
        """Pre-staging admission on the dispatch path: lazy deadline
        eviction and the 'staging' fault site (poisons the request's OWN
        host copy, upstream of the guard — exactly what a corrupted
        staging write looks like)."""
        eng = self.eng
        reqs = self._prune_expired(reqs)
        if eng._faults is not None or resilience.active_faults():
            for r in reqs:
                if resilience.data_fault(eng._faults, "staging",
                                         "nan") is not None:
                    # conflint: disable=CFX-HOSTSYNC fault-injection copy of host-staged numpy
                    poisoned = np.array(r.b2, copy=True)
                    poisoned[..., 0] = np.nan
                    r.b2 = poisoned
        return reqs

    # hot-path, futures-owner
    def _isolate_poisoned(self, reqs) -> list:
        """The SECOND finite guard (staging): one summation over the
        coalesced buffer answers 'is anything poisoned?' per BATCH; only
        on suspicion does the per-request scan run to fail the culprits
        alone. Requests poisoned after submit-time admission (or by an
        injected fault) therefore never reach the device, and the
        co-batched answers stay exactly what they would have been."""
        eng = self.eng
        live = []
        for r in reqs:
            if resilience.rhs_finite(r.b2):
                live.append(r)
                continue
            resilience.bump("staging_isolations")
            eng._restore_guards()
            eng._fail([r], RhsNonFinite(
                "rhs went non-finite after admission — isolated at "
                "staging (co-batched requests unaffected)"))
        return live

    # hot-path (numpy staging IS the point: one h2d per batch)
    def _stage(self, reqs):
        """Host-stage a session chunk: memcpy every request's columns
        into ONE bucket-width buffer (zero-padded — exactly the padding
        `SolveSession.solve` would add, so answers stay bitwise). A numpy
        buffer keeps staging off the device and, crucially, off the
        compiler: the device sees one transfer of one already-bucketed
        shape, never a fresh concatenate signature. Returns (buf, spec)
        with spec the (request, stack-slot, column-offset) scatter plan
        for the drain thread."""
        W = sum(r.width for r in reqs)
        wb = rank_bucket(W)
        lead = reqs[0].b2.shape[:-1]
        buf = np.zeros(lead + (wb,), reqs[0].b2.dtype)
        spec = []
        lo = 0
        for r in reqs:
            buf[..., lo:lo + r.width] = r.b2
            spec.append((r, None, lo))
            lo += r.width
        return buf, spec

    # hot-path
    def _revive_for(self, session, reqs) -> None:
        """Deadline-aware fault-in ahead of a dispatch to a spilled
        session (DESIGN §23): the revive-lane wait is capped at the
        requests' soonest deadline (else `revive_wait`), so a request
        expiring mid-revival fails with `DeadlineExceeded`/
        `SessionSpilled` through the usual survivor machinery — its
        admission slot released, the session left FULLY spilled with
        its record intact — instead of wedging the dispatcher. The
        resident fast path costs two attribute reads."""
        rs = getattr(session, "_residency", None)
        # racy fast-path read by design: fault_in re-checks under the
        # session lock, and a session cannot spill mid-dispatch (the
        # manager needs the session lock we are about to take)
        if rs is None or session._spill is None:
            return
        timeout = self.eng.revive_wait
        exps = [r.expiry for r in reqs if r.expiry is not None]
        if exps:
            timeout = max(0.0, min(exps) - time.perf_counter())
        rs.fault_in(session, timeout=timeout)

    # hot-path
    def _solve_session(self, session, buf, precision=None):
        """One dispatch through the session, checked when the policy
        says so. Holds the session lock so a drain-thread escalation
        (factor swap) is atomic against this dispatcher. 'auto'
        precision requests ALWAYS dispatch checked — the fused §20
        verdict is the ladder's escalation signal, with or without an
        engine HealthPolicy (§33)."""
        eng = self.eng
        with session._lock:
            if (precision == "auto"
                    or (eng.health is not None
                        and eng.health.check_output)):
                return session.solve_checked(buf, precision=precision)
            return session.solve(buf, precision=precision), None

    # hot-path, futures-owner
    def _run_chunk(self, session, reqs, solo: bool = False) -> None:
        eng = self.eng
        reqs = self._admit_stage(reqs)
        if not reqs:
            return
        try:
            buf, spec = self._stage(reqs)
            if (eng.health is not None and eng.health.check_rhs
                    and not eng.health.check_output
                    and eng._tick_staging()
                    and not resilience.rhs_finite(buf)):
                # no fused output verdict to backstop the staging guard:
                # one per-BATCH summation here; the per-request scan
                # runs only on suspicion. (With check_output on, the
                # device-side finite verdict detects staged poison for
                # FREE — NaN stays in its own answer column — and the
                # drain isolates the culprit with the same exact scan,
                # so the clean path stages without re-reading a byte.)
                reqs = self._isolate_poisoned(reqs)
                if not reqs:
                    return
                buf, spec = self._stage(reqs)
            self._revive_for(session, reqs)
            x, verdict = self._solve_session(session, buf,
                                             reqs[0].precision)
        except Exception as e:  # noqa: BLE001 — engine must survive
            self._redispatch_survivors(reqs, e, solo)
            return
        wb = buf.shape[-1]
        with eng._lock:
            eng._batches += 1
            eng._coalesced_requests += len(reqs)
            eng._bucket_hits[wb] = eng._bucket_hits.get(wb, 0) + 1
            eng._active_sessions[id(session)] = weakref.ref(session)
            self.batches += 1
            self.coalesced += len(reqs)
            self.bucket_hits[wb] = self.bucket_hits.get(wb, 0) + 1
        self._outq.put((spec, x, verdict, buf))

    # futures-owner
    def _redispatch_survivors(self, reqs, exc, solo: bool = False) -> None:
        """A batch-attributable failure (dispatch exception, failed d2h
        copy, unhealthy verdict on a multi-request batch) re-dispatches
        each member INDIVIDUALLY instead of failing all of them with the
        same exception: the innocent co-batched requests get their
        answers; only the actually-sick request fails (possibly after
        its own escalation ladder). One level deep — a solo request that
        fails again fails for real."""
        if solo or len(reqs) == 1:
            self.eng._fail(reqs, exc)
            return
        resilience.bump("survivor_redispatches", len(reqs))
        for r in reqs:
            self._run_chunk(r.session, [r], solo=True)

    # ------------------------------------------------------------------ #
    # the factor lane: coalesced cold-start dispatch
    # ------------------------------------------------------------------ #

    # hot-path
    def _dispatch_factors(self, reqs, may_defer: bool = False) -> list:
        """Per-plan coalescing of factor requests: same-plan requests
        stack into chunks of up to `max_factor_batch` matrices, each
        chunk one vmapped batched factor dispatch. Returns the deferred
        remainder (with `may_defer`, a small trailing chunk rides the
        next window once instead of wasting a whole bucket on a
        sliver — the solve lane's carry-over discipline)."""
        eng = self.eng
        # per-(plan, tier) coalescing: a served tier selects a distinct
        # compiled factor family, so mixed-tier requests cannot share a
        # stacked dispatch (§33)
        groups: dict[tuple, list] = {}
        order = []
        for r in reqs:
            key = (id(r.plan), r.precision)
            if key not in groups:
                groups[key] = []
                order.append((r.plan, key))
            groups[key].append(r)
        deferred: list = []
        for plan, key in order:
            greqs = groups[key]
            # mesh plans never slot-stack (the genuine gang/stacking
            # residue — their batch axis IS the parallelism): each
            # request dispatches as its own sharded (B, N, N) factor
            cap = 1 if plan.mesh is not None else eng.max_factor_batch
            chunks = [greqs[i:i + cap]
                      for i in range(0, len(greqs), cap)]
            last = chunks[-1]
            if (may_defer and len(last) <= cap // 2
                    and not any(r.carried for r in last)):
                for r in last:
                    r.carried = True
                deferred += last
                chunks = chunks[:-1]
            for c in chunks:
                self._run_factor_chunk(plan, c)
        return deferred

    # hot-path
    def _admit_stage_factor(self, reqs) -> list:
        """Pre-staging admission for the factor lane: lazy deadline
        eviction plus the 'factor' nan fault site (poisons the request's
        OWN host matrix, upstream of the staging guard — a corrupted
        staging write)."""
        eng = self.eng
        reqs = self._prune_expired(reqs)
        if eng._faults is not None or resilience.active_faults():
            for r in reqs:
                if resilience.data_fault(eng._faults, "factor",
                                         "nan") is not None:
                    # conflint: disable=CFX-HOSTSYNC fault-injection copy of host-staged numpy
                    poisoned = np.array(r.A, copy=True)
                    poisoned[..., 0, 0] = np.nan
                    r.A = poisoned
        return reqs

    # hot-path, futures-owner
    def _isolate_poisoned_A(self, reqs) -> list:
        """Factor-lane staging guard: a matrix gone non-finite after
        admission fails its OWN future and is dropped from the staged
        stack; co-batched factorizations are untouched (the vmapped
        factor body never mixes slots). One per-batch summation answers
        'anything poisoned?'; the per-request scan runs only on
        suspicion."""
        eng = self.eng
        live = []
        for r in reqs:
            if resilience.rhs_finite(r.A):
                live.append(r)
                continue
            resilience.bump("factor_isolations")
            eng._restore_guards()
            eng._fail([r], RhsNonFinite(
                "matrix went non-finite after admission — isolated at "
                "staging (co-batched factorizations unaffected)"))
        return live

    # hot-path (numpy staging: one h2d per factor batch)
    def _stage_factor(self, plan, reqs):
        """Host-stage a factor chunk: memcpy every request's matrix into
        ONE (bucket,)+shape staging buffer — the factor-lane mirror of
        `_stage`, with `_pad_batch`'s fill='eye' discipline in numpy:
        pad slots carry identity matrices (well-conditioned by
        construction, never a copy of a request that might itself be
        poisoned). The device sees one transfer and one prewarmed
        program per batch regardless of how many requests coalesced."""
        bb = rank_bucket(len(reqs))
        buf = np.empty((bb,) + plan.key.shape, np.dtype(plan.key.dtype))
        for i, r in enumerate(reqs):
            buf[i] = r.A
        if bb != len(reqs):
            # eye(M, N) for rectangular (QR) plans: full column rank by
            # construction, so pad slots stay factorable
            buf[len(reqs):] = np.eye(*plan.key.shape[-2:],
                                     dtype=buf.dtype)
        return buf

    # hot-path
    def _run_factor_chunk(self, plan, reqs, solo: bool = False) -> None:
        fb = self._build_factor_batch(plan, reqs, solo)
        if fb is not None:
            self._outq.put(fb)

    # hot-path, futures-owner
    def _build_factor_batch(self, plan, reqs, solo: bool = False):
        """Stage and dispatch one coalesced factor chunk (async —
        nothing blocks on device work here); returns the
        :class:`_FactorBatch` for the drain thread, or None when every
        request was already failed/evicted. A batch-attributable
        exception re-dispatches the members solo (`_redispatch_factor_
        survivors`), mirroring `_run_chunk`. The staged stack commits to
        THIS lane's device, so the factor program compiles and runs
        there and the opened sessions are lane-resident."""
        eng = self.eng
        reqs = self._admit_stage_factor(reqs)
        if not reqs:
            return None
        mesh = plan.mesh is not None

        def stage(rs):
            if mesh:
                # the mesh lane: ONE request IS the whole (B, N, N)
                # batch — no slot stacking (the batch axis is the
                # parallelism), so the 'stack' is the request's own
                # matrix batch, dispatched batch-sharded below
                # conflint: disable=CFX-HOSTSYNC A is the caller's host array (submit_factor stages host-side); no device value reaches here
                return np.asarray(rs[0].A)
            return self._stage_factor(plan, rs)

        try:
            buf = stage(reqs)
            if (eng.health is not None and eng.health.check_rhs
                    and eng._tick_staging()
                    and not resilience.rhs_finite(buf)):
                # exact per-batch guard (one summation of the staged
                # stack — noise next to the O(N^3) factor): poisoned
                # matrices fail alone BEFORE burning a factor dispatch,
                # and always as RhsNonFinite (exact attribution), even
                # when the fused verdict would also have caught them
                reqs = self._isolate_poisoned_A(reqs)
                if not reqs:
                    return None
                buf = stage(reqs)
            tier = reqs[0].precision
            checked = (tier is None and eng.health is not None
                       and eng.health.check_output)
            if mesh:
                (Ad,) = _shard_batch((jnp.asarray(buf),), plan.mesh)
            else:
                Ad = self._to_device(buf)
            with profiler.region("serve.factor"):
                if mesh and checked:
                    F, wA, verdict = plan._mesh_factor_health_fn()(Ad)
                elif mesh:
                    F = plan._factor_fn(Ad)
                    wA = verdict = None
                elif tier is not None:
                    # tier cold starts ride the unchecked tier factor
                    # family: the opened session's first checked solve
                    # carries the ladder's verdict (§33), so a fused
                    # post-factor probe here would be a second compile
                    # per tier for no added coverage
                    F = plan._tier_stacked_factor_fn(
                        tier, buf.shape[0])(Ad)
                    wA = verdict = None
                elif checked:
                    F, wA, verdict = plan._factor_health_fn(
                        buf.shape[0])(Ad)
                else:
                    F = plan._stacked_factor_fn(buf.shape[0])(Ad)
                    wA = verdict = None
        except Exception as e:  # noqa: BLE001 — engine must survive
            self._redispatch_factor_survivors(reqs, e, solo)
            return None
        bb = 1 if mesh else buf.shape[0]
        with eng._lock:
            eng._factor_batches += 1
            eng._factor_coalesced += len(reqs)
            eng._factor_slots += bb
            eng._factor_pad += bb - len(reqs)
            eng._factor_bucket_hits[bb] = \
                eng._factor_bucket_hits.get(bb, 0) + 1
            eng._active_plans[id(plan)] = weakref.ref(plan)
            self.factor_batches += 1
            self.factor_coalesced += len(reqs)
        return _FactorBatch(plan, reqs, F, wA, verdict, Ad, solo,
                            mesh=mesh, tier=tier)

    # futures-owner
    def _redispatch_factor_survivors(self, reqs, exc,
                                     solo: bool = False) -> None:
        """Batch-attributable factor-dispatch failure: re-dispatch each
        member individually (one level deep) so innocents still get
        their sessions; a solo retry that fails again fails for real."""
        if solo or len(reqs) == 1:
            self.eng._fail(reqs, exc)
            return
        resilience.bump("survivor_redispatches", len(reqs))
        for r in reqs:
            self._run_factor_chunk(r.plan, [r], solo=True)

    def _gang_for(self, plan) -> SessionGang:
        """This lane's device-resident gang for `plan`, created on
        first stacked contact (DESIGN §26). Dict mutation rides the
        engine lock; the gang carries its own RLock."""
        g = self._gangs.get(id(plan))
        if g is None:
            with self.eng._lock:
                g = self._gangs.get(id(plan))
                if g is None:
                    g = SessionGang(plan, self.device)
                    self._gangs[id(plan)] = g
        return g

    # hot-path
    def _dispatch_gang(self, plan, entries) -> None:
        """Cross-session coalescing through the plan's device-resident
        gang (DESIGN §26): per-session RHS concat first (width-capped;
        overflow falls back to per-session dispatch), then every
        request-carrying session dispatches from its resident gang slot
        in ONE vmapped program. Drifted sessions ride the stacked
        rank-bucketed Woodbury correction and checked engines the fused
        per-slot verdict, so neither excludes a session from stacking
        any more; what still falls back solo is counted per reason
        (`stack_exclusions`). All sessions here are pinned to THIS lane
        (requests route by session placement), so the gang's stacks
        share one device."""
        eng = self.eng
        ready = []
        for session, reqs in entries:
            reqs = self._admit_stage(reqs)
            chunk: list[_Request] = []
            width = 0
            rest: list[_Request] = []
            for r in reqs:
                if not rest and (not chunk or width + r.width
                                 <= eng.max_coalesce_width):
                    chunk.append(r)
                    width += r.width
                else:
                    rest.append(r)
            if chunk:
                ready.append((session, chunk, width))
            if rest:
                self._dispatch_session(session, rest)
        if len(ready) < 2:
            for session, chunk, _w in ready:
                eng._note_exclusion("singleton")
                self._run_chunk(session, chunk)
            return
        gang = self._gang_for(plan)
        checked = eng.health is not None and eng.health.check_output
        try:
            admitted, excluded = gang.ensure(
                [s for s, _c, _w in ready], eng.max_stack, checked)
        except Exception:  # noqa: BLE001 — adoption is best-effort
            admitted = {}
            excluded = {id(s): "error" for s, _c, _w in ready}
        part = []
        for session, chunk, w in ready:
            if id(session) in admitted:
                part.append((session, chunk, w))
            else:
                eng._note_exclusion(excluded.get(id(session), "error"))
                self._run_chunk(session, chunk)
        if len(part) == 1:
            eng._note_exclusion("singleton")
            self._run_chunk(part[0][0], part[0][1])
            return
        if part:
            self._run_gang(plan, gang, part, checked)

    # hot-path, futures-owner
    def _run_gang(self, plan, gang, part, checked: bool) -> None:
        """One dispatch for the whole gang window: stage the RHS into a
        (cap, N, wb) host buffer (one h2d — idle slots keep zero
        columns; the paper's trade, pay flops on idle slots to move no
        factor bytes) and solve straight off the RESIDENT stacks. Zero
        per-dispatch stack_trees, zero per-dispatch factor movement —
        the whole point of gang residency. The gang RLock is held
        across the dispatch (legal — the session-RLock precedent) so a
        concurrent adopt's donating slot write can never invalidate the
        snapshot mid-enqueue."""
        eng = self.eng
        reqs_all = [r for _s, chunk, _w in part for r in chunk]
        verdict = None
        poisoned = False
        try:
            wb = rank_bucket(max(w for _s, _c, w in part))
            with gang._lock:
                snap = gang.prepare([s for s, _c, _w in part])
                cap = snap["cap"]
                buf = np.zeros((cap, plan.N, wb),
                               part[0][1][0].b2.dtype)
                spec = []
                slot_sessions = {}
                for session, chunk, _w in part:
                    si = snap["slots"][id(session)]
                    slot_sessions[si] = session
                    lo = 0
                    for r in chunk:
                        buf[si, :, lo:lo + r.width] = r.b2
                        spec.append((r, si, lo))
                        lo += r.width
                if (eng.health is not None and eng.health.check_rhs
                        and not checked and eng._tick_staging()
                        and not resilience.rhs_finite(buf)):
                    # no fused verdict to backstop (check_output off):
                    # the per-batch staging guard runs here; culprits
                    # isolate per session chunk below, outside the lock
                    poisoned = True
                else:
                    if checked and snap["wA"] is None:
                        # a checked upgrade did not complete (snapshot
                        # failures mid-rebuild) — solo-dispatch this
                        # window; the next ensure() retries the upgrade
                        raise RuntimeError(
                            "gang probe stack unavailable for checked "
                            "dispatch")
                    with profiler.region("serve.solve"):
                        if snap["kb"]:
                            A0u = snap["A0"] if snap["sweeps"] else None
                            fn = (plan._stacked_update_solve_health_fn
                                  if checked
                                  else plan._stacked_update_solve_fn)(
                                cap, snap["kb"], wb, snap["sweeps"])
                            if checked:
                                X, verdict = fn(
                                    snap["F"], A0u, snap["Up"],
                                    snap["Vp"], snap["Y"],
                                    snap["Cinv"], snap["wA"], buf)
                            else:
                                X = fn(snap["F"], A0u, snap["Up"],
                                       snap["Vp"], snap["Y"],
                                       snap["Cinv"], buf)
                        elif checked:
                            X, verdict = plan._stacked_solve_health_fn(
                                cap, wb)(snap["F"],
                                         snap["A0"] if plan.key.refine
                                         else None, snap["wA"], buf)
                        else:
                            X = plan._stacked_solve_fn(cap, wb)(
                                snap["F"],
                                snap["A0"] if plan.key.refine else None,
                                buf)
        except Exception as e:  # noqa: BLE001
            self._redispatch_survivors(reqs_all, e)
            return
        if poisoned:
            for session, chunk, _w in part:
                live = self._isolate_poisoned(chunk)
                if live:
                    self._run_chunk(session, live)
            return
        for session, _c, _w in part:
            with session._lock:  # solves is guarded-by the session lock
                session.solves += 1
        with eng._lock:
            eng._batches += 1
            eng._coalesced_requests += len(reqs_all)
            eng._gang_batches += 1
            eng._gang_coalesced += len(reqs_all)
            eng._bucket_hits[wb] = eng._bucket_hits.get(wb, 0) + 1
            for session, _c, _w in part:
                eng._active_sessions[id(session)] = weakref.ref(session)
            self.batches += 1
            self.coalesced += len(reqs_all)
            self.gang_batches += 1
            self.gang_coalesced += len(reqs_all)
        if verdict is None:
            self._outq.put((spec, X, None, None))
        else:
            self._outq.put(_StackBatch(plan, spec, X, verdict,
                                       slot_sessions))

    # ------------------------------------------------------------------ #
    # drain: the only lane thread that blocks on device work
    # ------------------------------------------------------------------ #

    # futures-owner (post-mortem wrapper: escapes reach _thread_died)
    def _drain_loop(self) -> None:
        try:
            self._drain_inner()
        except BaseException as e:  # noqa: BLE001 — post-mortem + watchdog
            self._thread_died(threading.current_thread(), e)

    # futures-owner (the drain loop — the one thread that MAY block)
    def _drain_inner(self) -> None:
        eng = self.eng
        while True:
            item = self._outq.get()
            if item is _STOP:
                break
            t0 = time.perf_counter()
            try:
                if isinstance(item, _FactorBatch):
                    self._drain_factor(item)
                    continue
                if isinstance(item, _StackBatch):
                    self._drain_stack(item)
                    continue
                spec, block_on, verdict, buf = item
                reqs = [r for r, _si, _lo in spec]
                try:
                    resilience.maybe_fault(eng._faults, "drain")
                    resilience.maybe_fault(eng._faults, "d2h")
                    # ONE blocking device->host copy per coalesced
                    # batch; the per-request scatter is numpy views of
                    # it, so answering N requests costs zero extra
                    # device dispatches
                    xh = np.asarray(block_on)
                except Exception as e:  # noqa: BLE001
                    # batch-attributable drain failure routes through
                    # survivor re-dispatch, not batch-wide _fail
                    self._drain_redispatch(reqs, e)
                    continue
                if verdict is not None:
                    session = reqs[0].session
                    limit = eng._limit(session)
                    healthy, finite, res = resilience.evaluate(verdict,
                                                               limit)
                    if resilience.data_fault(eng._faults, "solve",
                                             "unhealthy") is not None:
                        healthy = False
                    if not healthy:
                        resilience.bump("output_failures")
                        eng._restore_guards()
                        self._drain_unhealthy(session, spec, buf,
                                              finite, res)
                        continue
                    if session._breaker is not None:
                        session._breaker.record_success()
                self.eng._settle(spec, xh)
            finally:
                self.busy_drain_s += time.perf_counter() - t0

    # futures-owner
    def _drain_stack(self, sb: _StackBatch) -> None:
        """Drain one CHECKED gang-stacked batch: ONE blocking d2h for
        the stacked answer, then per-slot verdict evaluation
        (`resilience.evaluate_slots` — slot verdicts are independent by
        construction). Healthy slots settle in place and their
        sessions' breakers record the success; each sick slot's
        requests re-dispatch SOLO through the escalation machinery
        (`_solo_drain`, the factor lane's solo-survivor shape), so a
        sick session never costs its gang-mates a re-dispatch."""
        eng = self.eng
        reqs = [r for r, _si, _lo in sb.spec]
        try:
            resilience.maybe_fault(eng._faults, "drain")
            resilience.maybe_fault(eng._faults, "d2h")
            xh = np.asarray(sb.x)
            limit = eng._plan_limit(sb.plan)
            verdicts = resilience.evaluate_slots(sb.verdict, limit)
            if resilience.data_fault(eng._faults, "solve",
                                     "unhealthy") is not None:
                verdicts = [(False, fin, res)
                            for _h, fin, res in verdicts]
        except Exception as e:  # noqa: BLE001
            self._drain_redispatch(reqs, e)
            return
        healthy_spec, sick = [], []
        for r, si, lo in sb.spec:
            if verdicts[si][0]:
                healthy_spec.append((r, si, lo))
            else:
                sick.append(r)
        for slot, session in sb.sessions.items():
            if verdicts[slot][0] and session._breaker is not None:
                session._breaker.record_success()
        if sick:
            nslots = len({si for _r, si, _lo in sb.spec
                          if not verdicts[si][0]})
            resilience.bump("output_failures", nslots)
            resilience.bump("gang_unhealthy_slots", nslots)
            eng._restore_guards()
            resilience.bump("survivor_redispatches", len(sick))
            for r in sick:
                self._solo_drain(r)
        if healthy_spec:
            eng._settle(healthy_spec, xh)

    # ------------------------------------------------------------------ #
    # the factor lane: drain, per-slot health, slice-out
    # ------------------------------------------------------------------ #

    # futures-owner
    def _drain_factor(self, fb: _FactorBatch) -> None:
        """Drain one coalesced factor batch: ONE block on the dispatched
        program (the factors never cross to the host — only the tiny
        verdict does, when checked), per-slot health evaluation, then
        device-side slice-out into independent resident sessions. Slot
        verdicts are independent, so — unlike the solve lane, which must
        re-dispatch to ATTRIBUTE a batch verdict — healthy neighbours of
        a sick slot settle in place; only the sick slot re-runs solo
        (distinguishing transient staged corruption from a genuinely
        unfactorable matrix) and fails alone with evidence."""
        eng = self.eng
        reqs = fb.reqs
        try:
            resilience.maybe_fault(eng._faults, "drain")
            verdicts = None
            if fb.verdict is not None:
                limit = eng._plan_limit(fb.plan)
                verdicts = resilience.evaluate_slots(fb.verdict, limit)
                if resilience.data_fault(eng._faults, "factor",
                                         "unhealthy") is not None:
                    verdicts = [(False, fin, res)
                                for _h, fin, res in verdicts]
            else:
                jax.block_until_ready(fb.factors)
        except Exception as e:  # noqa: BLE001
            self._drain_factor_redispatch(reqs, e)
            return
        entries = list(enumerate(reqs))
        if verdicts is not None:
            sick = [(i, r) for i, r in entries if not verdicts[i][0]]
            entries = [(i, r) for i, r in entries if verdicts[i][0]]
            for i, r in sick:
                resilience.bump("factor_unhealthy")
                eng._restore_guards()
                _h, finite, res = verdicts[i]
                if fb.solo:
                    limit = eng._plan_limit(fb.plan)
                    eng._fail([r], SolveUnhealthy(
                        f"coalesced factorization unhealthy after solo "
                        f"re-dispatch: finite={finite} res={res:.3e} "
                        f"(limit {limit:.3e})",
                        {"rungs": [{"rung": "factor", "finite": finite,
                                    "residual": res}],
                         "residual_limit": limit}))
                else:
                    self._solo_factor_drain(fb.plan, r)
        if entries:
            self._settle_factor(fb, entries)

    # futures-owner
    def _drain_factor_redispatch(self, reqs, exc) -> None:
        """Drain-side batch-attributable factor failure: re-run each
        request solo, inline (the rare path — the drain thread may
        block)."""
        if len(reqs) == 1:
            self.eng._fail(reqs, exc)
            return
        resilience.bump("survivor_redispatches", len(reqs))
        for r in reqs:
            self._solo_factor_drain(r.plan, r)

    # futures-owner
    def _solo_factor_drain(self, plan, r) -> None:
        """One factor request, re-dispatched and drained inline on the
        drain thread with its own per-slot verdict (solo, so a second
        failure is final)."""
        fb = self._build_factor_batch(plan, [r], solo=True)
        if fb is not None:
            self._drain_factor(fb)

    # futures-owner
    def _settle_factor(self, fb: _FactorBatch, entries) -> None:
        """Resolve a drained factor batch: slice each live slot's factor
        pytree, base matrix, and (when checked) probe row out of the
        stacked device arrays — `batched.unstack_tree`, lazy device
        indexing, zero host copies — and open one independent resident
        :class:`~conflux_tpu.serve.SolveSession` per request. The
        session is constructed exactly as `plan.factor` constructs it
        (same keep-A rule, same policy plumbing), so every downstream
        path — solve, update, drift refactor, the §20 health ladder —
        behaves identically. Sessions open PINNED to this lane's device
        (sid from the request, so re-submits route straight back
        here)."""
        eng = self.eng
        now = time.perf_counter()
        owned = eng._take([r for _i, r in entries])
        with eng._lock:
            for _i, r in entries:
                if r in owned:
                    eng._factor_latencies.append(now - r.t_submit)
            eng._flat_seq += len(owned)
            eng._completed += len(owned)
            st = eng._qos
            if st is not None:
                # classified cold starts settle against the same
                # per-class rings/ledger as solves (DESIGN §30)
                for r in owned:
                    if r.qos is not None:
                        st.record_settle(r.qos, now - r.t_submit,
                                         r.cost)
        plan = fb.plan
        if fb.mesh:
            # the mesh lane: the dispatched pytree IS the session state
            # (no slot axis to slice), and the session stays UNPINNED —
            # its state is batch-sharded across the plan's mesh, not
            # resident on this lane's device (DESIGN §32)
            trees = [fb.factors]
        else:
            trees = unstack_tree(fb.factors, len(fb.reqs))
        for i, r in entries:
            if r not in owned:
                continue
            A_i = fb.A if fb.mesh else fb.A[i]
            # tier-opened sessions keep A resident even without refine:
            # tier solves always consume A0 (the ladder refines against
            # the full-precision base, §33)
            keep_A = A_i if (plan.key.refine or fb.tier is not None) \
                else None
            session = SolveSession(plan, trees[i], keep_A,
                                   A_i, r.policy,
                                   device=None if fb.mesh
                                   else self.device, sid=r.sid,
                                   served_tier=fb.tier)
            if fb.wA is not None:
                # the probe row wA = w^T A0 came out of the checked
                # factor dispatch — the session opens with its half of
                # the Freivalds check already resident (a tuple of
                # stacks for QR plans: slice each part)
                session._probe = fb.wA if fb.mesh else (
                    tuple(p[i] for p in fb.wA)
                    if isinstance(fb.wA, tuple) else fb.wA[i])
            r.future.set_result(session)

    # futures-owner
    def _drain_redispatch(self, reqs, exc) -> None:
        """Survivor re-dispatch from the drain side: re-solve each
        request solo, synchronously (this is the rare failure path — the
        drain thread may block)."""
        if len(reqs) == 1:
            self.eng._fail(reqs, exc)
            return
        resilience.bump("survivor_redispatches", len(reqs))
        for r in reqs:
            self._solo_drain(r)

    # futures-owner
    def _solo_drain(self, r) -> None:
        """One request, re-dispatched and drained inline, with its own
        health verdict and (if needed) escalation ladder."""
        eng = self.eng
        session = r.session
        if not self._admit_stage([r]):
            return
        try:
            buf, spec = self._stage([r])
            if (eng.health is not None and eng.health.check_rhs
                    and not self._isolate_poisoned([r])):
                return
            self._revive_for(session, [r])
            x, verdict = self._solve_session(session, buf, r.precision)
            if verdict is not None:
                limit = eng._limit(session)
                healthy, finite, res = resilience.evaluate(verdict, limit)
                if resilience.data_fault(eng._faults, "solve",
                                         "unhealthy") is not None:
                    healthy = False
                if not healthy:
                    resilience.bump("output_failures")
                    eng._restore_guards()
                    self._escalate_settle(session, spec, buf, finite, res)
                    return
                if session._breaker is not None:
                    session._breaker.record_success()
            eng._settle(spec, np.asarray(x))
        except Exception as e:  # noqa: BLE001
            eng._fail([r], e)

    # futures-owner
    def _drain_unhealthy(self, session, spec, buf, finite, res) -> None:
        """An unhealthy verdict on a drained batch: multi-request
        batches isolate first (solo re-dispatch finds the sick request —
        a poisoned column fails alone, the survivors answer); a solo
        batch climbs the escalation ladder directly."""
        reqs = [r for r, _si, _lo in spec]
        if len(reqs) > 1:
            resilience.bump("survivor_redispatches", len(reqs))
            for r in reqs:
                self._solo_drain(r)
            return
        self._escalate_settle(session, spec, buf, finite, res)

    # futures-owner
    def _escalate_settle(self, session, spec, buf, finite, res) -> None:
        """Run the ladder for one request's staged buffer; settle on
        recovery, fail with the structured evidence (and count toward
        quarantine) otherwise. Tier-routed requests climb the precision
        ladder FIRST (`resilience.escalate_precision` — cheap higher-
        tier re-solves before any refactor), then fall through to the
        native rungs."""
        eng = self.eng
        reqs = [r for r, _si, _lo in spec]
        br = session._breaker
        evidence0 = {"rung": "dispatch", "finite": finite,
                     "residual": res}
        try:
            if reqs[0].precision is not None:
                xh = resilience.escalate_precision(
                    session, buf, reqs[0].precision, eng.health,
                    eng._limit(session), evidence0=evidence0,
                    faults=eng._faults)
            else:
                xh = resilience.escalate(
                    session, buf, eng.health, eng._limit(session),
                    evidence0=evidence0,
                    faults=eng._faults)
        except Exception as e:  # noqa: BLE001 — SolveUnhealthy et al.
            if br is not None:
                br.record_failure()
            eng._fail(reqs, e)
            return
        if br is not None:
            br.record_success()
        eng._settle(spec, xh)


class ServeEngine:
    """A thread-safe request queue in front of a fleet of SolveSessions.

    Knobs (the latency/throughput dial, DESIGN.md §19; resilience §20):

    max_batch_delay: how long the dispatcher holds the first request of a
        batch while more arrive to coalesce with it. 0 disables the wait
        (requests still coalesce when they are already queued — the burst
        shape); larger trades p50 latency for wider device dispatches.
    max_pending: admission bound on un-answered requests (queued plus in
        flight). `on_full` picks the policy at the bound: 'reject' (shed:
        submit raises :class:`EngineSaturated` with a backoff hint) or
        'block' (backpressure the submitter).
    max_coalesce_width: cap on coalesced RHS columns per dispatch — also
        the widest bucket `prewarm` needs to cover for a compile-free
        steady state.
    max_factor_batch: cap on coalesced factorizations per factor-lane
        dispatch (rounded up to a power of two — the batch buckets) and
        the widest `factor_batches` bucket `prewarm` needs to cover.
    stack_sessions / max_stack: opt-in gang-resident cross-session
        stacking for single-system plans (see module docstring;
        `max_stack` caps a gang's membership). Both are live knobs
        (`set_knobs`), which is how the adaptive controller steers
        them.
    max_lane_pending: optional per-lane slice of the pending bound on
        multi-lane engines — one lane's backlog sheds its own overflow
        (per-lane `sheds` counted in the lane stats rows) instead of
        filling `max_pending` and starving every other lane's
        admission. None (default) keeps the single global bound.
    latency_window: how many completed-request latencies the percentile
        window keeps.
    health: a :class:`~conflux_tpu.resilience.HealthPolicy` switches on
        the numerical guards (RHS finite checks, fused output verdicts,
        escalation, quarantine). None (default) keeps the dispatch path
        byte-identical to the unguarded engine — the checked programs
        are *different* compiled programs, so guarded answers are
        allclose, not bitwise, the unguarded ones.
    fault_plan: a :class:`~conflux_tpu.resilience.FaultPlan` consulted at
        the instrumented sites (staging, dispatch, drain, d2h, solve) —
        deterministic chaos for tests/soak; None costs one comparison.
    watchdog_interval: poll period of the worker-liveness watchdog
        (0 disables; a worker dying by exception still trips the same
        path directly).
    residency: a :class:`~conflux_tpu.tier.ResidentSet` managing the
        served fleet's tiers (DESIGN §23). The engine then (a) faults
        spilled sessions back in BEFORE dispatching to them —
        deadline-aware, so a request whose deadline expires mid-revival
        releases its admission slot and the session stays fully spilled
        — and (b) lends its coalesced factor lane to the manager's
        stale-drift revivals. `engine.stats()` gains the manager's tier
        gauges, and `checkpoint()`/`restore()` default to this fleet.
    revive_wait: worker-thread cap (seconds) on waiting for a revive
        admission slot when the faulting requests carry no deadline —
        bounds how long a saturated revive lane can stall the
        dispatcher before the requests fail with structured
        `SessionSpilled`.
    controller: a :class:`~conflux_tpu.control.AdaptiveController`
        (DESIGN §24) — opt-in closed-loop autotuning of the knobs above
        against a latency SLO, from windowed telemetry, on its own
        daemon thread. The controller writes exclusively through
        :meth:`set_knobs` (thread-safe, validated, never holding a lock
        across a dispatch) and only ever routes traffic onto
        already-prewarmed bucket programs. None (default) leaves every
        knob exactly as constructed — the default dispatch path is
        byte-identical to the controller-free engine.
    """

    def __init__(self, *, max_batch_delay: float = 0.002,
                 max_pending: int = 1024, on_full: str = "reject",
                 max_coalesce_width: int = 32,
                 max_factor_batch: int = 32,
                 stack_sessions: bool = False, max_stack: int = 8,
                 max_lane_pending: int | None = None,
                 latency_window: int = 8192,
                 persistent_cache: bool = True,
                 health: HealthPolicy | None = None,
                 fault_plan=None,
                 watchdog_interval: float = 0.2,
                 residency=None, revive_wait: float = 30.0,
                 controller=None,
                 lanes: int | str = 1, devices=None,
                 max_lane_revives: int = 8):
        if on_full not in ("reject", "block"):
            raise ValueError(f"unknown on_full {on_full!r} (reject|block)")
        if max_pending < 1 or max_coalesce_width < 1 or max_stack < 1 \
                or max_factor_batch < 1:
            raise ValueError("max_pending, max_coalesce_width, max_stack "
                             "and max_factor_batch must be >= 1")
        # ---- the lane fleet (DESIGN §25) -------------------------------
        # lanes=1 (default) or a one-device host: ONE lane on the default
        # device — the pre-fleet engine, byte-identical. lanes='auto':
        # one lane per jax device. devices=: an explicit device list.
        if devices is not None:
            devs = list(devices)
            if not devs:
                raise ValueError("devices must name at least one device")
        else:
            n = jax.device_count() if lanes == "auto" else int(lanes)
            if n < 1:
                raise ValueError("lanes must be >= 1 or 'auto'")
            if n == 1:
                devs = [None]
            else:
                avail = jax.devices()
                if n > len(avail):
                    raise ValueError(
                        f"lanes={n} exceeds jax.device_count()="
                        f"{len(avail)}")
                devs = list(avail[:n])
        if len(devs) == 1 and devices is None:
            devs = [None]  # single lane rides the default device
        if max_lane_revives < 0:
            raise ValueError("max_lane_revives must be >= 0")
        self.max_lane_revives = int(max_lane_revives)
        if persistent_cache:
            from conflux_tpu import cache

            cache.enable_persistent_cache()
        self.max_batch_delay = float(max_batch_delay)
        self.max_pending = int(max_pending)
        self.on_full = on_full
        self.max_coalesce_width = int(max_coalesce_width)
        self.max_factor_batch = rank_bucket(int(max_factor_batch))
        self.stack_sessions = bool(stack_sessions)
        self.max_stack = int(max_stack)
        # per-lane pending slice (DESIGN §25 follow-on): with a value
        # set, a multi-lane engine bounds each lane's share of the
        # pending set so one lane's backlog cannot starve admission
        # fleet-wide. None (default) keeps the single global bound —
        # byte-identical to the PR 9 engine.
        if max_lane_pending is not None and max_lane_pending < 1:
            raise ValueError("max_lane_pending must be >= 1 (or None)")
        self.max_lane_pending = (None if max_lane_pending is None
                                 else int(max_lane_pending))
        self.health = health
        self._faults = fault_plan
        self.watchdog_interval = float(watchdog_interval)
        self.residency = residency
        self.revive_wait = float(revive_wait)
        if residency is not None and residency.engine is None:
            # lend the factor lane to the tier manager's stale-drift
            # revivals (tier.ResidentSet._revive_refactor)
            residency.engine = self

        # per-device lanes: each owns its input queue, its 2-deep
        # double-buffer handoff queue (the dispatcher stages/dispatches
        # batch i+1 while the drain thread waits on batch i; a third
        # batch blocks the dispatcher instead of growing in-flight
        # device work), its dispatcher/drain threads, and its carry
        self._lanes: tuple = tuple(
            DeviceLane(self, i, d) for i, d in enumerate(devs))
        self._lane_by_dev: dict = {_devkey(ln.device): ln
                                   for ln in self._lanes}
        # the shared cold-start pool (multi-lane only): factor requests
        # with no explicit placement queue here and any lane with a free
        # dispatch round draws them — work-stealing load balance.
        # Guarded by _lock for mutation; emptiness fast-checks are racy
        # by design (a missed draw is picked up by the next wake).
        self._factor_pool: deque = deque()
        # one wake in flight at a time: a submission burst must not fan
        # WAKEs across every lane (each waked lane would draw a sliver
        # and the burst would factor in fragments instead of full
        # buckets) — the flag clears at the next pool draw, and the
        # drawing lane re-wakes if backlog remains
        self._pool_waked = False        # guarded-by: _lock
        self._sid_seq = 0               # guarded-by: _lock
        # the admission lock: every counter and the live set below are
        # `# guarded-by: _lock` (conflint CFX-LOCK enforces it). This
        # lock must NEVER be held across a device dispatch — the
        # lockcheck harness forbids it at runtime.
        self._lock = threading.Lock()
        self._not_full = threading.Condition(self._lock)
        self._closed = False            # guarded-by: _lock
        # checkpoint drain barrier: admission holds while True so the
        # snapshot observes a quiesced fleet (pending == 0)
        self._draining = False          # guarded-by: _lock
        # serializes whole checkpoint() calls: two overlapping drains
        # would share the single _draining flag, and whichever snapshot
        # finished first would reopen admission under the other —
        # silently voiding its pending==0 consistent cut
        self._ckpt_lock = threading.Lock()
        self._pending = 0               # guarded-by: _lock
        self._queue_peak = 0            # guarded-by: _lock
        self._requests = 0              # guarded-by: _lock
        self._completed = 0             # guarded-by: _lock
        self._failed = 0                # guarded-by: _lock
        self._sheds = 0                 # guarded-by: _lock
        self._consec_sheds = 0          # guarded-by: _lock
        self._batches = 0               # guarded-by: _lock
        self._coalesced_requests = 0    # guarded-by: _lock
        self._latencies: deque = deque(  # guarded-by: _lock
            maxlen=int(latency_window))
        # factor-lane (cold-start) counters: batches dispatched, requests
        # coalesced into them, total bucket slots vs pad slots (the
        # pad-waste ratio), and the session-open latency window
        self._factor_requests = 0       # guarded-by: _lock
        self._factor_batches = 0        # guarded-by: _lock
        self._factor_coalesced = 0      # guarded-by: _lock
        self._factor_slots = 0          # guarded-by: _lock
        self._factor_pad = 0            # guarded-by: _lock
        self._factor_latencies: deque = deque(  # guarded-by: _lock
            maxlen=int(latency_window))
        # window-delta telemetry for the adaptive controller (and any
        # profiler.StatsWindow): total samples ever appended to each
        # rolling latency window (sequence tokens for latency_window()),
        # per-bucket dispatch hit counters, and the count of chunks the
        # coalescing width cap split (the width-growth pressure signal)
        self._lat_seq = 0               # guarded-by: _lock
        self._flat_seq = 0              # guarded-by: _lock
        self._bucket_hits: dict = {}    # guarded-by: _lock
        self._factor_bucket_hits: dict = {}  # guarded-by: _lock
        self._width_capped = 0          # guarded-by: _lock
        # gang-stacked serving telemetry (DESIGN §26): stacked batches
        # dispatched and the requests they carried; per-reason counts
        # of sessions that fell back to a solo dispatch instead of
        # stacking (the exclusion trace); and, with stacking DISABLED,
        # the per-window count of same-plan sessions that would have
        # stacked — the controller's enable signal
        self._gang_batches = 0          # guarded-by: _lock
        self._gang_coalesced = 0        # guarded-by: _lock
        self._gang_opportunity = 0      # guarded-by: _lock
        # pre-seeded so the closed holes are PROVABLY closed: a bench
        # or ops read sees upd_pending/checked at literal zero, not
        # merely absent (they only move if a regression reopens them)
        self._stack_exclusions: dict = {  # guarded-by: _lock
            k: 0 for k in ("upd_pending", "checked", "mesh", "batched",
                           "singleton", "stack_cap", "error",
                           "kind", "precision")}
        # recently-served sessions/plans, weakly held — the adaptive
        # controller's prewarm targets (active_targets())
        self._active_sessions: dict = {}  # guarded-by: _lock
        self._active_plans: dict = {}     # guarded-by: _lock
        # measured drain rate (completions/s, EMA) installed by the
        # controller; sizes EngineSaturated.retry_after when present
        self._drain_rate: float | None = None  # guarded-by: _lock
        # guard-relaxation state: the controller may thin the staging
        # guard to 1-in-stride batches and swap in a relaxed policy
        # after a long clean streak; ANY trip restores both instantly
        # (engine-side, `_restore_guards` — never waiting for a
        # controller tick). Benign racy reads by design: both old and
        # new values are valid, a stale read only moves one batch's
        # sampling point.
        self._staging_stride = 1
        self._staging_tick = 0          # guarded-by: _lock
        self._health_strict = health
        # every admitted, unanswered request. Resolution OWNERSHIP: a
        # request's future is only ever resolved by the path that removed
        # it from this set under the lock (`_take`), so a wedged worker
        # finishing late after close()/watchdog failed its request can
        # never double-resolve the Future.
        self._live: set = set()         # guarded-by: _lock
        # (thread name, exc) post-mortem: write-once by the dying worker,
        # racy reads tolerate staleness by design — not lock-guarded
        self._dead: tuple | None = None
        # multi-tenant QoS state (DESIGN §30): stays None until the
        # first CLASSIFIED submission, so the qos=None engine carries no
        # new state and every hot-path branch is one attribute check
        self._qos = None                # guarded-by: _lock
        self._qos_latency_window = int(latency_window)

        profiler.register_engine(self)
        for lane in self._lanes:
            lane.start()
        self._watchdog = None
        if self.watchdog_interval > 0:
            self._watchdog = threading.Thread(
                target=self._watchdog_loop, name="serve-engine-watchdog",
                daemon=True)
            self._watchdog.start()
        # the adaptive controller (conflux_tpu.control) attaches LAST so
        # its first window observes a fully-constructed engine; close()
        # stops it first, and its loop exits on its own when a watchdog
        # trip closes the engine under it (the knobs simply freeze at
        # their last values — the controller is advisory, never
        # load-bearing)
        self._controller = None
        if controller is not None:
            controller.attach(self)
            controller.start()
            self._controller = controller

    # ------------------------------------------------------------------ #
    # client surface
    # ------------------------------------------------------------------ #

    # hot-path (admission: host work only, no device syncs)
    def submit(self, session, b, *, deadline: float | None = None,
               qos=None, precision=None) -> Future:
        """Enqueue one solve against `session`; returns a Future whose
        result is a HOST (numpy) array with the shape and values
        `session.solve(b)` would have returned. A served answer crosses
        the host boundary anyway, so the engine pays it once per
        coalesced batch (one contiguous device->host copy on the drain
        thread) instead of per request — the per-request scatter is then
        numpy views, zero extra device dispatches.

        `deadline` (seconds from now) bounds how long the request may
        wait: a request still queued past its deadline is lazily evicted
        — its pending slot is released and its future fails with
        :class:`DeadlineExceeded` (an abandoned `result(timeout)` alone
        would leak the slot). Raises :class:`EngineSaturated` at the
        pending bound under the 'reject' policy (with a `retry_after`
        backoff hint); blocks under 'block'. With a
        :class:`HealthPolicy`, a non-finite RHS raises
        :class:`RhsNonFinite` here and a quarantined session
        :class:`SessionQuarantined`.

        `qos=` classifies the request (DESIGN §30,
        :class:`conflux_tpu.qos.QosClass`): the tenant joins the
        weighted fair-share ledger — an over-share tenant on a
        contended engine is shed with a structured
        :class:`~conflux_tpu.resilience.TenantThrottled` — and the
        tier picks the request's collect delay inside the lane's
        coalescing window (latency ~0, throughput the engine window,
        batch a stretched window). `qos=None` (the default) keeps
        every path byte-identical to the unclassified engine.

        `precision=` routes THIS request through a served tier's
        program family (DESIGN §33): a tier name
        (`serve.PRECISION_TIERS`) dispatches that tier, 'auto' starts
        on the session's sticky rung and ALWAYS carries the fused §20
        verdict (the ladder's escalation signal — even on an unguarded
        engine), None keeps the session's own serving config (bitwise
        pre-§33 for native sessions). Tier requests are a counted gang
        exclusion, never an error; mesh-sharded plans refuse them at
        admission."""
        return self._admit(self._prepare(session, b, deadline, qos,
                                         precision))

    # hot-path (admission prelude: validation + request construction —
    # no locks, no device syncs)
    def _prepare(self, session, b, deadline=None, qos=None,
                 precision=None):
        """submit()'s lock-free prelude — fast-fail checks, RHS
        normalization/guarding, request construction, lane resolution.
        Shared with :meth:`submit_many` so a batched wire frame runs
        the identical validation per item."""
        # conflint: disable=CFX-LOCK benign racy fast-fail; _admit re-checks locked
        if self._closed:
            raise EngineClosed("submit() on a closed ServeEngine")
        if self._dead is not None:
            name, exc = self._dead
            raise EngineClosed(f"engine worker {name} died: {exc!r}")
        if self.health is not None:
            br = resilience.breaker_for(session, self.health)
            ok, retry = br.allow()
            if not ok:
                raise SessionQuarantined(
                    f"session quarantined after repeated escalation "
                    f"failures (breaker open; probe in ~{retry:.2f}s)",
                    retry_after=retry)
        b2, squeeze = _normalize_rhs(session, b)
        if (self.health is not None and self.health.check_rhs
                and not resilience.rhs_finite(
                    b2, sample=self.health.submit_guard_sample)):
            resilience.bump("rhs_rejects")
            self._restore_guards()
            raise RhsNonFinite(
                "rhs contains NaN/Inf — rejected at admission (a poisoned "
                "request would corrupt every co-batched answer)")
        if qos is not None and not isinstance(qos, qos_mod.QosClass):
            raise TypeError(f"qos must be a conflux_tpu.qos.QosClass "
                            f"(or None), got {type(qos).__name__}")
        precision = serve.check_precision_request(precision)
        if precision is not None and session.plan.mesh is not None:
            raise MeshPlanUnsupported(
                "mesh-sharded plans serve their native precision only — "
                "per-request precision= does not compose with the mesh "
                "lane (DESIGN §33)", surface="submit")
        now = time.perf_counter()
        req = _Request(session, b2, int(b2.shape[-1]), squeeze, Future(),
                       now, None if deadline is None else now + deadline,
                       qos=qos, precision=precision)
        if qos is not None:
            # byte/flop-aware ledger weight (DESIGN §32): a large-N
            # mesh solve occupies the slots it actually displaces
            req.cost = qos_mod.request_cost(session.plan.key.shape,
                                            width=req.width)
        # resolve the owning lane BEFORE admission (placement may move a
        # not-yet-pinned session's state — device work, so never under
        # the admission lock), so every live request is lane-attributed
        # for the per-lane watchdog
        req.lane = self._lane_for(session)
        return req

    # hot-path (batched admission: ONE lock round-trip per wire frame)
    def submit_many(self, items) -> list:
        """Batched :meth:`submit` for the zero-copy wire (DESIGN §31):
        `items` is ``[(session, b, qos)]``; returns len(items) futures,
        aligned. Items that can be admitted WITHOUT waiting are
        admitted under a single acquisition of the admission lock — a
        coalesced control frame pays one lock round-trip instead of
        one per request — and routed (queue pushes) outside it, like
        submit(). An item that would have to WAIT (the checkpoint
        drain barrier, or the 'block' policy at the global/per-lane
        pending bound) first flushes its already-admitted frame-mates
        to their lanes, then waits alone through the ordinary
        :meth:`_admit` path: an admitted-but-unrouted request can
        never complete, so a condition wait that needs ITS pending
        slot to free would deadlock the frame (and wedge the wire
        recv thread behind it).

        Per-item failures (validation, quarantine, saturation, tenant
        throttle) are set ON that item's future instead of raised, so
        one bad request never takes down its frame-mates; the wire
        encodes each future's exception back to its own caller."""
        reqs: deque = deque()
        futs: list = []
        for session, b, qos in items:
            try:
                req = self._prepare(session, b, qos=qos)
            except Exception as e:
                fut = Future()
                fut.set_exception(e)
                futs.append(fut)
            else:
                reqs.append(req)
                futs.append(req.future)
        while reqs:
            admitted = []
            with self._lock:
                while reqs:
                    req = reqs[0]
                    try:
                        if not self._admit_locked(req, wait=False):
                            break  # would wait: flush admitted first
                    except Exception as e:
                        reqs.popleft()
                        req.future.set_exception(e)
                        continue
                    reqs.popleft()
                    admitted.append(req)
            for req in admitted:
                self._route(req)
            if reqs:
                # the head of the remainder must wait; every admitted
                # frame-mate is routed by now (free to complete and
                # release its slot), so the plain blocking path holds
                # no frame state — then resume batching the tail
                req = reqs.popleft()
                try:
                    self._admit(req)
                except Exception as e:
                    req.future.set_exception(e)
        return futs

    def _admit(self, req) -> Future:
        """Shared admission control for both lanes: the bounded pending
        set (shed with a backoff hint, or block), registration in the
        `_live` resolution-ownership set, and the queue push."""
        with self._lock:
            self._admit_locked(req)
        self._route(req)
        return req.future

    # requires-lock: _lock
    def _admit_locked(self, req, wait: bool = True) -> bool:
        """The locked body of admission (also the per-item step of
        :meth:`submit_many`'s batch). May WAIT on `_not_full` (drain
        barrier / 'block' policy); with ``wait=False`` every
        would-wait site instead returns False with NOTHING committed,
        so a batched caller can route its already-admitted work before
        blocking — a wait taken while admitted-but-unrouted
        frame-mates hold pending slots could never be satisfied by
        them. Returns True when the request was admitted."""
        if self._closed:
            raise EngineClosed("submit() on a closed ServeEngine")
        while self._draining and not self._closed:
            if isinstance(req, _FactorRequest):
                # A factor submission must SHED at the drain
                # barrier, never wait: a client-thread stale-drift
                # revival (tier._revive_refactor) legitimately
                # holds its session RLock while submitting here,
                # and checkpoint()'s save_fleet needs that same
                # lock — and _draining only clears after save_fleet
                # returns, so waiting would close the cycle and
                # wedge the engine forever. EngineSaturated routes
                # the revival onto its direct plan._factor_once
                # fallback (same program family, same bits).
                raise EngineSaturated(
                    "factor lane paused at the checkpoint drain "
                    "barrier (snapshot serializing) — retry "
                    "shortly, or fall back to plan.factor",
                    retry_after=0.05)
            if not wait:
                return False
            # checkpoint drain barrier: hold admission (both
            # policies) until the snapshot completes — brief by
            # construction, the snapshot is host-side serialization
            self._not_full.wait()
        if self._closed:
            raise EngineClosed("engine closed while checkpointing")
        if self._pending >= self.max_pending:
            if self.on_full == "reject":
                self._sheds += 1
                self._consec_sheds += 1
                hint, why = self._shed_hint_locked()
                raise EngineSaturated(
                    f"{self._pending} pending requests >= max_pending="
                    f"{self.max_pending} (shed policy 'reject'; "
                    f"{why})", retry_after=hint,
                    **self._qos_shed_attr(req))
            if not wait:
                return False
            while self._pending >= self.max_pending \
                    and not self._closed:
                self._not_full.wait()
            if self._closed:
                raise EngineClosed("engine closed while blocked")
        lane = getattr(req, "lane", None)
        slice_cap = self.max_lane_pending
        take_slot = (slice_cap is not None and lane is not None
                     and len(self._lanes) > 1)
        if take_slot:
            # the per-lane pending slice: one hot lane's backlog
            # sheds ITS OWN overflow instead of filling the global
            # bound and starving every other lane's admission
            if lane.pending >= slice_cap:
                if self.on_full == "reject":
                    self._sheds += 1
                    self._consec_sheds += 1
                    lane.sheds += 1
                    hint, why = self._shed_hint_locked()
                    raise EngineSaturated(
                        f"lane {lane.index} holds {lane.pending} "
                        f"pending >= max_lane_pending={slice_cap} "
                        f"(per-lane slice; other lanes keep "
                        f"admitting — {why})", retry_after=hint,
                        **self._qos_shed_attr(req))
                if not wait:
                    return False
                while lane.pending >= slice_cap \
                        and not self._closed:
                    self._not_full.wait()
                if self._closed:
                    raise EngineClosed("engine closed while blocked")
        # weighted fair-share admission (DESIGN §30): runs LAST so
        # a throttle has committed nothing to roll back; the
        # qos=None path is one attribute check
        if req.qos is not None:
            self._qos_admit_locked(req)
        if take_slot:
            req.lane_slot = True
            lane.pending += 1
        self._consec_sheds = 0
        self._pending += 1
        self._requests += 1
        if isinstance(req, _FactorRequest):
            self._factor_requests += 1
        self._live.add(req)
        if self._pending > self._queue_peak:
            self._queue_peak = self._pending
        return True

    # requires-lock: _lock
    def _shed_hint_locked(self) -> tuple:
        """(retry_after, reason) for an EngineSaturated shed — sized
        from the measured drain rate when the controller installed one
        (the k-th consecutive shed backs off k drain intervals, so a
        retrying herd lands as slots actually free up), else the
        original exponential-backoff guess."""
        rate = self._drain_rate
        if rate is not None and rate > 0.0:
            hint = min(1.0, max(1e-4, self._consec_sheds / rate))
            why = (f"retry in ~{1e3 * hint:.0f}ms, sized "
                   f"from the measured drain rate "
                   f"{rate:.0f}/s")
        else:
            hint = min(1.0, 1e-3 * (1 << min(
                self._consec_sheds - 1, 10)))
            why = (f"retry in ~{1e3 * hint:.0f}ms, backoff "
                   "hint doubles per consecutive shed")
        return hint, why

    def _qos_shed_attr(self, req) -> dict:
        """Saturation-shed attribution (DESIGN §30): {} on the
        qos=None path (EngineSaturated raises exactly as before);
        tenant/qos_class kwargs — plus the lazy per-class health count
        `engine_saturated[tenant/tier]` — for a classified request, so
        a global-bound shed is auditable next to the fair-share
        TenantThrottled sheds."""
        if req.qos is None:
            return {}
        key = req.qos.key
        resilience.bump(f"engine_saturated[{key}]")
        return {"tenant": req.qos.tenant, "qos_class": key}

    # requires-lock: _lock
    def _qos_admit_locked(self, req) -> None:
        """Weighted fair-share admission for a CLASSIFIED request (the
        qos=None path never calls this). Lazily creates the engine's
        QoS state, interns the class (latest declaration of a
        tenant/tier key wins), and consults the ledger: a throttle
        raises :class:`~conflux_tpu.resilience.TenantThrottled` with a
        `retry_after` sized from the tenant's weighted fraction of the
        measured drain rate — by then roughly one of the tenant's OWN
        slots should have freed. Throttling is a policy outcome, so it
        applies under both on_full policies ('block' waits out global
        saturation but never a fair-share violation — blocking would
        let the over-quota tenant queue in front of everyone else)."""
        st = self._qos
        if st is None:
            st = self._qos = qos_mod.EngineQosState(
                self._qos_latency_window)
        cls = st.intern(req.qos)
        req.qos = cls
        over = st.ledger.try_admit(cls, self._pending, self.max_pending,
                                   req.cost)
        if over is None:
            st.record_admit(cls)
            return
        st.record_throttle(cls)
        rate = self._drain_rate
        frac = st.ledger.frac(cls.tenant)
        if rate is not None and rate * frac > 0.0:
            hint = min(1.0, max(1e-4, over / (rate * frac)))
            why = (f"retry in ~{1e3 * hint:.0f}ms, sized from the "
                   f"tenant's {100 * frac:.0f}% share of the measured "
                   f"drain rate {rate:.0f}/s")
        else:
            hint = min(1.0, 2e-3 * max(1.0, over))
            why = (f"retry in ~{1e3 * hint:.0f}ms, scaled by the "
                   "tenant's over-share backlog")
        raise resilience.TenantThrottled(
            f"tenant {cls.tenant!r} is at/over its fair share "
            f"({st.ledger.share(cls.tenant, self.max_pending):.0f} of "
            f"max_pending={self.max_pending}) while the engine is "
            f"contended ({self._pending} pending; {why})",
            retry_after=hint, tenant=cls.tenant, qos_class=cls.key)

    def _note_exclusion(self, reason: str) -> None:
        """Count one stacking exclusion: a session the gang path COULD
        have stacked fell back to a solo dispatch. Before PR 10 a
        disqualified session left no trace of why — these per-reason
        counters ('upd_pending', 'checked', 'mesh', 'batched',
        'singleton', 'stack_cap', 'error') are the trace, surfaced in
        `stats()`/`counters()` and merged into
        `profiler.serve_stats()['engine']`."""
        with self._lock:
            self._stack_exclusions[reason] = \
                self._stack_exclusions.get(reason, 0) + 1

    def _route(self, req) -> None:
        """Hand an admitted request to its lane's queue — or, for an
        unpinned cold-start on a multi-lane engine, to the shared
        work-stealing pool (waking the least-loaded lane). The dead-lane
        re-sweep closes the race between a lane dying and a request
        landing in its queue: either the trip's sweep of `_live` sees
        the request, or this sees `dead` — resolution ownership makes a
        double sweep harmless."""
        if isinstance(req, _FactorRequest) and req.pool:
            with self._lock:
                self._factor_pool.append(req)
            self._wake_lane()
            return
        lane = req.lane
        d = lane._inq.qsize() + 1
        if d > lane.queue_hw:  # monotone high-water; racy max by design
            lane.queue_hw = d
        lane._inq.put(req)
        if lane.dead:
            with self._lock:
                leftover = [r for r in self._live
                            if getattr(r, "lane", None) is lane]
            self._fail(leftover, EngineClosed(
                f"lane {lane.index} is dead (worker threads exhausted "
                f"their revival budget) — request failed by the "
                "admission front"))

    # hot-path (placement: at most one state move per session, ever)
    def _lane_for(self, session):
        """The lane that owns `session`, pinning it on first contact.

        Placement is deterministic (DESIGN §25): an explicit
        `session.device` wins; otherwise the consistent hash of the
        session id over the engine's devices (`place_session`) — a
        session with no sid gets one assigned (stable for its lifetime;
        give sessions stable sids for restart-deterministic placement).
        Mesh-sharded sessions are never pinned — their state spans the
        whole mesh — and ride the first live lane (the DESIGN §25
        placeholder made real: the lane contributes its dispatcher/
        drain threads, admission and coalescing; the mesh contributes
        the devices). Sessions on a device no lane
        serves (or a dead lane) are served by the first live lane:
        dispatch follows the committed factors, so answers are
        unaffected, only the thread that runs them."""
        lanes = self._lanes
        if len(lanes) == 1:
            return lanes[0]
        if session.plan.mesh is not None:
            for ln in lanes:
                if not ln.dead:
                    return ln
            return lanes[0]
        dev = session.device
        if dev is None:
            with session._lock:
                dev = session.device
                if dev is None:
                    if session.sid is None:
                        session.sid = self._auto_sid()
                    alive = [ln.device for ln in lanes if not ln.dead]
                    dev = place_session(
                        session.sid,
                        alive or [ln.device for ln in lanes])
                    session.to_device(dev)
        lane = self._lane_by_dev.get(_devkey(dev))
        if lane is None or lane.dead:
            for ln in lanes:
                if not ln.dead:
                    return ln
            return lanes[0]
        return lane

    def _auto_sid(self) -> str:
        with self._lock:
            self._sid_seq += 1
            return f"auto-{self._sid_seq}"

    @property
    def lanes(self) -> tuple:
        """The engine's :class:`DeviceLane`s, in device order (length 1
        on a single-lane engine)."""
        return self._lanes

    @property
    def devices(self) -> tuple:
        """The lane devices (a single None = the default device)."""
        return tuple(ln.device for ln in self._lanes)

    def placement(self, sid):
        """The device `place_session` pins `sid` to on THIS engine's
        device list — the ops-facing "where would this session land"
        query."""
        return place_session(sid, [ln.device for ln in self._lanes])

    def _pool_pending(self) -> bool:
        # racy emptiness fast-check by design (see __init__)
        return bool(self._factor_pool)

    def _pool_draw(self, n) -> list:
        """Pop up to `n` queued cold-start requests from the shared
        factor pool (None = all) — lane dispatchers call this every
        round, so any lane with a free round takes work. Drawing clears
        the wake-in-flight flag: the next submission burst gets a fresh
        wake."""
        if not self._factor_pool:
            return []
        out: list = []
        with self._lock:
            while self._factor_pool and (n is None or len(out) < n):
                out.append(self._factor_pool.popleft())
            self._pool_waked = False
        return out

    def _wake_lane(self, force: bool = False) -> None:
        """Nudge the least-loaded live lane (queue depth, ties to the
        lowest index) — the admission half of cold-start load
        balancing; the dispatch-round pool draw is the stealing half.
        At most one wake rides between draws (see `_pool_waked`) so a
        burst coalesces into full buckets; `force` bypasses that (lane
        death re-arms the pool)."""
        with self._lock:
            if self._pool_waked and not force:
                return
            self._pool_waked = True
        best = None
        best_load = None
        for ln in self._lanes:
            if ln.dead:
                continue
            load = ln._inq.qsize()
            if best is None or load < best_load:
                best, best_load = ln, load
        if best is not None:
            best._inq.put(_WAKE)

    # hot-path (admission: host work only, no device syncs)
    def submit_factor(self, plan, A, *, policy=None,
                      deadline: float | None = None,
                      sid=None, device=None, qos=None,
                      precision=None) -> Future:
        """Enqueue one factorization against `plan`; returns a Future
        whose result is a device-resident
        :class:`~conflux_tpu.serve.SolveSession` — exactly what
        ``plan.factor(A, policy=policy)`` would have opened, down to the
        bits (both ride the same stacked factor program family; see
        `FactorPlan._stacked_factor_fn`). Same-plan requests landing in
        one ``max_batch_delay`` window coalesce into ONE vmapped batched
        factor dispatch at a power-of-two batch bucket, so session churn
        pays the per-dispatch overhead once per batch instead of once
        per matrix.

        `A` is host-staged (numpy memcpy into the stacked buffer — one
        transfer per batch); pad slots carry identity matrices. Shares
        the solve lane's admission control (:class:`EngineSaturated` /
        'block', `deadline=` lazy eviction, close semantics). With a
        :class:`HealthPolicy`, a non-finite `A` raises
        :class:`RhsNonFinite` here (sampled guard; the staging guard
        re-checks exactly), and every coalesced factorization carries a
        fused per-slot post-factor finite/probe-residual verdict —
        a sick slot re-dispatches solo and fails alone with structured
        evidence (:class:`SolveUnhealthy`), its co-batched neighbours
        untouched. Mesh-sharded plans ride the MESH LANE (DESIGN §32):
        the request dispatches as its own batch-sharded (B, N, N)
        factor — no slot stacking, the batch axis is the parallelism —
        through the first live lane's dispatcher, with the same
        admission, deadline, staging-guard and per-batch health
        machinery; the opened session is unpinned (its state spans the
        mesh). Only `device=` naming a device outside the plan's mesh
        still raises :class:`~conflux_tpu.resilience.MeshPlanUnsupported`
        (sharded state cannot migrate off its mesh).

        On a multi-lane engine the cold start LOAD-BALANCES: with no
        `sid`/`device` the request joins the shared pool and whichever
        lane has a free dispatch round takes it (work-stealing);
        `sid=` pins the opened session by consistent hash
        (`place_session` — deterministic across restarts), `device=`
        pins it explicitly.

        `qos=` classifies the cold start exactly as on :meth:`submit`
        (DESIGN §30): the tenant's factor churn counts against the
        same fair-share ledger as its solves, so a bulk tenant cannot
        starve the latency class by flooding session opens instead."""
        # conflint: disable=CFX-LOCK benign racy fast-fail; _admit re-checks locked
        if self._closed:
            raise EngineClosed("submit_factor() on a closed ServeEngine")
        if self._dead is not None:
            name, exc = self._dead
            raise EngineClosed(f"engine worker {name} died: {exc!r}")
        if not isinstance(plan, FactorPlan):
            raise TypeError(f"submit_factor takes a FactorPlan, got "
                            f"{type(plan).__name__} (submit() serves "
                            "sessions)")
        if plan.mesh is not None and device is not None \
                and not any(device == d for d in plan.mesh.devices.flat):
            raise MeshPlanUnsupported(
                "device= names a device outside this plan's mesh — a "
                "mesh-sharded session's state cannot migrate off its "
                "mesh", surface="factor_lane")
        # conflint: disable=CFX-HOSTSYNC host request ingestion, not a device readback
        A2 = np.asarray(A)
        if tuple(A2.shape) != plan.key.shape:
            raise ValueError(f"A shape {A2.shape} does not match the "
                             f"plan's {plan.key.shape}")
        want = np.dtype(plan.key.dtype)
        if A2.dtype != want:
            A2 = A2.astype(want)  # mirror jnp.asarray's implicit cast
        if (self.health is not None and self.health.check_rhs
                and not resilience.rhs_finite(
                    A2, sample=self.health.submit_guard_sample)):
            resilience.bump("factor_rejects")
            self._restore_guards()
            raise RhsNonFinite(
                "matrix contains NaN/Inf — rejected at admission (a "
                "poisoned system would waste a coalesced factor dispatch)")
        if qos is not None and not isinstance(qos, qos_mod.QosClass):
            raise TypeError(f"qos must be a conflux_tpu.qos.QosClass "
                            f"(or None), got {type(qos).__name__}")
        precision = serve.check_precision_request(precision)
        if precision == "auto":
            # a cold start has no verdict history yet: "auto" opens on
            # the ladder's cheapest rung (§33) and the session's first
            # checked solve drives any escalation
            precision = serve.PRECISION_TIERS[0]
        if precision is not None and plan.mesh is not None:
            raise MeshPlanUnsupported(
                "precision= tiers are not served for mesh-sharded "
                "plans (the ladder's program families are per-device)",
                surface="factor_lane")
        now = time.perf_counter()
        req = _FactorRequest(plan, A2, policy, Future(), now,
                             None if deadline is None else now + deadline,
                             sid=sid, device=device, qos=qos,
                             precision=precision)
        if qos is not None:
            # byte/flop-aware ledger weight: the O(N^3) cold start
            # counts for the slots it displaces (qos.request_cost)
            req.cost = qos_mod.request_cost(plan.key.shape, factor=True)
        if plan.mesh is not None:
            # the mesh lane: the opened session stays UNPINNED (its
            # state spans the mesh — an in-mesh device= was a placement
            # no-op) and the request rides the first live lane's
            # dispatcher, like _lane_for routes mesh solves
            req.device = None
            for ln in self._lanes:
                if not ln.dead:
                    req.lane = ln
                    break
            else:
                req.lane = self._lanes[0]
            return self._admit(req)
        # lane resolution (multi-lane): an explicit device pins the lane,
        # a sid pins it by consistent hash, otherwise the request joins
        # the shared pool and the lanes load-balance it between them
        if len(self._lanes) == 1:
            req.lane = self._lanes[0]
        elif device is not None:
            lane = self._lane_by_dev.get(_devkey(device))
            if lane is None:
                raise ValueError(
                    f"device {device} is not one of this engine's lane "
                    "devices — open the session with plan.factor, or "
                    "build the engine with devices= including it")
            req.lane = lane
        elif sid is not None:
            req.lane = self._lane_by_dev[_devkey(self.placement(sid))]
            req.device = req.lane.device
        else:
            req.pool = True
        return self._admit(req)

    def factor(self, plan, A, timeout: float | None = None, *,
               policy=None, deadline: float | None = None,
               sid=None, device=None, qos=None):
        """Blocking convenience (the mirror of :meth:`solve`):
        ``submit_factor(plan, A).result(timeout)`` — returns the opened
        :class:`~conflux_tpu.serve.SolveSession`."""
        return self.submit_factor(plan, A, policy=policy,
                                  deadline=deadline, sid=sid,
                                  device=device, qos=qos).result(timeout)

    def solve(self, session, b, timeout: float | None = None,
              deadline: float | None = None, qos=None, precision=None):
        """Blocking convenience: ``submit(session, b).result(timeout)``."""
        return self.submit(session, b, deadline=deadline,
                           qos=qos, precision=precision).result(timeout)

    # futures-owner
    def close(self, timeout: float | None = None) -> list:
        """Stop admission, drain every in-flight request, join the
        workers. Queued requests are answered, not dropped; idempotent.
        Returns the names of wedged worker threads ([] normally): when a
        join times out, still-pending futures are failed with
        :class:`EngineClosed` naming the wedged thread instead of being
        left hanging forever."""
        with self._lock:
            already = self._closed
            self._closed = True
            self._not_full.notify_all()
        if self._controller is not None:
            # stop the knob writer before tearing down what it tunes
            self._controller.close()
        if not already:
            for lane in self._lanes:
                lane._inq.put(_STOP)
        wedged = []
        for lane in self._lanes:
            lane._dispatcher.join(timeout)
            lane._drainer.join(timeout)
            wedged += [t.name for t in (lane._dispatcher, lane._drainer)
                       if t.is_alive() and not lane.dead]
        if wedged:
            with self._lock:
                leftover = list(self._live)
            self._fail(leftover, EngineClosed(
                f"close(timeout={timeout}) gave up: worker thread(s) "
                f"{wedged} wedged; {len(leftover)} pending request(s) "
                "failed instead of hanging"))
        return wedged

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------ #
    # knob actuation (the adaptive controller's write surface, DESIGN §24)
    # ------------------------------------------------------------------ #

    def set_knobs(self, *, max_batch_delay: float | None = None,
                  max_pending: int | None = None,
                  max_coalesce_width: int | None = None,
                  max_factor_batch: int | None = None,
                  stack_sessions: bool | None = None,
                  max_stack: int | None = None,
                  max_lane_pending: int | None = None,
                  health: HealthPolicy | None = None,
                  staging_stride: int | None = None,
                  drain_rate: float | None = None,
                  qos_contention: float | None = None,
                  qos_tier_delay: dict | None = None,
                  lane: int | None = None) -> dict:
        """Thread-safe knob actuation: the write half of the adaptive
        control loop (`conflux_tpu.control.AdaptiveController`), also a
        public ops surface. Writes land under the admission lock; the
        hot paths read each knob once per decision point, so a move
        applies at the NEXT batch window / admission — never mid-batch,
        and never with a lock held across a dispatch. Validation mirrors
        the constructor (`max_factor_batch` rounds up to its power-of-two
        bucket); raising `max_pending` wakes blocked submitters.

        `health` swaps the active policy object (the first swap records
        the original as the strict restore point — see
        `_restore_guards`); `staging_stride` thins the exact staging
        guard to 1-in-stride batches (any guard trip resets it to 1
        instantly, engine-side). `drain_rate` installs the measured
        completions/s estimate that sizes `EngineSaturated.retry_after`
        (None leaves the current estimate in place). Returns the full
        knob dict after the move.

        `qos_contention` moves the fair-share ledger's contention
        fraction (the pending fraction of `max_pending` above which
        over-share tenants throttle, DESIGN §30); `qos_tier_delay`
        merges per-tier collect-delay overrides in seconds (keys from
        `conflux_tpu.qos.TIERS`; a None value clears that tier's
        override). Either knob lazily creates the engine's QoS state;
        neither appears in the knob dict of an engine that has none.

        `lane=` scopes the move to ONE lane: only `max_batch_delay` may
        ride it (the per-lane coalescing window the adaptive controller
        tunes independently per device, DESIGN §25) — the write lands as
        that lane's `delay_override`, leaving the engine-wide default
        and every other lane untouched."""
        if max_batch_delay is not None and max_batch_delay < 0:
            raise ValueError("max_batch_delay must be >= 0")
        if lane is not None:
            if not 0 <= int(lane) < len(self._lanes):
                raise ValueError(f"lane {lane} out of range "
                                 f"(engine has {len(self._lanes)})")
            if max_batch_delay is None or any(
                    v is not None for v in (max_pending,
                                            max_coalesce_width,
                                            max_factor_batch,
                                            stack_sessions, max_stack,
                                            max_lane_pending, health,
                                            staging_stride, drain_rate,
                                            qos_contention,
                                            qos_tier_delay)):
                raise ValueError("lane= scopes exactly one knob: "
                                 "max_batch_delay")
            with self._lock:
                self._lanes[int(lane)].delay_override = \
                    float(max_batch_delay)
                return self._knobs_locked()
        if (max_pending is not None and max_pending < 1) \
                or (max_coalesce_width is not None
                    and max_coalesce_width < 1) \
                or (max_factor_batch is not None and max_factor_batch < 1):
            raise ValueError("max_pending, max_coalesce_width and "
                             "max_factor_batch must be >= 1")
        if staging_stride is not None and staging_stride < 1:
            raise ValueError("staging_stride must be >= 1")
        if max_stack is not None and max_stack < 1:
            raise ValueError("max_stack must be >= 1")
        if max_lane_pending is not None and max_lane_pending < 1:
            raise ValueError("max_lane_pending must be >= 1")
        if qos_contention is not None \
                and not 0 < qos_contention <= 1:
            raise ValueError("qos_contention must be in (0, 1]")
        if qos_tier_delay is not None:
            for tier, v in qos_tier_delay.items():
                if tier not in qos_mod.TIERS:
                    raise ValueError(
                        f"qos_tier_delay key {tier!r} is not one of "
                        f"{qos_mod.TIERS}")
                if v is not None and v < 0:
                    raise ValueError("qos_tier_delay values must be "
                                     ">= 0 seconds (or None to clear)")
        with self._lock:
            if max_batch_delay is not None:
                self.max_batch_delay = float(max_batch_delay)
            if max_pending is not None:
                self.max_pending = int(max_pending)
                self._not_full.notify_all()  # blocked submitters re-check
            if max_coalesce_width is not None:
                self.max_coalesce_width = int(max_coalesce_width)
            if max_factor_batch is not None:
                self.max_factor_batch = rank_bucket(int(max_factor_batch))
            if stack_sessions is not None:
                # flipping stacking is always safe mid-flight: the
                # dispatcher reads the flag once per window, gangs keep
                # their resident state across an off/on cycle, and the
                # controller only flips ON after prewarming the stacked
                # bucket (`FactorPlan.bucket_ready(stack=...)`)
                self.stack_sessions = bool(stack_sessions)
            if max_stack is not None:
                self.max_stack = int(max_stack)
            if max_lane_pending is not None:
                self.max_lane_pending = int(max_lane_pending)
                self._not_full.notify_all()  # blocked submitters re-check
            if health is not None:
                if self._health_strict is None:
                    self._health_strict = self.health
                self.health = health
            if staging_stride is not None:
                self._staging_stride = int(staging_stride)
            if drain_rate is not None:
                self._drain_rate = float(drain_rate)
            if qos_contention is not None or qos_tier_delay is not None:
                st = self._qos
                if st is None:
                    st = self._qos = qos_mod.EngineQosState(
                        self._qos_latency_window)
                if qos_contention is not None:
                    st.ledger.contention = float(qos_contention)
                if qos_tier_delay is not None:
                    for tier, v in qos_tier_delay.items():
                        if v is None:
                            st.tier_delay.pop(tier, None)
                        else:
                            st.tier_delay[tier] = float(v)
            return self._knobs_locked()

    # requires-lock: _lock
    def _knobs_locked(self) -> dict:
        out = {"max_batch_delay": self.max_batch_delay,
                "max_pending": self.max_pending,
                "max_coalesce_width": self.max_coalesce_width,
                "max_factor_batch": self.max_factor_batch,
                "stack_sessions": self.stack_sessions,
                "max_stack": self.max_stack,
                "max_lane_pending": self.max_lane_pending,
                "staging_stride": self._staging_stride,
                "drain_rate": self._drain_rate,
                "health_relaxed": (self._health_strict is not None
                                   and self.health
                                   is not self._health_strict),
                "lanes": len(self._lanes),
                "lane_delays": {ln.index: ln.delay_override
                                for ln in self._lanes
                                if ln.delay_override is not None}}
        if self._qos is not None:
            # present only with live QoS state, so a qos=None engine's
            # knob dict is unchanged (knobs() equality in old tests)
            out["qos_contention"] = self._qos.ledger.contention
            out["qos_tier_delay"] = dict(self._qos.tier_delay)
        return out

    def knobs(self) -> dict:
        """The current knob values (a consistent snapshot)."""
        with self._lock:
            return self._knobs_locked()

    def _restore_guards(self) -> None:
        """Any guard trip restores full-strength guarding INSTANTLY,
        on the tripping thread: the controller only ever relaxes the
        sampling knobs on sustained-silence evidence, and the restore
        path cannot wait for its next tick (a poison burst would ride
        the relaxed window). Plain attribute stores — benign against
        concurrent readers, both old and new values are valid."""
        self._staging_stride = 1
        strict = self._health_strict
        if strict is not None and self.health is not strict:
            self.health = strict

    def _tick_staging(self) -> bool:
        """True when this batch should run the exact staging guard
        (1-in-stride sampling while the controller has the guard
        relaxed; stride 1 = every batch, the default)."""
        s = self._staging_stride
        if s <= 1:
            return True
        with self._lock:
            self._staging_tick += 1
            return self._staging_tick % s == 0

    def active_targets(self) -> tuple:
        """(sessions, plans) recently served by this engine, live refs
        only — the controller's prewarm targets when it grows a bucket
        set. Dead weakrefs are pruned as a side effect."""
        with self._lock:
            srefs = list(self._active_sessions.items())
            prefs = list(self._active_plans.items())
        sessions, plans, dead_s, dead_p = [], [], [], []
        for k, ref in srefs:
            obj = ref()
            (sessions.append(obj) if obj is not None
             else dead_s.append(k))
        for k, ref in prefs:
            obj = ref()
            (plans.append(obj) if obj is not None else dead_p.append(k))
        if dead_s or dead_p:
            with self._lock:
                for k in dead_s:
                    self._active_sessions.pop(k, None)
                for k in dead_p:
                    self._active_plans.pop(k, None)
        return sessions, plans

    # (not a futures-owner: readoption never touches request futures)
    def _gang_readopt(self, sessions) -> None:
        """Adopt revived sessions straight back into their lane gangs —
        the tier layer's grouped-revival hook (`tier.ResidentSet.
        revive_many`): by the time traffic touches a revived fleet its
        slots are already written, so a revival storm rejoins the
        stacked path without a first-window solo straggle. Advisory
        and best-effort — any failure leaves dispatch-time adoption to
        pick the sessions up; called WITHOUT any session lock held."""
        if not self.stack_sessions:
            return
        groups: dict = {}
        for s in sessions:
            if s.plan.batched or s.plan.mesh is not None:
                continue
            lane = self._lane_for(s)
            key = (id(s.plan), lane.index)
            if key not in groups:
                groups[key] = (lane, s.plan, [])
            groups[key][2].append(s)
        checked = self.health is not None and self.health.check_output
        for lane, plan, group in groups.values():
            try:
                lane._gang_for(plan).ensure(group, self.max_stack,
                                            checked)
            except Exception:  # noqa: BLE001 — adoption is advisory
                pass

    # ------------------------------------------------------------------ #
    # durable checkpoint / warm restart (DESIGN §23)
    # ------------------------------------------------------------------ #

    def checkpoint(self, path: str, sessions=None, names=None, *,
                   base=None, gen=None, full=True) -> dict:
        """Snapshot the served fleet to `path` at a drain barrier.

        Admission holds (both `on_full` policies block briefly) while
        the engine waits for `pending == 0`, so the snapshot observes
        no in-flight mutation — a consistent cut of every session's
        factors, base, Woodbury drift state, probe row and counters,
        across ALL tiers without moving anything (resident state d2hs,
        spilled records serialize in place; `conflux_tpu.tier.
        save_fleet`). `sessions` defaults to the attached residency's
        fleet. Restored sessions (`restore`) solve BITWISE identically
        to their pre-checkpoint selves. Returns {name: record dir}.

        `base`/`gen`/`full` pass through to `tier.save_fleet`'s
        incremental mode (DESIGN §35): against a previous generation
        dir, clean sessions (dirty clock unchanged) carry as
        references (``full=False``) or byte-identical local copies
        (``full=True`` compaction) instead of re-serializing."""
        if sessions is None and self.residency is None:
            raise ValueError(
                "checkpoint() needs sessions= when the engine has "
                "no residency-managed fleet")
        from conflux_tpu import tier

        # one checkpoint at a time: concurrent calls each queue behind
        # the mutex and take their own complete drain barrier, so
        # _draining never clears while another snapshot is serializing
        with self._ckpt_lock:
            with self._lock:
                self._draining = True
                while self._pending and not self._closed:
                    self._not_full.wait()
            try:
                if sessions is None:
                    # resolve the fleet AT the barrier, so sessions
                    # adopted while we queued behind an earlier
                    # checkpoint still make this snapshot
                    sessions = self.residency.sessions()
                return tier.save_fleet(path, sessions, names,
                                       base=base, gen=gen, full=full)
            finally:
                with self._lock:
                    self._draining = False
                    self._not_full.notify_all()

    def restore(self, path: str) -> list:
        """Rebuild a `checkpoint()` fleet: plans from their exact keys,
        sessions with their full state and counters. With a residency
        attached the sessions come back HOST-tier and fault in lazily
        as traffic touches them (the scalable warm restart — restore
        cost is file reads, capacity stays bounded); without one they
        restore eagerly device-resident. Returns the sessions in
        checkpoint order."""
        from conflux_tpu import tier

        return tier.load_fleet(path, residency=self.residency)

    # ------------------------------------------------------------------ #
    # prewarming
    # ------------------------------------------------------------------ #

    def prewarm(self, target, widths=(1,), stacks=(), factor_batches=(),
                update_ranks=(), precisions=(), wait: bool = True):
        """Compile the declared traffic's programs before it lands.

        `target` is a SolveSession (solve-lane warming) or a FactorPlan
        (factor-lane warming only — no session exists yet at cold
        start). `widths` are RHS widths (rounded up to power-of-two
        buckets — include the coalesced widths you expect;
        `max_coalesce_width` covers the worst case), `stacks` are
        cross-session stack sizes (single-system plans only), and
        `factor_batches` are coalesced cold-start batch sizes (rounded
        up likewise; `(1, 2, ..., max_factor_batch)` covers every bucket
        churn traffic can produce, INCLUDING the bucket-1 program that
        `plan.factor` itself rides). Warms the CHECKED programs instead
        when the engine's health policy checks outputs — whatever
        program steady-state traffic will actually ride observes zero
        compiles (asserted via `plan.trace_counts` in
        tests/test_engine.py::test_engine_prewarm_zero_compiles_in_steady_state
        and its checked twin in tests/test_resilience.py). `wait=False`
        compiles on a background thread (the engine-start pattern) and
        returns the Thread.

        `precisions` warms the §33 per-request tier program families
        next to the native ones: each named tier's solve programs (per
        width bucket, against a session target) and its coalesced
        factor programs (per factor bucket). `"auto"` warms the WHOLE
        ladder — an auto request may escalate to any rung, and every
        rung's checked program must be resident for the steady state to
        stay compile-free."""
        plan = target if isinstance(target, FactorPlan) else target.plan
        session = None if isinstance(target, FactorPlan) else target
        tiers: list = []
        auto = False
        for p in precisions:
            p2 = serve.check_precision_request(p)
            if p2 == "auto":
                auto = True
                tiers += [t for t in serve.PRECISION_TIERS
                          if t not in tiers]
            elif p2 is not None and p2 not in tiers:
                tiers.append(p2)

        def run():
            with profiler.region("engine.prewarm"):
                if session is not None:
                    with session._lock:  # a spilled target faults in
                        session._ensure_resident()
                    for wb in sorted({rank_bucket(w) for w in widths}):
                        self._prewarm_width(session, wb)
                        for t in tiers:
                            self._prewarm_tier_width(session, t, wb,
                                                     auto)
                        for s in stacks:
                            self._prewarm_stack(session, rank_bucket(s),
                                                wb, update_ranks)
                for fbk in sorted({rank_bucket(n) for n in factor_batches}):
                    self._prewarm_factor(plan, fbk)
                    for t in tiers:
                        self._prewarm_tier_factor(plan, t, fbk)

        if wait:
            run()
            return None
        t = threading.Thread(target=run, name="serve-engine-prewarm",
                             daemon=True)
        t.start()
        return t

    def _prewarm_width(self, session, wb: int) -> None:
        """Warm one RHS bucket on EVERY lane device. A jitted program
        traces once per shape but compiles one executable per device,
        so each lane must eat its own first-dispatch compile here —
        dedupe rides the plan's (kind, bucket, device) warm registry,
        so warming two sessions of one plan (or calling prewarm twice)
        repeats nothing."""
        plan = session.plan
        checked = self.health is not None and self.health.check_output
        kind = "solve_health" if checked else "solve"
        shape = ((plan.B, plan.N, wb) if plan.batched else (plan.M, wb))
        if plan.mesh is not None:
            # mesh lane: the sharded executable is keyed on the plan's
            # device SET, not one lane device (dispatch rides the first
            # live lane, see `_lane_for`) — one warm covers every lane,
            # and a per-lane `put_tree` would gather the sharded factors
            # onto a single device. devkey None = the mesh itself.
            if plan.device_warm(kind, wb, None):
                return
            b2 = jnp.zeros(shape, jnp.dtype(plan.key.dtype))
            (b2,) = _shard_batch((b2,), plan.mesh)
            with session._lock:
                session._ensure_resident()
                F, A, A0 = session._factors, session._A, session._A0
                probe = session._probe_row() if checked else None
            if checked:
                x, _ = plan._solve_health_fn(wb)(F, A0, probe, b2)
                x.block_until_ready()
            else:
                plan._solve_fn(wb)(F, A, b2).block_until_ready()
            plan.mark_device_warm(kind, wb, None)
            return
        for lane in self._lanes:
            dk = _devkey(lane.device)
            if plan.device_warm(kind, wb, dk):
                continue
            b2 = jnp.zeros(shape, jnp.dtype(plan.key.dtype))
            with session._lock:
                session._ensure_resident()
                F, A, A0 = session._factors, session._A, session._A0
                probe = session._probe_row() if checked else None
            if lane.device is not None:
                # temporary per-device copies: compile-time only, freed
                # with this loop iteration. The RHS stays UNCOMMITTED —
                # traffic dispatches host-staged RHS buffers the same
                # way, and the executable cache keys on the commitment
                # signature, so a committed prewarm RHS would warm a
                # program traffic never runs
                F = put_tree(F, lane.device)
                A = put_tree(A, lane.device)
                A0 = put_tree(A0, lane.device)
                probe = put_tree(probe, lane.device)
            if checked:
                x, _ = plan._solve_health_fn(wb)(F, A0, probe, b2)
                x.block_until_ready()
            else:
                plan._solve_fn(wb)(F, A, b2).block_until_ready()
            plan.mark_device_warm(kind, wb, dk)

    def _prewarm_stack(self, session, sb: int, wb: int,
                       update_ranks=()) -> None:
        """Warm the gang-stacked programs for one (stack, width)
        bucket on every lane device: the plain stacked solve (or the
        checked per-slot-verdict variant when this engine's policy
        checks outputs), plus — for each rank bucket in
        `update_ranks` — the stacked Woodbury programs a drifting gang
        will dispatch, fed zero drift state (the clean-slot shape, the
        exact signature a mixed clean/drifted gang uses). Also warms
        the gang's slot-write programs (`batched.write_slot_tree`), so
        adoption itself stays compile-free after prewarm."""
        plan = session.plan
        if plan.batched:
            raise ValueError(
                "stacks= prewarming applies to single-system plans only")
        checked = self.health is not None and self.health.check_output
        kind = "stacked_health" if checked else "stacked"
        for lane in self._lanes:
            dk = _devkey(lane.device)
            ranks = sorted({rank_bucket(k) for k in update_ranks
                            if not plan.device_warm(
                                "stacked_usolve",
                                (sb, rank_bucket(k), wb), dk)})
            if plan.device_warm(kind, (sb, wb), dk) and not ranks:
                continue
            with session._lock:
                session._ensure_resident()
                F0, A0, A0full = (session._factors, session._A,
                                  session._A0)
                probe = session._probe_row() if checked else None
            if lane.device is not None:
                F0 = put_tree(F0, lane.device)
                A0 = put_tree(A0, lane.device)
                A0full = put_tree(A0full, lane.device)
                probe = put_tree(probe, lane.device)
            F = stack_trees([F0] * sb)
            A = None if A0 is None else jnp.stack([A0] * sb)
            wA = None if probe is None else jnp.stack([probe] * sb)
            # the RHS stays uncommitted, matching traffic (see
            # _prewarm_width)
            b = jnp.zeros((sb, plan.N, wb), jnp.dtype(plan.key.dtype))
            if not plan.device_warm(kind, (sb, wb), dk):
                if checked:
                    x, _v = plan._stacked_solve_health_fn(sb, wb)(
                        F, A, wA, b)
                    x.block_until_ready()
                else:
                    plan._stacked_solve_fn(sb, wb)(
                        F, A, b).block_until_ready()
                # warm the gang's adopt/write-back row writes too (one
                # program per stacked leaf shape)
                from conflux_tpu.batched import write_slot_tree

                jax.block_until_ready(
                    write_slot_tree(stack_trees([F0] * sb), F0, 0))
                plan.mark_device_warm(kind, (sb, wb), dk)
            if not ranks:
                continue
            from conflux_tpu.update import zero_update_state

            sweeps = plan.key.refine + session.policy.refine
            A0s = jnp.stack([A0full] * sb) if sweeps else None
            for kb in ranks:
                z = zero_update_state(plan.N, kb, plan.key.dtype,
                                      plan.key.factor_dtype)
                Up = jnp.stack([z[0]] * sb)
                Vp = jnp.stack([z[1]] * sb)
                Y = jnp.stack([z[2]] * sb)
                Ci = jnp.stack([z[3]] * sb)
                if checked:
                    x, _v = plan._stacked_update_solve_health_fn(
                        sb, kb, wb, sweeps)(F, A0s, Up, Vp, Y, Ci,
                                            wA, b)
                    x.block_until_ready()
                else:
                    plan._stacked_update_solve_fn(
                        sb, kb, wb, sweeps)(
                        F, A0s, Up, Vp, Y, Ci, b).block_until_ready()
                plan.mark_device_warm("stacked_usolve", (sb, kb, wb),
                                      dk)

    def _prewarm_factor(self, plan, bb: int) -> None:
        checked = self.health is not None and self.health.check_output
        if plan.mesh is not None:
            # mesh lane: the (B, N, N) batch IS the dispatch (no slot
            # stacking, `_dispatch_factors` caps mesh chunks at 1), so
            # every requested bucket warms the same bucket-1 sharded
            # program — `_factor_fn` plain, `_mesh_factor_health_fn`
            # checked. One warm per mesh (devkey None), identity batch
            # filler as below.
            kind = "factor_health" if checked else "factor"
            if plan.device_warm(kind, 1, None):
                return
            buf = np.empty(plan.key.shape, np.dtype(plan.key.dtype))
            buf[:] = np.eye(*plan.key.shape[-2:], dtype=buf.dtype)
            (Ad,) = _shard_batch((jnp.asarray(buf),), plan.mesh)
            if checked:
                F, _wA, v = plan._mesh_factor_health_fn()(Ad)
                v.block_until_ready()
            else:
                F = plan._factor_fn(Ad)
            jax.block_until_ready(F)
            plan.mark_device_warm(kind, 1, None)
            return
        kind = "factor_health" if checked else "factor"
        # identity stacks: well-conditioned in every mode (LU, Cholesky,
        # trsm/blocked/inv substitution — an identity's diagonal-block
        # inverses are identities too) — the same filler the pad slots
        # use
        buf = np.empty((bb,) + plan.key.shape, np.dtype(plan.key.dtype))
        buf[:] = np.eye(*plan.key.shape[-2:], dtype=buf.dtype)
        for lane in self._lanes:
            dk = _devkey(lane.device)
            if plan.device_warm(kind, bb, dk):
                continue
            Ad = lane._to_device(buf)
            if checked:
                F, wA, v = plan._factor_health_fn(bb)(Ad)
                v.block_until_ready()
            else:
                F = plan._stacked_factor_fn(bb)(Ad)
                wA = None
            # warm the drain-side slice-out too: `_settle_factor` slices
            # each slot out of the stacked device arrays with eager
            # indexing, and each (slot, shape, device) slice is its own
            # tiny compiled program — cold ones would put first-batch
            # compile stalls on every NEW lane even with the factor
            # program warm
            slots = unstack_tree(F, bb)
            jax.block_until_ready(slots)
            jax.block_until_ready([Ad[i] for i in range(bb)])
            if wA is not None:
                jax.block_until_ready([wA[i] for i in range(bb)])
            plan.mark_device_warm(kind, bb, dk)

    def _prewarm_tier_width(self, session, tier: str, wb: int,
                            auto: bool = False) -> None:
        """Warm one served tier's solve program for one RHS bucket on
        every lane device (§33). 'auto' traffic always dispatches the
        CHECKED tier variant — the fused verdict is the ladder's
        escalation signal — so auto warming compiles `tier_health`
        even on an unguarded engine. Warming a cross-tier bucket also
        populates the session's derived `_tier_factors` cache (and the
        bucket-1 `tier_factor` program it rides)."""
        plan = session.plan
        if plan.mesh is not None:
            return  # tiers are validated away at submit for mesh plans
        checked = auto or (self.health is not None
                           and self.health.check_output)
        kind = "tier_health" if checked else "tier"
        shape = ((plan.B, plan.N, wb) if plan.batched
                 else (plan.M, wb))
        for lane in self._lanes:
            dk = _devkey(lane.device)
            if plan.device_warm(kind, (tier, wb), dk):
                continue
            b2 = jnp.zeros(shape, jnp.dtype(plan.key.dtype))
            with session._lock:
                session._ensure_resident()
                F = (session._factors
                     if tier == session._served_tier
                     else session._tier_factor(tier))
                A0 = session._A0
                probe = session._probe_row() if checked else None
            if lane.device is not None:
                F = put_tree(F, lane.device)
                A0 = put_tree(A0, lane.device)
                probe = put_tree(probe, lane.device)
            if checked:
                x, _ = plan._tier_solve_health_fn(tier, wb)(
                    F, A0, probe, b2)
                x.block_until_ready()
            else:
                plan._tier_solve_fn(tier, wb)(
                    F, A0, b2).block_until_ready()
            plan.mark_device_warm(kind, (tier, wb), dk)

    def _prewarm_tier_factor(self, plan, tier: str, bb: int) -> None:
        """Warm one served tier's coalesced factor bucket on every lane
        device — plus the drain-side slot slice-outs, mirroring the
        native `_prewarm_factor`. Tier factor batches dispatch
        UNCHECKED (§33: the opened session's first checked solve
        carries the ladder's verdict), so there is no health variant to
        warm here."""
        if plan.mesh is not None:
            return
        buf = np.empty((bb,) + plan.key.shape, np.dtype(plan.key.dtype))
        buf[:] = np.eye(*plan.key.shape[-2:], dtype=buf.dtype)
        for lane in self._lanes:
            dk = _devkey(lane.device)
            if plan.device_warm("tier_factor", (tier, bb), dk):
                continue
            Ad = lane._to_device(buf)
            F = plan._tier_stacked_factor_fn(tier, bb)(Ad)
            slots = unstack_tree(F, bb)
            jax.block_until_ready(slots)
            jax.block_until_ready([Ad[i] for i in range(bb)])
            plan.mark_device_warm("tier_factor", (tier, bb), dk)

    # ------------------------------------------------------------------ #
    # resolution ownership + failure bookkeeping
    # ------------------------------------------------------------------ #

    def _take(self, reqs) -> set:
        """Atomically claim resolution ownership: only requests still in
        `_live` are returned, and their pending slots are released. The
        claimer — and nobody else — resolves their futures."""
        with self._lock:
            owned = {r for r in reqs if r in self._live}
            self._live.difference_update(owned)
            self._pending -= len(owned)
            for r in owned:
                if r.lane_slot and r.lane is not None:
                    r.lane.pending -= 1
                    r.lane_slot = False
            self._not_full.notify_all()
        return owned

    def _fail(self, reqs, exc: Exception) -> None:
        owned = self._take(reqs)
        with self._lock:
            self._failed += len(owned)
            st = self._qos
            if st is not None:
                # per-class failure accounting + the ledger slot release
                # (the DRR refill) — classified requests only
                for r in owned:
                    if r.qos is not None:
                        st.record_fail(r.qos, r.cost)
        for r in owned:
            r.future.set_exception(exc)

    def _settle(self, spec, xh) -> None:
        """Resolve a drained batch: per-request scatter as numpy views
        of the one host copy (zero extra device dispatches)."""
        now = time.perf_counter()
        owned = self._take([r for r, _si, _lo in spec])
        with self._lock:
            for r in owned:
                self._latencies.append(now - r.t_submit)
            self._lat_seq += len(owned)
            self._completed += len(owned)
            st = self._qos
            if st is not None:
                # per-class latency rings + completion counts + the
                # ledger slot release (classified requests only; the
                # qos=None path pays one attribute read)
                for r in owned:
                    if r.qos is not None:
                        st.record_settle(r.qos, now - r.t_submit,
                                         r.cost)
        for r, si, lo in spec:
            if r not in owned:
                continue
            xs = (xh[..., lo:lo + r.width] if si is None
                  else xh[si, :, lo:lo + r.width])
            if r.squeeze:
                xs = xs[..., 0]
            r.future.set_result(xs)

    def _limit(self, session) -> float:
        return self._plan_limit(session.plan)

    def _plan_limit(self, plan) -> float:
        # 'auto' precision requests carry a verdict even on an
        # unguarded engine (the ladder's escalation signal, §33) — the
        # default HealthPolicy supplies the residual limit then
        policy = self.health if self.health is not None \
            else resilience.HealthPolicy()
        return policy.resolved_residual_limit(
            np.dtype(plan.key.dtype), plan.N)

    # ------------------------------------------------------------------ #
    # watchdog: a dead worker fails pending work instead of queueing
    # (multi-lane: a dead LANE fails only its own work, then revives)
    # ------------------------------------------------------------------ #

    def _is_worker_thread(self) -> bool:
        """True on any lane's dispatcher/drain thread — the tier
        manager's refactor-revival must not block on the factor lane
        from one (a worker waiting on its own queue would deadlock)."""
        t = threading.current_thread()
        for ln in self._lanes:
            if t is ln._dispatcher or t is ln._drainer:
                return True
        return False

    def _lane_died(self, lane, thread, exc: BaseException) -> None:
        """Post-mortem hook run ON a dying lane worker thread: record
        the cause and trip the watchdog path immediately (the polling
        watchdog is the backstop for silent deaths). A single-lane
        engine trips whole — exactly the pre-fleet behavior; a
        multi-lane engine trips ONLY the dead lane (its fault domain)
        and leaves the rest of the fleet serving."""
        lane._dead = (thread.name, exc)
        if len(self._lanes) == 1:
            self._dead = (thread.name, exc)
            self._watchdog_trip([thread.name], exc)
        else:
            self._lane_trip(lane, [thread.name], exc, dying=thread)

    # futures-owner
    def _lane_trip(self, lane, names, exc, dying=None) -> None:
        """Per-lane watchdog action (multi-lane engines): the blast
        radius of a dead lane worker is ITS lane. Fail the live
        requests routed to that lane (queued or in flight — resolution
        ownership makes failing an about-to-settle one harmless), then
        respawn the dead threads, bounded by `max_lane_revives`; past
        the budget the lane is marked dead and the admission front
        routes around it (all lanes dead = the global trip). Other
        lanes' work never notices."""
        if not lane._trip_lock.acquire(blocking=False):
            return  # a concurrent trip (dying thread + poll) owns it
        try:
            resilience.bump("watchdog_trips")
            with self._lock:
                revive = (lane.revives < self.max_lane_revives
                          and not self._closed)
                if not revive:
                    lane.dead = True
                leftover = [r for r in self._live
                            if getattr(r, "lane", None) is lane]
            self._fail(leftover, EngineClosed(
                f"lane {lane.index} worker thread(s) {names} died"
                + (f" ({exc!r})" if exc is not None else "")
                + f" — {len(leftover)} pending request(s) on this lane "
                "failed by the watchdog; other lanes unaffected"))
            if revive:
                lane.revive(exclude=dying)
                resilience.bump("lane_revives")
            if self._pool_pending():
                # the dead lane may have held the one in-flight wake:
                # re-arm the pool so queued cold starts aren't stranded
                self._wake_lane(force=True)
        finally:
            lane._trip_lock.release()

    # futures-owner
    def _watchdog_trip(self, names, exc) -> None:
        resilience.bump("watchdog_trips")
        with self._lock:
            self._closed = True
            self._not_full.notify_all()
            leftover = list(self._live)
        self._fail(leftover, EngineClosed(
            f"engine worker thread(s) {names} died"
            + (f" ({exc!r})" if exc is not None else "")
            + f" — {len(leftover)} pending request(s) failed by the "
            "watchdog instead of queueing forever"))
        # unwedge whichever workers survived
        for lane in self._lanes:
            lane._inq.put(_STOP)
            try:
                lane._outq.put_nowait(_STOP)
            # conflint: disable=CFX-FUTURE a full outq already wakes the drain; nothing owned here
            except Full:
                pass

    def _watchdog_loop(self) -> None:
        while True:
            time.sleep(self.watchdog_interval)
            # conflint: disable=CFX-LOCK benign racy poll; a stale read only delays one tick
            if self._closed:
                return
            if len(self._lanes) == 1:
                lane = self._lanes[0]
                dead = [t.name for t in (lane._dispatcher, lane._drainer)
                        if not t.is_alive()]
                if dead:
                    exc = (lane._dead[1] if lane._dead is not None
                           else None)
                    self._watchdog_trip(dead, exc)
                    return
                continue
            for lane in self._lanes:
                if lane.dead:
                    continue
                dead = [t.name for t in (lane._dispatcher, lane._drainer)
                        if not t.is_alive()]
                if dead:
                    exc = (lane._dead[1] if lane._dead is not None
                           else None)
                    self._lane_trip(lane, dead, exc)
            if all(ln.dead for ln in self._lanes):
                # nothing left to serve: the global trip fails whatever
                # is still pending and closes the engine
                self._watchdog_trip(["all lanes"], None)
                return

    # ------------------------------------------------------------------ #
    # observability (merged into profiler.serve_stats()['engine'])
    # ------------------------------------------------------------------ #

    def counters(self) -> dict:
        """The raw counter/gauge snapshot WITHOUT the percentile
        computation — the cheap read the windowed-telemetry path
        (`profiler.StatsWindow` → the controller tick) takes every
        interval. `stats()` sorts the full latency rings for its
        percentiles, which is fine for humans and benches but not for
        a 4-times-a-second control loop sharing one core with the
        dispatch path."""
        with self._lock:
            out = {
                "pending": self._pending,
                "queue_peak": self._queue_peak,
                "requests": self._requests,
                "completed": self._completed,
                "failed": self._failed,
                "shed": self._sheds,
                "batches": self._batches,
                "coalesced_requests": self._coalesced_requests,
                "factor_requests": self._factor_requests,
                "factor_batches": self._factor_batches,
                "factor_coalesced_requests": self._factor_coalesced,
                "factor_slots": self._factor_slots,
                "factor_pad_slots": self._factor_pad,
                "width_capped": self._width_capped,
                "gang_batches": self._gang_batches,
                "gang_coalesced_requests": self._gang_coalesced,
                "gang_opportunity": self._gang_opportunity,
                "stack_exclusions": dict(self._stack_exclusions),
                "gang": self._gang_locked(),
                "bucket_hits": dict(self._bucket_hits),
                "factor_bucket_hits": dict(self._factor_bucket_hits),
                "lanes": self._lane_rows_locked(),
            }
            if self._qos is not None:
                # present only once classified traffic (or a qos knob
                # write) created the state — a qos=None engine's
                # counter dict is unchanged
                out["qos"] = self._qos.counters(self.max_pending)
            return out

    # requires-lock: _lock
    def _gang_locked(self) -> dict:
        """Aggregate gang-residency gauges across every lane's gangs —
        SORT-FREE and lock-free on the gang side (racy reads of
        monotone counters by design; this rides the 10 Hz counters()
        path)."""
        gangs = members = slots = 0
        adopts = releases = refreshes = rebuilds = 0
        for ln in self._lanes:
            for g in ln._gangs.values():
                gangs += 1
                members += len(g._by_id)
                slots += g.cap
                adopts += g.adopts
                releases += g.releases
                refreshes += g.refreshes
                rebuilds += g.rebuilds
        return {"gangs": gangs, "sessions": members,
                "capacity_slots": slots, "adopts": adopts,
                "releases": releases, "refreshes": refreshes,
                "rebuilds": rebuilds}

    # requires-lock: _lock
    def _lane_rows_locked(self) -> list:
        """Per-lane telemetry rows — SORT-FREE (counters() ships these
        to the 10 Hz controller tick): per-device batches and coalesced
        means, cold-start batches, queue depth/high-water, busy-time
        occupancy, the resolved coalescing window, and the fault-domain
        state (revivals spent, dead flag)."""
        now = time.perf_counter()
        rows = []
        for ln in self._lanes:
            wall = max(1e-9, now - ln.t_start)
            busy = max(ln.busy_dispatch_s, ln.busy_drain_s)
            rows.append({
                "lane": ln.index,
                "device": (None if ln.device is None else str(ln.device)),
                "delay": ln.delay,
                "batches": ln.batches,
                "coalesced_requests": ln.coalesced,
                "coalesced_mean": (ln.coalesced / ln.batches
                                   if ln.batches else 0.0),
                "factor_batches": ln.factor_batches,
                "factor_coalesced_requests": ln.factor_coalesced,
                "gang_batches": ln.gang_batches,
                "gang_coalesced_requests": ln.gang_coalesced,
                "bucket_hits": dict(ln.bucket_hits),
                "pending": ln.pending,
                "sheds": ln.sheds,
                "queue_depth": ln._inq.qsize(),
                "queue_peak": ln.queue_hw,
                "occupancy": min(1.0, busy / wall),
                "revives": ln.revives,
                "dead": ln.dead,
            })
        return rows

    def stats(self) -> dict:
        """Engine counters: queue depth high-water mark, batches
        dispatched, mean coalesced batch size, shed count, and
        p50/p95/p99 request latency over the rolling window, plus the
        factor lane's cold-start counters — factor batches dispatched,
        mean coalesced factor-batch size, pad-waste ratio (identity pad
        slots / total bucket slots dispatched), and session-open
        latency percentiles. (Health outcomes — guard trips,
        escalations, evictions, quarantines — are global counters:
        `profiler.serve_stats()['health']`.)"""
        with self._lock:
            lats = sorted(self._latencies)
            flats = sorted(self._factor_latencies)
            batches = self._batches
            fbatches = self._factor_batches
            out = {
                "pending": self._pending,
                "queue_peak": self._queue_peak,
                "requests": self._requests,
                "completed": self._completed,
                "failed": self._failed,
                "shed": self._sheds,
                "batches": batches,
                "coalesced_requests": self._coalesced_requests,
                "coalesced_mean": (self._coalesced_requests / batches
                                   if batches else 0.0),
                "latency_p50_ms": 1e3 * _percentile(lats, 50),
                "latency_p95_ms": 1e3 * _percentile(lats, 95),
                "latency_p99_ms": 1e3 * _percentile(lats, 99),
                "factor_requests": self._factor_requests,
                "factor_batches": fbatches,
                "factor_coalesced_requests": self._factor_coalesced,
                "factor_coalesced_mean": (self._factor_coalesced / fbatches
                                          if fbatches else 0.0),
                "factor_slots": self._factor_slots,
                "factor_pad_slots": self._factor_pad,
                "factor_pad_waste": (self._factor_pad / self._factor_slots
                                     if self._factor_slots else 0.0),
                "factor_latency_p50_ms": 1e3 * _percentile(flats, 50),
                "factor_latency_p95_ms": 1e3 * _percentile(flats, 95),
                "factor_latency_p99_ms": 1e3 * _percentile(flats, 99),
                "width_capped": self._width_capped,
                "gang_batches": self._gang_batches,
                "gang_coalesced_requests": self._gang_coalesced,
                "gang_coalesced_mean": (self._gang_coalesced
                                        / self._gang_batches
                                        if self._gang_batches else 0.0),
                "gang_opportunity": self._gang_opportunity,
                "stack_exclusions": dict(self._stack_exclusions),
                "gang": self._gang_locked(),
                "bucket_hits": dict(self._bucket_hits),
                "factor_bucket_hits": dict(self._factor_bucket_hits),
                "lanes": self._lane_rows_locked(),
                "knobs": self._knobs_locked(),
            }
            psc = pfb = 0
            for ref in self._active_sessions.values():
                s = ref()
                if s is not None:
                    # conflint: disable=CFX-LOCK benign racy reads of monotonic ints (ops counter roll-up)
                    psc += s.precision_escalations
                    pfb += s.precision_fallbacks
            # ladder traffic roll-up over the engine's recently-served
            # sessions (§33): rung climbs + drifted-session tier
            # fallbacks. Global twins live in serve_stats()['health'].
            out["precision_escalations"] = psc
            out["precision_fallbacks"] = pfb
            if self._qos is not None:
                # per-class counters + latency percentiles + SLO
                # attainment (absent on a qos=None engine)
                out["qos"] = self._qos.stats(self.max_pending)
        if self.residency is not None:
            # outside the engine lock: the manager takes its own
            # (engine-lock -> manager-lock never nests)
            out["tier"] = self.residency.stats()
        if self._controller is not None:
            # likewise outside: the controller's stats take its own lock
            out["controller"] = self._controller.stats()
        return out

    def latency_samples(self) -> list:
        """The rolling latency window in seconds (profiler merges these
        across engines for fleet-wide percentiles)."""
        with self._lock:
            return list(self._latencies)

    def factor_latency_samples(self) -> list:
        """The factor lane's rolling session-open latency window in
        seconds (submit_factor admission -> session resolved)."""
        with self._lock:
            return list(self._factor_latencies)

    def latency_window(self, token: int | None = None) -> tuple:
        """(new_token, samples): the latencies recorded SINCE `token`
        (a sequence number returned by a previous call; None = the
        whole rolling window). The windowed read under the ring buffer:
        if more samples landed than the ring holds, the overflow is
        gone and the ring's full contents are returned. This is what
        `profiler.StatsWindow` (and through it the adaptive controller)
        percentiles over — tail latency of THIS window, not of the
        cumulative ring."""
        with self._lock:
            seq = self._lat_seq
            lats = list(self._latencies)
            if token is None:
                return seq, lats
            n = min(len(lats), max(0, seq - token))
            return seq, lats[len(lats) - n:] if n else []

    def factor_latency_window(self, token: int | None = None) -> tuple:
        """`latency_window` for the factor lane's session-open window."""
        with self._lock:
            seq = self._flat_seq
            lats = list(self._factor_latencies)
            if token is None:
                return seq, lats
            n = min(len(lats), max(0, seq - token))
            return seq, lats[len(lats) - n:] if n else []

    def qos_latency_samples(self) -> dict:
        """Per-class rolling latency windows in seconds, keyed
        'tenant/tier' ({} on a qos=None engine). The per-class twin of
        :meth:`latency_samples`."""
        with self._lock:
            st = self._qos
            if st is None:
                return {}
            return {k: list(d) for k, d in st.latencies.items()}

    def qos_latency_window(self, key: str,
                           token: int | None = None) -> tuple:
        """:meth:`latency_window` for ONE QoS class's ring (`key` is
        the 'tenant/tier' class key). A class the engine has not seen
        (including every key on a qos=None engine) reads as (0, []),
        so a per-class `profiler.StatsWindow` may open ahead of the
        class's first request."""
        with self._lock:
            st = self._qos
            if st is None or key not in st.latencies:
                return 0, []
            seq = st.lat_seq[key]
            lats = list(st.latencies[key])
            if token is None:
                return seq, lats
            n = min(len(lats), max(0, seq - token))
            return seq, lats[len(lats) - n:] if n else []
