"""Throughput serving: plan cache + device-resident solve sessions.

The one-shot entry points (`solvers.solve`, `lu_distributed_host`) pay a
host scatter, a jit trace, and a host gather per call, and repeated solves
against the same matrix re-run the whole O(N^3) pipeline. A serving
workload ("many users, many right-hand sides") wants the opposite cost
profile: compile once per *shape/config*, factor once per *matrix*, and
answer each request with only the O(N^2) substitution against factors that
never leave the device.

Two objects deliver that split:

- :class:`FactorPlan` — the compiled-program cache. ``FactorPlan.create``
  is keyed the way the internal ``_build*`` lru_caches already key
  (shape, dtype, tile size, knobs, mesh identity) but covers the WHOLE
  pipeline — factor program and solve program together — so a process
  serving one traffic shape compiles exactly two XLA programs, total.
  Plans also switch on the persistent compilation cache
  (`conflux_tpu.cache`), so even the first trace of a known config
  deserializes instead of compiling.

- :class:`SolveSession` — device-resident factors. ``plan.factor(A)``
  runs the factor program once and pins its outputs on device;
  ``session.solve(b)`` then runs only the substitution (+ the plan's
  refinement sweeps). N new RHS batches cost N substitutions — never a
  refactorization, never a host round-trip of the factors.

Batched plans (shape ``(B, N, N)``) vmap the blocked single-device
factor/solve over the batch and shard it across a `batch_mesh` as data
parallelism (see `conflux_tpu.batched`); 2D plans serve a single system
per call on one device. Every traced program bumps a plan-level trace
counter at trace time, so tests (and monitoring) can assert the
"zero recompiles after the first call" contract instead of trusting it.

Sessions also absorb *drift*: ``session.update(U, V)`` applies a rank-k
change A <- A + U V^H through a Sherman-Morrison-Woodbury correction
(`conflux_tpu.update`) instead of a refactorization — O(N^2 k) refresh,
O(N^2 + N k) per later solve, all device-resident, compiled once per
(rank bucket, RHS bucket) — and a :class:`~conflux_tpu.update.DriftPolicy`
triggers one true refactor through the plan's cached factor program when
accumulated rank or capacitance conditioning stops paying.

    plan = FactorPlan.create((32, 256, 256), jnp.float32, v=128, mesh=mesh)
    session = plan.factor(A)          # O(N^3), once
    x1 = session.solve(b1)            # O(N^2) substitution only
    x2 = session.solve(b2)            # same compiled program, same factors
    session.update(U, V)              # rank-k drift, NO refactor
    x3 = session.solve(b3)            # base factors + k x k correction
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from conflux_tpu.ops import blas
from conflux_tpu import profiler
from conflux_tpu.batched import (
    _batch_spec,
    _shard_batch,
    put_tree,
    unstack_tree,
)
from conflux_tpu.parallel.mesh import lookup_mesh, mesh_cache_key
from conflux_tpu.update import (
    DriftPolicy,
    capacitance,
    health_spot_check,
    health_spot_check_slots,
    probe_row,
    probe_vector,
    rank_bucket,
    updated_matvec,
    woodbury_apply,
)

# Every solve program computes its right-hand sides in column tiles of this
# fixed width. XLA picks its dot kernel by the RHS width (under jax 0.9.0
# the CPU backend has separate kernels at width 1, 2-16, 32 and 64, with
# different accumulation orders), so without tiles one column's bits would
# depend on how many requests the engine coalesced with it — and coalesced
# answers must be bitwise `SolveSession.solve`'s (DESIGN §19).
RHS_TILE = 8


def _rhs_tiles(fn, *cols):
    """fn over RHS_TILE-wide column tiles of `cols` (each (..., n_i, nb),
    nb a power of two; narrower buckets zero-pad to one tile). Returns
    fn's outputs with a leading tile axis. A single tile calls fn
    directly; more ride one `lax.map`, which compiles the same tile body."""
    nb = cols[0].shape[-1]
    if nb <= RHS_TILE:
        pad = RHS_TILE - nb
        cols = [jnp.pad(c, [(0, 0)] * (c.ndim - 1) + [(0, pad)])
                for c in cols]
        return jax.tree.map(lambda o: o[None], fn(*cols))
    t = nb // RHS_TILE
    split = [jnp.moveaxis(c.reshape(c.shape[:-1] + (t, RHS_TILE)), -2, 0)
             for c in cols]
    return lax.map(lambda cs: fn(*cs), split)


def _untile(x, nb: int):
    """(t, ..., n, RHS_TILE) column tiles back to (..., n, nb)."""
    x = jnp.moveaxis(x, 0, -2)
    return x.reshape(x.shape[:-2] + (-1,))[..., :nb]


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Identity of a compiled serving pipeline — the cache key.

    Mirrors the keying of the internal ``_build*`` caches (geometry +
    mesh identity + trace-time knobs), lifted to the serving surface:
    two calls that agree on every field share one compiled factor program
    and one compiled solve program.
    """

    shape: tuple          # (B, N, N) batched, (N, N) single, or (M, N)
                          # tall-skinny (kind='qr' least squares, M >= N)
    dtype: str            # storage dtype of A
    factor_dtype: str     # dtype the factorization runs in (HPL-MxP knob)
    v: int                # tile size
    refine: int           # classic-IR sweeps fused into the solve program
    kind: str             # factorization family: 'lu' | 'chol' | 'qr'
                          # (DESIGN §33 — replaces the old spd boolean;
                          # 'qr' serves min||Ax-b|| least-squares)
    substitution: str     # 'trsm' | 'inv' | 'blocked' ('auto' resolves
                          # at create — DESIGN §27)
    precision: Any        # trailing-GEMM precision
    backend: str          # gemm backend
    panel_algo: str       # LU panel election algo
    mesh_key: Any         # batch-mesh identity (None = default device)

    @property
    def spd(self) -> bool:
        """Back-compat read of the pre-§33 boolean: True iff the plan
        factors by Cholesky. Writers must use `kind` — the codec and
        the cache key speak `kind` only."""
        return self.kind == "chol"


PLAN_KINDS = ("lu", "chol", "qr")

# the per-request precision ladder (DESIGN §33): each served tier names
# a factor dtype + the IR sweeps its solve programs fuse. 'bf16_ir'
# factors in bfloat16 (half the resident factor bytes of f32) and ALWAYS
# refines at least once; 'f64' degrades to f32 storage when x64 is off
# (jax canonicalizes the dtype — same programs, documented in TUNING).
# Requests say precision='auto' to start on the cheapest rung and let
# the §20 Freivalds verdict drive escalation up this tuple.
PRECISION_TIERS = ("bf16_ir", "f32", "f64")


def check_precision_request(precision):
    """Validate a per-request ``precision=`` value (submit/solve
    surface): None (the plan's native path, bitwise pre-§33 behavior),
    a served tier name, or 'auto'. Returns the value; raises
    ValueError naming the offending value otherwise."""
    if precision is None or precision == "auto" \
            or precision in PRECISION_TIERS:
        return precision
    raise ValueError(
        f"unknown precision {precision!r} — expected None, 'auto', or "
        f"one of {PRECISION_TIERS}")


def next_precision_tier(tier: str):
    """The next rung up the ladder, or None at the top (escalation
    then falls through to the native `resilience.escalate` rungs)."""
    i = PRECISION_TIERS.index(tier)
    return PRECISION_TIERS[i + 1] if i + 1 < len(PRECISION_TIERS) \
        else None


_PLANS: dict[PlanKey, "FactorPlan"] = {}
_PLANS_LOCK = threading.Lock()


def _encode_precision(p):
    """JSON-encode a PlanKey's trailing-GEMM precision. Only the enum
    (tagged), None, and plain strings are representable — anything else
    (a tuple of precisions, a config object, a jnp dtype) would pass
    through json.dump into the fleet codec and poison every later
    restore, so it is refused HERE with the offending value named,
    while the checkpoint is still writable."""
    if isinstance(p, lax.Precision):
        return ["precision", p.name]
    if p is None or isinstance(p, str):
        return p
    raise ValueError(
        f"plan precision {p!r} (type {type(p).__name__}) is not "
        "codec-representable — use None, a string, or lax.Precision")


def _decode_precision(p):
    """Inverse of :func:`_encode_precision`. Malformed payloads (a
    mistagged list, a number, a dict — anything no encoder produced)
    raise ValueError with the offending value instead of flowing into
    a PlanKey that would never match its originating plan."""
    if isinstance(p, list):
        if len(p) == 2 and p[0] == "precision" \
                and isinstance(p[1], str) \
                and p[1] in lax.Precision.__members__:
            return lax.Precision[p[1]]
        raise ValueError(
            f"malformed precision payload {p!r} — expected "
            "['precision', <enum name>]")
    if p is None or isinstance(p, str):
        return p
    raise ValueError(
        f"malformed precision payload {p!r} (type {type(p).__name__}) "
        "— expected None, a string, or a tagged enum pair")


def plan_spec(plan: "FactorPlan") -> dict:
    """JSON-serializable identity of a plan — the persistence/wire
    codec shared by the checkpoint fleet.json (`tier.save_fleet`), the
    serve fabric's cross-process session open (`conflux_tpu.fabric`
    worker 'open' op, DESIGN §28) and anything else that must rebuild
    the EXACT plan in another process. Mesh-sharded plans carry their
    mesh identity (device ids + axis names + device-grid shape) in a
    ``"mesh"`` sub-dict; :func:`plan_from_spec` rebuilds the mesh on a
    process holding the SAME local devices (cross-host restore of
    sharded state stays unsupported — DESIGN §32)."""
    k = plan.key
    d = {"shape": list(k.shape), "dtype": k.dtype,
         "factor_dtype": k.factor_dtype, "v": k.v,
         "refine": k.refine, "kind": k.kind,
         "substitution": k.substitution,
         "precision": _encode_precision(k.precision),
         "backend": k.backend, "panel_algo": k.panel_algo}
    if k.mesh_key is not None:
        mesh = plan.mesh
        d["mesh"] = {
            "device_ids": [int(dev.id) for dev in mesh.devices.flat],
            "axis_names": [str(a) for a in mesh.axis_names],
            "device_shape": [int(s) for s in mesh.devices.shape]}
    return d


def mesh_from_spec(m: dict):
    """Rebuild a batch mesh from its :func:`plan_spec` wire identity —
    the mesh half of the checkpoint/fabric codec. The rebuilt mesh
    registers under the SAME `mesh_cache_key` as the original (the key
    is (device ids, axis names)), so a restored plan lands on the
    identical PlanKey and compiled-program family. A process that does
    not hold every named device id cannot host the sharded state —
    that is the genuine cross-host-migration residue, surfaced as
    :class:`~conflux_tpu.resilience.MeshPlanUnsupported`."""
    import numpy as np

    ids = [int(i) for i in m["device_ids"]]
    local = {dev.id: dev for dev in jax.devices()}
    missing = [i for i in ids if i not in local]
    if missing:
        from conflux_tpu.resilience import MeshPlanUnsupported

        raise MeshPlanUnsupported(
            f"mesh plan names device ids {missing} this process does "
            "not hold — sharded session state cannot migrate across "
            "hosts (restore on a host with the same device topology)",
            surface="plan_codec")
    devs = np.array([local[i] for i in ids], dtype=object)
    devs = devs.reshape(tuple(int(s) for s in m["device_shape"]))
    return jax.sharding.Mesh(devs, tuple(str(a) for a in m["axis_names"]))


def plan_from_spec(d: dict) -> "FactorPlan":
    """Reconstruct the EXACT PlanKey from a :func:`plan_spec` dict
    (trace-time knobs included, not re-derived from process globals)
    and get-or-build its plan — the restore/adopt path's half of the
    bitwise contract: same key, same compiled program family, same
    bits. Mesh plans rebuild their mesh from the spec's ``"mesh"``
    sub-dict (:func:`mesh_from_spec`) — same devices, same axis names,
    same out_shardings."""
    mesh_key = None
    m = d.get("mesh")
    if m is not None:
        mesh_key = mesh_cache_key(mesh_from_spec(m))
    # migration shim (§33): pre-kind checkpoints spelled the
    # factorization family as a bare 'spd' boolean — decode it so every
    # PR-16-era durable fleet.json stays restorable bitwise
    if "kind" in d:
        kind = str(d["kind"])
        if kind not in PLAN_KINDS:
            raise ValueError(
                f"plan spec names unknown kind {kind!r} — expected one "
                f"of {PLAN_KINDS}")
    else:
        kind = "chol" if bool(d["spd"]) else "lu"
    key = PlanKey(
        shape=tuple(int(s) for s in d["shape"]), dtype=d["dtype"],
        factor_dtype=d["factor_dtype"], v=int(d["v"]),
        refine=int(d["refine"]), kind=kind,
        substitution=d["substitution"],
        precision=_decode_precision(d["precision"]),
        backend=d["backend"], panel_algo=d["panel_algo"],
        mesh_key=mesh_key)
    return FactorPlan.from_key(key)


class _CompileOnce:
    """Serialize the FIRST call of a jitted program; later calls bypass.

    jax.jit wrappers are cheap to build but trace on first call, and two
    engine workers hitting a cold wrapper concurrently can both pay the
    trace (double-compiling the bucket and double-bumping the plan's
    trace counters). Memoizing the wrapper under the plan lock is not
    enough — the trace happens at call time — so the first execution
    holds a per-program lock; once it completes, the hot path is
    lock-free.
    """

    __slots__ = ("fn", "_lock", "_warm")

    def __init__(self, fn):
        self.fn = fn
        self._lock = threading.Lock()
        self._warm = False

    def __call__(self, *args):
        if self._warm:
            return self.fn(*args)
        with self._lock:
            out = self.fn(*args)
            self._warm = True
        return out

    @property
    def warm(self) -> bool:
        """True once the first call completed — the program is traced
        and cached, so later calls are dispatch-only. The adaptive
        controller's prewarm gate (`FactorPlan.bucket_ready`) reads
        this: a knob move may only route traffic onto warm buckets."""
        return self._warm


def clear_plans() -> None:
    """Drop every cached plan (tests; frees the jitted closures)."""
    with _PLANS_LOCK:
        _PLANS.clear()


class FactorPlan:
    """A reusable, cached scatter→factor→solve pipeline for one config.

    Construct through :meth:`create` (the cache); the constructor itself
    builds the jitted programs but does not trace them — tracing happens
    on first use and is counted in :attr:`trace_counts`.
    """

    def __init__(self, key: PlanKey):
        self.key = key
        shape = key.shape
        self.batched = len(shape) == 3
        self.B = shape[0] if self.batched else None
        self.N = shape[-1]
        # M is the RHS row count (== N for the square kinds; > N for a
        # tall-skinny 'qr' least-squares plan) — _rhs/_stage size by it
        self.M = shape[-2]
        if key.kind not in PLAN_KINDS:
            raise ValueError(
                f"unknown plan kind {key.kind!r} — expected one of "
                f"{PLAN_KINDS}")
        if key.kind == "qr":
            if self.batched:
                raise ValueError(
                    "kind='qr' serves single tall-skinny systems — a "
                    f"batched plan shape {shape} has no least-squares "
                    "semantics here (open one session per system; the "
                    "engine's factor lane coalesces them)")
            if self.M < self.N:
                raise ValueError(
                    f"kind='qr' needs M >= N (min||Ax-b|| over a "
                    f"tall-skinny A), got {shape}")
            if key.substitution != "trsm":
                raise ValueError(
                    "kind='qr' substitutes through R only "
                    "(substitution='trsm'); 'blocked'/'inv' are the "
                    "square kinds' engines")
        else:
            if shape[-1] != shape[-2]:
                raise ValueError(
                    f"plan needs square systems, got {shape}")
            if self.N % key.v:
                raise ValueError(
                    f"N={self.N} not a multiple of v={key.v}; pre-pad "
                    "with an identity extension (cf. solvers.solve)")
        self.mesh = (lookup_mesh(key.mesh_key)
                     if key.mesh_key is not None else None)
        if key.kind == "qr" and key.mesh_key is not None:
            raise ValueError(
                "kind='qr' plans are unsharded (a single tall system "
                "has no batch axis to mesh-shard)")
        if self.mesh is not None and not self.batched:
            raise ValueError(
                "a mesh only applies to batched (B, N, N) plans — a single "
                "system has no batch axis to shard")
        if self.batched and self.mesh is not None \
                and self.B % self.mesh.devices.size:
            raise ValueError(
                f"plan batch {self.B} must be a multiple of the mesh size "
                f"{self.mesh.devices.size} (pad the batch, or create the "
                "plan at the padded size and slice results)")
        # trace-time side effects let tests assert "second call compiles
        # nothing" without reaching into jax internals
        self.trace_counts = {"factor": 0, "solve": 0}
        # concurrent engine workers fill the memoized program caches
        # double-checked under this lock (one built wrapper per bucket)
        # and serialize each wrapper's first call through _CompileOnce
        # (one TRACE per bucket) — see tests/test_serve.py's thread hammer
        self._compile_lock = threading.Lock()
        self._factor_fn = _CompileOnce(self._build_factor())
        self._solve_cache: dict[Any, Any] = {}
        self._update_cache: dict[tuple, Any] = {}
        # the factor lane's stacked cold-start programs, keyed by batch
        # bucket (kept apart from _solve_cache, whose keys tests assert)
        self._factor_cache: dict[tuple, Any] = {}
        # the blocked-trsm engine's fused-probe checked programs
        # (DESIGN §27) — their OWN memo dict, again because
        # tests/test_serve.py asserts set(_solve_cache) == width
        # buckets exactly; release_buckets/bucket_ready cover it like
        # the others so the adaptive controller's grow/retire cycle
        # can neither strand nor re-compile the family
        self._trsm_cache: dict[tuple, Any] = {}
        # per-DEVICE warm registry (kept apart from the program caches,
        # whose key sets tests assert): one jitted program traces once
        # per shape but compiles one executable per device it runs on,
        # so a mesh-sharded serve fleet must warm each (kind, bucket)
        # once per LANE device. Engine prewarm records completions here
        # and dedupes identical (plan, bucket, device) work across
        # sessions/lanes; devkey None is the default device.
        self._warm_devices: set = set()  # guarded-by: _compile_lock

    def _memo(self, cache: dict, key, build):
        """Double-checked get-or-build of a compiled-program cache entry;
        the built wrapper is a :class:`_CompileOnce` so the bucket is
        traced exactly once even under concurrent first callers."""
        fn = cache.get(key)
        if fn is None:
            with self._compile_lock:
                fn = cache.get(key)
                if fn is None:
                    fn = _CompileOnce(build())
                    cache[key] = fn
        return fn

    # ------------------------------------------------------------------ #
    # cache
    # ------------------------------------------------------------------ #

    @classmethod
    def create(cls, shape, dtype, *, v: int = 256, factor_dtype=None,
               refine: int = 0, kind: str | None = None,
               spd: bool = False, mesh=None,
               substitution: str = "auto", precision=None,
               backend: str | None = None,
               persistent_cache: bool = True) -> "FactorPlan":
        """Get-or-build the plan for a traffic shape.

        shape is (B, N, N) for a batched plan, (N, N) for a
        single-system plan, or (M, N) with M > N for a tall-skinny
        least-squares plan (`kind='qr'`); `dtype` is the request dtype.
        `kind` picks the factorization family: 'lu' (default), 'chol'
        (SPD A), or 'qr' (min||Ax-b|| via the blocked CholeskyQR2
        recipe, `conflux_tpu.qr` — sessions answer the least-squares
        solution x of each rhs). `spd=True` is the pre-§33 spelling of
        kind='chol' and stays accepted. `factor_dtype`, `refine` follow
        `solvers.solve`; `mesh` (a `batch_mesh`) shards batched plans
        across devices. `persistent_cache=True` also switches on the
        on-disk XLA cache so cold processes reuse warm compiles.

        `substitution` picks the per-request engine: 'trsm' runs the
        classic triangular substitutions; 'blocked' runs them BLOCKED
        (diagonal-block inverses computed at factor time, O(N/bs)
        GEMM steps per solve — `conflux_tpu.ops.batched_trsm`, DESIGN
        §27); 'inv' inverts the FULL triangular factors at factor time
        so every solve is two batched GEMVs. XLA's *batched* small-rhs
        triangular_solve is serial per row (measured 70x slower than
        the GEMV form at B=32, N=256 on CPU), and every servable plan
        may be dispatched VMAPPED — batched plans over their own batch
        axis, single-system plans through the factor lane's stacked
        programs (§21) and the gang-resident stacks (§26) — so 'auto'
        resolves to 'blocked' everywhere: triangular accuracy (error
        growth ~ max cond of a bs-wide diagonal block) at GEMM speed.
        'trsm' and 'inv' stay explicit opt-ins; 'inv' trades the
        larger cond(L) cond(U) growth term for the two-GEMV solve
        shape. The serve tests hold every engine to the one-shot
        oracle's residual bars, and the plan's `refine` sweeps restore
        working accuracy when the traffic is harder.
        """
        if persistent_cache:
            from conflux_tpu import cache

            cache.enable_persistent_cache()
        dtype = jnp.dtype(dtype)
        fdtype = dtype if factor_dtype is None else jnp.dtype(factor_dtype)
        precision = (blas.matmul_precision() if precision is None
                     else precision)
        backend = blas.get_backend() if backend is None else backend
        if kind is None:
            kind = "chol" if spd else "lu"
        elif spd and kind != "chol":
            raise ValueError(
                f"kind={kind!r} contradicts spd=True (the legacy "
                "spelling of kind='chol') — pass one or the other")
        if kind == "qr" and substitution == "auto":
            # QR substitutes through R alone (one triangular solve on
            # the Q^H-projected rhs) — the blocked/inv engines are the
            # square kinds' machinery
            substitution = "trsm"
        if substitution == "auto":
            # branch on how the plan will be SERVED, not on its shape
            # alone: batched plans vmap their solve body over the batch
            # axis, and single-system plans are served vmapped too —
            # the factor lane's stacked programs and the gang-resident
            # stacks (§21/§26) — so every auto plan takes the blocked
            # engine (the vmapped-safe fast path). Callers wanting the
            # classic serial substitutions or the full-inverse GEMV
            # form opt in explicitly.
            served_vmapped = len(shape) == 3 or mesh is None
            substitution = "blocked" if served_vmapped else "trsm"
        if substitution not in ("trsm", "inv", "blocked"):
            raise ValueError(
                f"unknown substitution {substitution!r} "
                "(auto|trsm|inv|blocked)")
        key = PlanKey(
            shape=tuple(int(s) for s in shape), dtype=dtype.name,
            factor_dtype=fdtype.name, v=int(v), refine=int(refine),
            kind=kind, substitution=substitution,
            precision=precision, backend=backend,
            panel_algo=blas.get_panel_algo(),
            mesh_key=None if mesh is None else mesh_cache_key(mesh))
        with _PLANS_LOCK:
            plan = _PLANS.get(key)
            if plan is None:
                plan = cls(key)
                _PLANS[key] = plan
        return plan

    @classmethod
    def from_key(cls, key: PlanKey) -> "FactorPlan":
        """Get-or-build the plan for an EXACT :class:`PlanKey` — the
        checkpoint-restore path (`conflux_tpu.tier.load_fleet`), which
        must reconstruct the key verbatim (trace-time knobs included)
        rather than re-derive them from process globals: same key, same
        compiled program family, bitwise the same restored solves."""
        if not isinstance(key, PlanKey):
            raise TypeError(f"from_key takes a PlanKey, got "
                            f"{type(key).__name__}")
        with _PLANS_LOCK:
            plan = _PLANS.get(key)
            if plan is None:
                plan = cls(key)
                _PLANS[key] = plan
        return plan

    def spec(self) -> dict:
        """This plan's :func:`plan_spec` dict (JSON/wire codec)."""
        return plan_spec(self)

    @classmethod
    def from_spec(cls, d: dict) -> "FactorPlan":
        """Get-or-build the plan a :func:`plan_spec` dict names."""
        return plan_from_spec(d)

    # ------------------------------------------------------------------ #
    # bucket lifecycle (the adaptive controller's actuation surface)
    # ------------------------------------------------------------------ #

    def bucket_ready(self, *, width: int | None = None,
                     factor_batch: int | None = None,
                     stack=None,
                     checked: bool = False,
                     precision: str | None = None) -> bool:
        """True when the named bucket's program is built AND warm (first
        call completed — traced, cached, dispatch-only from here on).

        The prewarm-before-switch gate: `conflux_tpu.control.
        AdaptiveController` grows an engine's active bucket set by
        prewarming the target bucket on a background thread and only
        actuating the knob once this reports True, so a knob move can
        never put a compile stall on the serving path. `checked` asks
        about the health-guarded program variant (what an engine with
        ``check_output`` dispatches). `precision` asks about a served
        tier's program family instead of the native one: with `width`,
        the per-tier solve program (`("tier", tier, wb)` /
        `("tier_health", tier, wb)`); with `factor_batch`, the per-tier
        stacked factor program (`("tier_factor", tier, bb)`)."""
        # checked programs of a fused-probe (blocked) plan live in
        # their own memo dict — look there, or a controller knob move
        # would see a warm bucket as forever-cold (or vice versa)
        checked_cache = (self._trsm_cache if self._fused_probe
                         else self._solve_cache)
        if precision is not None:
            tier = check_precision_request(precision)
            if tier is None or tier == "auto":
                raise ValueError(
                    "bucket_ready(precision=) names a concrete tier "
                    f"from {PRECISION_TIERS}, not {precision!r}")
            if width is not None:
                key = (("tier_health", tier, int(width)) if checked
                       else ("tier", tier, int(width)))
                fn = self._solve_cache.get(key)
                if fn is None or not fn.warm:
                    return False
            if factor_batch is not None:
                fn = self._factor_cache.get(
                    ("tier_factor", tier, int(factor_batch)))
                if fn is None or not fn.warm:
                    return False
            if stack is not None:
                raise ValueError(
                    "gang-stacked buckets have no per-tier program "
                    "family (tier requests are a counted gang "
                    "exclusion, DESIGN §33)")
            return width is not None or factor_batch is not None
        if width is not None:
            key = ("health", int(width)) if checked else int(width)
            fn = (checked_cache if checked else self._solve_cache).get(key)
            if fn is None or not fn.warm:
                return False
        if factor_batch is not None:
            key = (("factor_health", int(factor_batch)) if checked
                   else ("factor", int(factor_batch)))
            fn = self._factor_cache.get(key)
            if fn is None or not fn.warm:
                return False
        if stack is not None:
            # stack = (sessions, width): the gang-stacked bucket the
            # adaptive controller prewarm-gates before flipping
            # `stack_sessions` on (DESIGN §26)
            sb, wb = int(stack[0]), int(stack[1])
            key = (("gstack_health", sb, wb) if checked
                   else ("stacked", sb, wb))
            fn = (checked_cache if checked else self._solve_cache).get(key)
            if fn is None or not fn.warm:
                return False
        return (width is not None or factor_batch is not None
                or stack is not None)

    def release_buckets(self, widths=(), factor_batches=()) -> int:
        """Drop retired bucket programs from the plan's caches — the
        reverse of prewarming, so a bucket set that grew under a traffic
        peak does not pin dead compiled programs (and their jitted
        closures) forever. `widths` drops each RHS bucket's plain,
        checked, refine, and stacked solve programs from `_solve_cache`;
        `factor_batches` drops the stacked cold-start programs (plain +
        checked) from `_factor_cache`. Non-bucket entries — the probe
        program, the Woodbury update programs — are never touched, and
        factor bucket 1 is refused outright: ``plan.factor`` itself
        rides it. Returns the number of cache entries dropped.

        A released bucket is not forbidden, just cold: traffic touching
        it again rebuilds and re-TRACES the program (`trace_counts`
        grow), which is exactly why the adaptive controller retires only
        buckets with a long zero-hit history and the zero-compile
        steady-state contract is stated over the ACTIVE bucket set. A
        dispatcher holding a wrapper it fetched before the release keeps
        using it safely — release only unlinks the cache entry."""
        dropped = 0
        with self._compile_lock:
            wbs = {int(w) for w in widths}
            fbs = set()
            for w in widths:
                wb = int(w)
                keys = [wb, ("health", wb), ("refine", wb)]
                keys += [k for k in self._solve_cache
                         if isinstance(k, tuple) and len(k) == 3
                         and k[0] in ("stacked", "gstack_health",
                                      "tier", "tier_health")
                         and k[2] == wb]
                for key in keys:
                    dropped += self._solve_cache.pop(key, None) is not None
                # the blocked engine's fused-probe checked programs
                # retire with their width bucket too — a retired bucket
                # must not pin the family's jitted closures, and a
                # regrow must re-trace (never find a stale wrapper)
                tkeys = [("health", wb)]
                tkeys += [k for k in self._trsm_cache
                          if len(k) == 3 and k[0] == "gstack_health"
                          and k[2] == wb]
                for key in tkeys:
                    dropped += self._trsm_cache.pop(key, None) is not None
            for bb in factor_batches:
                bb = int(bb)
                if bb == 1:
                    raise ValueError(
                        "factor bucket 1 is the plan.factor/refactor "
                        "path itself (FactorPlan._factor_once) — it is "
                        "not a retirable coalescing bucket")
                fbs.add(bb)
                keys = [("factor", bb), ("factor_health", bb)]
                keys += [k for k in self._factor_cache
                         if isinstance(k, tuple) and len(k) == 3
                         and k[0] == "tier_factor" and k[2] == bb]
                for key in keys:
                    dropped += (self._factor_cache.pop(key, None)
                                is not None)
            # a released bucket is COLD again on every device: drop its
            # per-device warm records too, or a later regrow would skip
            # the re-warm and put the first-dispatch compile back on
            # the serving path
            self._warm_devices = {
                k for k in self._warm_devices
                if not (
                    (k[0] in ("solve", "solve_health") and k[1] in wbs)
                    or (k[0] in ("stacked", "stacked_health")
                        and isinstance(k[1], tuple) and k[1][1] in wbs)
                    or (k[0] == "stacked_usolve"
                        and isinstance(k[1], tuple) and k[1][2] in wbs)
                    or (k[0] in ("tier", "tier_health")
                        and isinstance(k[1], tuple) and k[1][1] in wbs)
                    or (k[0] == "tier_factor"
                        and isinstance(k[1], tuple) and k[1][1] in fbs)
                    or (k[0] in ("factor", "factor_health")
                        and k[1] in fbs))}
        return dropped

    @staticmethod
    def _warm_key(kind: str, bucket, devkey) -> tuple:
        # composite buckets ((stack, width), (stack, rank, width),
        # (tier, width)) pass through as tuples; int() on them was a
        # latent crash, and tier names are strings — pass those through
        b = (tuple((x if isinstance(x, str) else int(x)) for x in bucket)
             if isinstance(bucket, tuple) else int(bucket))
        return (kind, b, devkey)

    def device_warm(self, kind: str, bucket, devkey) -> bool:
        """True when (kind, bucket) has completed a warm-up dispatch on
        the device identified by `devkey` (see `engine._devkey`; None =
        the default device). The per-lane prewarm dedupe read. `bucket`
        is an int for the width/factor families and a tuple for the
        stacked ones."""
        with self._compile_lock:
            return self._warm_key(kind, bucket, devkey) \
                in self._warm_devices

    def mark_device_warm(self, kind: str, bucket, devkey) -> None:
        """Record a completed (kind, bucket, device) warm-up. Called by
        the engine AFTER the warming dispatch finished, so a crashed
        prewarm never poisons the registry."""
        with self._compile_lock:
            self._warm_devices.add(self._warm_key(kind, bucket, devkey))

    # ------------------------------------------------------------------ #
    # program builders
    # ------------------------------------------------------------------ #

    def _one_factor(self, A, fdtype=None):
        """Per-system factorization in the factor dtype (`fdtype`
        overrides the key's — the per-request precision ladder's served
        tiers factor the SAME base at their own dtype, §33). Returns the
        device-resident factor pytree the solve program consumes: packed
        factors for 'trsm' substitution, packed factors + diagonal-block
        inverses for 'blocked' (the bs-wide blocks only — O(N bs^2)
        inversion work, `ops.batched_trsm.diag_block_inverses`), explicit
        FULL triangular inverses (computed here, once, in the compute
        dtype) for 'inv', and the thin (Q, R) pair for kind='qr'
        (blocked CholeskyQR2, `qr.single.qr_factor_blocked`)."""
        from conflux_tpu.cholesky.single import _cholesky_blocked
        from conflux_tpu.lu.single import _lu_factor_blocked
        from conflux_tpu.ops.batched_trsm import diag_block_inverses

        self.trace_counts["factor"] += 1  # trace-time, not per call
        k = self.key
        fd = jnp.dtype(k.factor_dtype if fdtype is None else fdtype)
        Af = A.astype(fd)
        cdtype = blas.compute_dtype(fd)
        if k.kind == "qr":
            from conflux_tpu.qr.single import qr_factor_blocked

            Q, R = qr_factor_blocked(Af, v=min(k.v, self.N))
            return (Q, R)
        if k.spd:
            L = _cholesky_blocked(Af, k.v, k.precision, k.backend)
            if k.substitution == "blocked":
                Dl = diag_block_inverses(L.astype(cdtype), lower=True)
                return (L, Dl)
            if k.substitution != "inv":
                return (L,)
            eye = jnp.eye(self.N, dtype=cdtype)
            Li = lax.linalg.triangular_solve(
                L.astype(cdtype), eye, left_side=True, lower=True)
            return (Li,)
        LU, perm = _lu_factor_blocked(Af, k.v, k.precision, k.backend,
                                      k.panel_algo)
        if k.substitution == "blocked":
            LUc = LU.astype(cdtype)
            Dl = diag_block_inverses(LUc, lower=True, unit_diagonal=True)
            Du = diag_block_inverses(LUc, lower=False)
            return (LU, Dl, Du, perm)
        if k.substitution != "inv":
            return (LU, perm)
        LUc = LU.astype(cdtype)
        eye = jnp.eye(self.N, dtype=cdtype)
        Li = lax.linalg.triangular_solve(
            LUc, eye, left_side=True, lower=True, unit_diagonal=True)
        Ui = lax.linalg.triangular_solve(
            LUc, eye, left_side=True, lower=False)
        return (Li, Ui, perm)

    def _base_corr(self, factors):
        """The per-system base substitution r -> A0^{-1} r through the
        resident factor pytree — shared by the solve program and the
        Woodbury update programs (which wrap it in the capacitance
        correction). Traceable; factors carry no batch axis here (vmap
        adds it outside)."""
        from conflux_tpu.solvers import cholesky_solve, lu_solve

        k = self.key
        if k.kind == "qr":
            # least-squares normal-equations-free substitution: project
            # the (M, k) residual/rhs onto range(A) through Q^H, then
            # one triangular solve through R — (M, k) -> (N, k). The IR
            # sweep in _one_solve reuses this corr verbatim (the corr of
            # the LS residual IS the LS correction).
            Q, R = factors
            hi = lax.Precision.HIGHEST

            def qr_corr(r):
                y = jnp.matmul(Q.conj().T, r.astype(Q.dtype),
                               precision=hi)
                return lax.linalg.triangular_solve(
                    R, y, left_side=True, lower=False)

            return qr_corr
        if k.substitution == "blocked":
            from conflux_tpu.ops.batched_trsm import blocked_solve

            # the blocked engine (DESIGN §27): forward + back
            # substitution through the factor-resident diagonal-block
            # inverses — every step a GEMM, so the vmapped stacked
            # programs never touch XLA's serial batched trsm
            if k.spd:
                L, Dl = factors

                def corr(r):
                    Lc = L.astype(Dl.dtype)
                    y = blocked_solve(Lc, Dl, r.astype(Dl.dtype),
                                      lower=True)
                    Du = jnp.swapaxes(Dl.conj(), -1, -2)
                    return blocked_solve(Lc.conj().T, Du, y,
                                         lower=False)
            else:
                LU, Dl, Du, perm = factors

                def corr(r):
                    LUc = LU.astype(Dl.dtype)
                    y = blocked_solve(LUc, Dl,
                                      r.astype(Dl.dtype)[perm],
                                      lower=True)
                    return blocked_solve(LUc, Du, y, lower=False)
            return corr
        if k.substitution == "inv":
            hi = lax.Precision.HIGHEST
            if k.spd:
                Li = factors[0]

                def corr(r):
                    y = jnp.matmul(Li, r.astype(Li.dtype), precision=hi)
                    return jnp.matmul(Li.conj().T, y, precision=hi)
            else:
                Li, Ui, perm = factors

                def corr(r):
                    y = jnp.matmul(Li, r.astype(Li.dtype)[perm],
                                   precision=hi)
                    return jnp.matmul(Ui, y, precision=hi)
            return corr
        if k.spd:
            return lambda r: cholesky_solve(factors[0], r)
        return lambda r: lu_solve(factors[0], factors[1], r)

    def _one_solve(self, factors, A, b2, sweeps=None):
        """Per-system substitution + the plan's IR sweeps (`sweeps`
        overrides the key's — the served tiers fuse their own count,
        §33). `A` is only consumed when the sweep count > 0 (the
        residual matvec — for kind='qr' the (M, k) residual's corr IS
        the least-squares correction, so the same loop refines
        min||Ax-b||)."""
        self.trace_counts["solve"] += 1  # trace-time, not per call
        k = self.key
        corr = self._base_corr(factors)
        cdtype = blas.compute_dtype(jnp.dtype(k.dtype))
        sweeps = k.refine if sweeps is None else sweeps

        def tile(b):
            x = corr(b).astype(cdtype)
            for _ in range(sweeps):
                r = (b.astype(cdtype)
                     - jnp.matmul(A.astype(cdtype), x,
                                  precision=lax.Precision.HIGHEST))
                x = x + corr(r).astype(cdtype)
            return x

        return _untile(_rhs_tiles(tile, b2), b2.shape[-1])

    def _build_factor(self):
        fn = self._one_factor
        if self.batched:
            fn = jax.vmap(fn)
        if self.mesh is None:
            return jax.jit(fn)
        # the factor pytree per mode — (L,) / (Li,) spd, (LU, perm) trsm,
        # (Li, Ui, perm) inv — every leaf batch-axis-first, batch-sharded
        k = self.key
        spec3, spec2 = _batch_spec(self.mesh, 3), _batch_spec(self.mesh, 2)
        spec4 = _batch_spec(self.mesh, 4)  # (B, nb, bs, bs) dinv stacks
        if k.spd:
            out_shardings = ((spec3, spec4)
                             if k.substitution == "blocked" else (spec3,))
        elif k.substitution == "blocked":
            out_shardings = (spec3, spec4, spec4, spec2)
        elif k.substitution == "inv":
            out_shardings = (spec3, spec3, spec2)
        else:
            out_shardings = (spec3, spec2)
        return jax.jit(fn, out_shardings=out_shardings)

    def _solve_fn(self, nrhs: int):
        """The jitted substitution program for a given RHS-width BUCKET.

        `SolveSession.solve` rounds the request width up to the next
        power of two (pad + slice — columns are independent through every
        substitution/GEMM/IR step, so padded answers are bitwise those of
        the unpadded width), so a traffic mix of widths compiles O(log)
        programs. The bucket contract is asserted here and in
        tests/test_serve.py."""
        if nrhs & (nrhs - 1) or nrhs < 1:
            raise AssertionError(
                f"_solve_fn takes power-of-two RHS buckets, got {nrhs} — "
                "route request widths through SolveSession.solve")

        def build():
            one = self._one_solve
            f = jax.vmap(one) if self.batched else one
            if self.mesh is None:
                return jax.jit(f)
            return jax.jit(f, out_shardings=_batch_spec(self.mesh, 3))

        return self._memo(self._solve_cache, nrhs, build)

    def _check_stack_bucket(self, what: str, ns: int, nrhs: int) -> None:
        if self.batched:
            raise AssertionError(
                "stacked dispatch is for single-system plans — batched "
                "plans already amortize over their own batch axis")
        if ns & (ns - 1) or ns < 1 or nrhs & (nrhs - 1) or nrhs < 1:
            raise AssertionError(
                f"{what} takes power-of-two buckets, got "
                f"({ns}, {nrhs}) — route requests through ServeEngine")

    def _stacked_solve_fn(self, ns: int, nrhs: int):
        """The engine's cross-session program: `ns` sessions of this
        (single-system) plan stack their factor pytrees on a new leading
        axis and ride ONE vmapped substitution dispatch (`ServeEngine`
        with ``stack_sessions=True`` — the gang-resident stacks of
        `conflux_tpu.gang` index their device-resident state straight
        into this program). Bucketed like everything else — power-of-two
        session count and RHS width; the engine pads by repeating a
        session slot / zero columns and slices back. The stacked result
        is allclose to, but not bitwise, the per-session dispatch (XLA
        batches the GEMMs differently under vmap); it IS bitwise
        invariant to the stack bucket size and the pad-slot contents
        (slots never interact), which is the gang's within-a-bucket
        contract for plain sessions."""
        self._check_stack_bucket("_stacked_solve_fn", ns, nrhs)
        return self._memo(self._solve_cache, ("stacked", ns, nrhs),
                          lambda: jax.jit(jax.vmap(self._one_solve)))

    def _stacked_solve_health_fn(self, ns: int, nrhs: int):
        """Checked stacked program — what closes the gang's `checked`
        exclusion hole: (F, A0, wA, b) -> (x, (2, ns) verdict) with the
        §20 Freivalds verdict fused PER SLOT
        (`update.health_spot_check_slots`), so health-guarded sessions
        ride the same one-dispatch stacked path as plain ones and a
        sick slot is attributed without re-dispatching its gang-mates
        (the factor lane's per-slot-flags machinery,
        `resilience.evaluate_slots`). A0 is None for refine-free plans
        (the body never consumes it); wA is the stacked probe rows the
        gang keeps resident."""
        self._check_stack_bucket("_stacked_solve_health_fn", ns, nrhs)
        if self._fused_probe:
            from conflux_tpu.update import health_verdict_from_stats_slots

            def build_fused():
                w = self.probe_w
                body = jax.vmap(self._blocked_probe_body)

                def f(factors, A0, wA, b2):
                    self._bump("health")  # trace-time, not per call
                    x, xsum, wAx = body(factors, wA, b2)
                    return x, health_verdict_from_stats_slots(
                        w, xsum, wAx, b2)

                return jax.jit(f)

            return self._memo(self._trsm_cache,
                              ("gstack_health", ns, nrhs), build_fused)

        def build():
            w = self.probe_w
            body = jax.vmap(self._one_solve)

            def f(factors, A0, wA, b2):
                self._bump("health")  # trace-time, not per call
                x = body(factors, A0, b2)
                return x, health_spot_check_slots(w, wA, x, b2)

            return jax.jit(f)

        return self._memo(self._solve_cache, ("gstack_health", ns, nrhs),
                          build)

    def _stacked_update_solve_fn(self, ns: int, kb: int, nrhs: int,
                                 sweeps: int):
        """Stacked rank-bucketed Woodbury program — what closes the
        gang's `upd_pending` exclusion hole: every slot rides the base
        substitution plus a kb-bucketed capacitance correction
        (`update.woodbury_apply` via `_one_update_solve`), with clean
        slots carrying zero U/V (exactly-zero correction) and drifted
        slots their `pad_update_state`-padded state. A0 is None when
        sweeps == 0. Signature: (F, A0, Up, Vp, Y, Cinv, b) -> x."""
        self._check_stack_bucket("_stacked_update_solve_fn", ns, nrhs)

        def build():
            import functools

            one = functools.partial(self._one_update_solve, sweeps)
            return jax.jit(jax.vmap(one))

        return self._memo(self._update_cache,
                          ("gusolve", ns, kb, nrhs, sweeps), build)

    def _stacked_update_solve_health_fn(self, ns: int, kb: int, nrhs: int,
                                        sweeps: int):
        """Checked stacked Woodbury program: drifted AND health-guarded
        sessions in one dispatch. The per-slot projected residual
        routes through each slot's DRIFTED matrix
        (w^T A1 = wA + (w^T Up) Vp^H; zero-padded columns inert), so
        SMW garbage trips its own slot's verdict only."""
        self._check_stack_bucket("_stacked_update_solve_health_fn",
                                 ns, nrhs)

        def build():
            import functools

            one = functools.partial(self._one_update_solve, sweeps)
            w = self.probe_w
            body = jax.vmap(one)

            def f(factors, A0, Up, Vp, Y, Cinv, wA, b2):
                self._bump("health")  # trace-time, not per call
                x = body(factors, A0, Up, Vp, Y, Cinv, b2)
                return x, health_spot_check_slots(w, wA, x, b2, Up, Vp)

            return jax.jit(f)

        return self._memo(self._update_cache,
                          ("guhealth", ns, kb, nrhs, sweeps), build)

    # ------------------------------------------------------------------ #
    # stacked (cold-start) factor programs — the engine's factor lane
    # ------------------------------------------------------------------ #

    @property
    def _pallas_factor(self) -> bool:
        """True when this plan's stacked factor programs run the factor
        itself through the batch-blocked Pallas kernels
        (`ops.pallas_factor`, DESIGN §29) instead of vmapping
        `_one_factor`: opt-in via `backend='pallas'`, non-mesh plans
        only (the kernel grid owns the batch axis), and f32/f64 with
        `dtype == factor_dtype` (the kernel's verified dtypes; equality
        keeps the in-kernel probe row `wA = w^T A` on the same operand
        `probe_row` would read). Everything about the bucket lifecycle
        and the bitwise bucket/pad-invariance contract is unchanged —
        only the traced factor body differs."""
        k = self.key
        return (k.backend == "pallas" and self.mesh is None
                and k.kind != "qr"  # no batch-grid QR kernel (§29)
                and jnp.dtype(k.dtype) == jnp.dtype(k.factor_dtype)
                and jnp.dtype(k.factor_dtype) in (jnp.float32,
                                                  jnp.float64))

    def _stacked_factor_body(self, Ast, probe: bool = False):
        """The stacked factor computation of XLA-backend plans, shared
        by :meth:`_stacked_factor_fn` and :meth:`_factor_health_fn`:
        (bb,) + key.shape -> stacked factor pytree (plus the (bb, N)
        probe rows wA when `probe`), by vmapping `_one_factor` verbatim
        — bit continuity with every pre-§29 program. `_pallas_factor`
        plans use the core/epilogue pair below instead. Traceable;
        callers jit."""
        w = self.probe_w if probe else None
        one = self._one_factor
        f = jax.vmap(jax.vmap(one)) if self.batched else jax.vmap(one)
        F = f(Ast)
        if not probe:
            return F
        if self.key.kind == "qr":
            # the least-squares probe pair (u, uA) per slot — vmap of a
            # tuple-returning body yields a tuple of stacks (§33)
            from conflux_tpu.update import probe_lstsq

            probe_one = lambda A0: probe_lstsq(w, A0)  # noqa: E731
        else:
            probe_one = lambda A0: probe_row(w, A0)  # noqa: E731
        inner_probe = (jax.vmap(jax.vmap(probe_one))
                       if self.batched else jax.vmap(probe_one))
        return F, inner_probe(Ast)

    def _pallas_factor_core(self, Ast, probe: bool = False):
        """EAGER half of a `_pallas_factor` plan's stacked factor:
        flatten the stack (batched plans fold (bb, B) into one kernel
        batch — pure metadata, `dtype == factor_dtype` is part of the
        eligibility gate so no cast happens here) and dispatch the
        batch-grid kernel (`blas.batched_lu_factor` /
        `batched_cholesky_factor`) as its OWN compiled program. Returns
        (LU, perm[, wA]) / (L[, wA]) stacks. Off-TPU the kernel runs in
        interpret mode — a large inlined XLA graph whose per-slot bits
        are invariant to the kernel batch only when the program boundary
        sits exactly at the kernel wrapper: under a caller's outer jit
        the graph fuses with its consumers differently per bucket size
        and the factor lane's bitwise bucket-invariance contract breaks
        (measured: low-bit LU drift between bucket 1 and 4). So this
        half must NEVER run under a trace — the bucket programs are
        Python closures chaining this dispatch with the jitted
        :meth:`_pallas_factor_epilogue`."""
        k = self.key
        shp = Ast.shape
        A2 = (Ast.reshape((shp[0] * shp[1],) + shp[2:])
              if self.batched else Ast)
        w = self.probe_w if probe else None
        if k.spd:
            out = blas.batched_cholesky_factor(A2, probe_w=w,
                                               backend="pallas")
            return out if probe else (out,)
        return blas.batched_lu_factor(A2, probe_w=w, backend="pallas")

    def _pallas_factor_epilogue(self, core, probe: bool = False):
        """Traceable second half of a `_pallas_factor` plan's stacked
        factor: the substitution epilogue on the kernel's stacked output
        — per-slot diagonal-block inverses for 'blocked' (the §27
        factor-time pass), full triangular inverses for 'inv' — plus
        the (bb, B) unflatten for batched plans. Every epilogue op is
        per-slot exact (vmapped triangular-solve custom calls, triangle
        masking, reshapes), so the kernel's per-slot bitwise invariance
        survives to the session pytrees. Callers jit (one program per
        bucket; `trace_counts['factor']` counts its traces)."""
        from conflux_tpu.ops.batched_trsm import diag_block_inverses

        self.trace_counts["factor"] += 1  # trace-time, not per call
        k = self.key
        cdtype = blas.compute_dtype(jnp.dtype(k.factor_dtype))
        if k.spd:
            L = core[0]
            wA = core[1] if probe else None
            if k.substitution == "blocked":
                dbi = jax.vmap(lambda t: diag_block_inverses(t, lower=True))
                F = (L, dbi(L.astype(cdtype)))
            elif k.substitution == "inv":
                Lc = L.astype(cdtype)
                eye = jnp.broadcast_to(jnp.eye(self.N, dtype=cdtype),
                                       Lc.shape)
                F = (lax.linalg.triangular_solve(
                    Lc, eye, left_side=True, lower=True),)
            else:
                F = (L,)
        else:
            LU, perm = core[0], core[1]
            wA = core[2] if probe else None
            if k.substitution == "blocked":
                LUc = LU.astype(cdtype)
                dbi_l = jax.vmap(lambda t: diag_block_inverses(
                    t, lower=True, unit_diagonal=True))
                dbi_u = jax.vmap(lambda t: diag_block_inverses(
                    t, lower=False))
                F = (LU, dbi_l(LUc), dbi_u(LUc), perm)
            elif k.substitution == "inv":
                LUc = LU.astype(cdtype)
                eye = jnp.broadcast_to(jnp.eye(self.N, dtype=cdtype),
                                       LUc.shape)
                Li = lax.linalg.triangular_solve(
                    LUc, eye, left_side=True, lower=True,
                    unit_diagonal=True)
                Ui = lax.linalg.triangular_solve(
                    LUc, eye, left_side=True, lower=False)
                F = (Li, Ui, perm)
            else:
                F = (LU, perm)

        def unflat(x):
            if not self.batched:
                return x
            B = self.key.shape[0]
            return x.reshape((x.shape[0] // B, B) + x.shape[1:])

        F = tuple(unflat(x) for x in F)
        if not probe:
            return F
        return F, unflat(wA)

    def _stacked_factor_fn(self, bb: int):
        """The factor lane's coalesced cold-start program: `bb` systems
        of this plan stack on a new leading axis — (bb,) + key.shape —
        and factor in ONE dispatch (vmapped `_one_factor`, or the
        batch-grid Pallas kernel for `_pallas_factor` plans — see
        :meth:`_stacked_factor_body`), at power-of-two batch
        buckets so a traffic mix of coalesced sizes compiles O(log)
        programs (pad slots carry identity matrices, well-conditioned by
        construction). Per-slot factors are BITWISE invariant to the
        bucket size and to the pad contents (slots never interact —
        asserted in tests/test_factor_lane.py), which is why
        :meth:`factor` itself rides this program at bucket 1: a session
        opened by `plan.factor` and one opened by a coalesced engine
        dispatch are the same bits. (The UNvmapped factor body differs
        from its vmapped form at rounding level, so routing both paths
        through one program family is what makes the contract hold; the
        Pallas kernel keeps it by flooring its grid batch at 2 slots —
        `ops.pallas_factor._pad_batch_floor`.)"""
        if self.mesh is not None:
            raise AssertionError(
                "the stacked factor program is unsharded — mesh plans "
                "factor through the batch-sharded _factor_fn")
        if bb & (bb - 1) or bb < 1:
            raise AssertionError(
                f"_stacked_factor_fn takes power-of-two batch buckets, "
                f"got {bb} — route requests through ServeEngine")

        def build():
            if not self._pallas_factor:
                return jax.jit(self._stacked_factor_body)
            epi = jax.jit(self._pallas_factor_epilogue)

            def run(Ast):
                return epi(self._pallas_factor_core(Ast))

            return run

        return self._memo(self._factor_cache, ("factor", bb), build)

    def _factor_health_fn(self, bb: int):
        """Checked cold-start program: factor the stack AND produce the
        post-factor health evidence in the SAME dispatch —
        (bb,)+shape A -> (factors, wA, verdict (2, bb)).

        wA[i] = w^T A_i is each session's Freivalds probe row
        (`update.probe_row`), computed here so coalesced sessions open
        with their probe already device-resident (no later lazy probe
        dispatch). The verdict solves A_i x = w through the fresh
        factors — one O(N^2) substitution per system next to the O(N^3)
        factor — and projects the residual through wA; slot i's verdict
        depends only on slot i's matrix, so one sick system can never
        contaminate its co-batched slots' evidence (blast-radius
        isolation at the verdict level). Per-slot reductions run OUTSIDE
        the vmaps as a handful of batched ops (the XLA-CPU fixed-op-cost
        rule, §20).

        `_fused_probe` plans run the probe solve through
        `_blocked_probe_body` — finite/projection accumulators ride the
        back substitution's own block loop (§27), so the verdict costs
        two O(N) dots; `_pallas_factor` plans additionally compute the
        factor AND wA inside the batch-grid kernel
        (`_stacked_factor_body`), making the checked coalesced factor
        one dispatch end to end (§29). All three producers emit the
        same (2, bb) verdict block `resilience.evaluate_slots` reads."""
        if self.mesh is not None:
            raise AssertionError(
                "the checked stacked factor program is unsharded — mesh "
                "plans factor through the batch-sharded _factor_fn")
        if bb & (bb - 1) or bb < 1:
            raise AssertionError(
                f"_factor_health_fn takes power-of-two batch buckets, "
                f"got {bb} — route requests through ServeEngine")

        def build():
            w = self.probe_w
            fused = self._fused_probe
            if self.key.kind == "qr":
                # least-squares verdict: u_i ∈ range(A_i) by
                # construction (`update.probe_lstsq`), so the minimizer
                # of ||A_i x − u_i|| reproduces u_i exactly and the
                # per-slot projected residual |u·u − uA·x| vanishes at
                # the solution — same tripwire scale as the square
                # lane's |w·w − wA·x| (u is normalized to ||u|| = √M).
                # qr plans are single-system, unfused, XLA-backend.
                solve_u = jax.vmap(self._one_solve)

                def check_qr(F, wA, Ast):
                    u_st, uA_st = wA
                    x = solve_u(F, Ast, u_st[..., None])
                    cdtype = x[..., 0].dtype
                    finite = jnp.isfinite(
                        jnp.sum(x, axis=tuple(range(1, x.ndim))))
                    x0 = x[..., 0].astype(cdtype)
                    uc = u_st.astype(cdtype)
                    ax = jnp.sum(uA_st.astype(cdtype) * x0, axis=-1)
                    num = jnp.abs(jnp.sum(uc * uc, axis=-1) - ax)
                    den = (jnp.sqrt(jnp.sum(jnp.abs(uc) ** 2, axis=-1))
                           + jnp.finfo(cdtype).tiny)
                    return jnp.stack([finite.astype(jnp.float32),
                                      (num / den).astype(jnp.float32)])

                def f_qr(Ast):
                    self._bump("factor_health")  # trace-time
                    F, wA = self._stacked_factor_body(Ast, probe=True)
                    return F, wA, check_qr(F, wA, Ast)

                return jax.jit(f_qr)
            if fused:
                # the §27 fused probe epilogue: the probe solve's back
                # substitution accumulates the finite/projection stats
                # in its own block loop, so the verdict costs two O(N)
                # dots instead of a pass over x
                probe_body = jax.vmap(self._blocked_probe_body,
                                      in_axes=(0, 0, None))
                if self.batched:
                    probe_body = jax.vmap(probe_body, in_axes=(0, 0, None))
            else:
                solve_one = jax.vmap(self._one_solve, in_axes=(0, 0, None))
                if self.batched:
                    solve_one = jax.vmap(solve_one, in_axes=(0, 0, None))

            def check(F, wA, Ast):
                # per-slot verdict, batched reductions outside the vmaps:
                # finite flag rides one summation per slot (factor NaNs
                # propagate into x), residual is the probe projection
                # |w.w - wA.x0| / ||w|| per system, max-reduced over the
                # plan's own batch axis for batched plans
                w2 = w[:, None].astype(jnp.dtype(self.key.dtype))
                if fused:
                    _x, xsum, wAx = probe_body(F, wA, w2)
                    cdtype = wAx.dtype
                    fin_acc = (jnp.sum(xsum, axis=-1) if self.batched
                               else xsum)
                    ax = wAx
                else:
                    x = solve_one(F, Ast, w2)
                    cdtype = x[..., 0].dtype
                    fin_acc = jnp.sum(x, axis=tuple(range(1, x.ndim)))
                    x0 = x[..., 0].astype(cdtype)
                    ax = jnp.sum(wA.astype(cdtype) * x0, axis=-1)
                finite = jnp.isfinite(fin_acc)
                wc = w.astype(cdtype)
                num = jnp.abs(jnp.sum(wc * wc) - ax)
                den = (jnp.sqrt(jnp.sum(jnp.abs(wc) ** 2))
                       + jnp.finfo(cdtype).tiny)
                res = num / den
                if self.batched:
                    res = jnp.max(res, axis=-1)
                return jnp.stack([finite.astype(jnp.float32),
                                  res.astype(jnp.float32)])

            if self._pallas_factor:
                # same core/epilogue split as _stacked_factor_fn: the
                # kernel (which already computed wA in-grid) dispatches
                # standalone, and ONE jitted epilogue program builds the
                # substitution pytree + probe solve + verdict
                def epi(Ast, core):
                    self._bump("factor_health")  # trace-time
                    F, wA = self._pallas_factor_epilogue(core, probe=True)
                    return F, wA, check(F, wA, Ast)

                jepi = jax.jit(epi)

                def run(Ast):
                    return jepi(Ast,
                                self._pallas_factor_core(Ast, probe=True))

                return run

            def f(Ast):
                self._bump("factor_health")  # trace-time, not per call
                F, wA = self._stacked_factor_body(Ast, probe=True)
                return F, wA, check(F, wA, Ast)

            return jax.jit(f)

        return self._memo(self._factor_cache, ("factor_health", bb), build)

    def _mesh_factor_health_fn(self):
        """The mesh lane's checked cold-start program: factor ONE
        (B, N, N) batch through the batch-sharded factor body AND
        produce the session's health evidence in the SAME sharded
        dispatch — A -> (factors, wA, verdict (2, 1)).

        The factor body is the same vmapped `_one_factor` that
        `_factor_fn` jits (a mesh `plan.factor` rides `_factor_fn`
        through `_factor_once`), so the engine's checked mesh factor
        and the bare one carry the same bits. wA[i] = w^T A_i is the
        per-system Freivalds probe row ((B, N), batch-sharded) — the
        session opens with its probe device-resident, like the stacked
        lane. The verdict reduces over the plan's OWN batch axis (one
        mesh session is one tenant: max residual, any non-finite slot
        poisons it) into the (2, 1) block `resilience.evaluate_slots`
        reads, so the engine's drain path treats a mesh factor as a
        one-slot batch."""
        if self.mesh is None:
            raise AssertionError(
                "_mesh_factor_health_fn is the mesh lane's checked "
                "factor program — unsharded plans ride "
                "_factor_health_fn")

        def build():
            w = self.probe_w
            fused = self._fused_probe
            if fused:
                probe_body = jax.vmap(self._blocked_probe_body,
                                      in_axes=(0, 0, None))
            else:
                solve_one = jax.vmap(self._one_solve, in_axes=(0, 0, None))
            k = self.key
            spec3 = _batch_spec(self.mesh, 3)
            spec2 = _batch_spec(self.mesh, 2)
            spec4 = _batch_spec(self.mesh, 4)
            if k.spd:
                fac_shard = ((spec3, spec4) if k.substitution == "blocked"
                             else (spec3,))
            elif k.substitution == "blocked":
                fac_shard = (spec3, spec4, spec4, spec2)
            elif k.substitution == "inv":
                fac_shard = (spec3, spec3, spec2)
            else:
                fac_shard = (spec3, spec2)

            def check(F, wA, A):
                w2 = w[:, None].astype(jnp.dtype(k.dtype))
                if fused:
                    _x, xsum, wAx = probe_body(F, wA, w2)
                    cdtype = wAx.dtype
                    fin_acc = jnp.sum(xsum)
                    ax = wAx
                else:
                    x = solve_one(F, A, w2)
                    cdtype = x[..., 0].dtype
                    fin_acc = jnp.sum(x)
                    x0 = x[..., 0].astype(cdtype)
                    ax = jnp.sum(wA.astype(cdtype) * x0, axis=-1)
                finite = jnp.isfinite(fin_acc)
                wc = w.astype(cdtype)
                num = jnp.abs(jnp.sum(wc * wc) - ax)
                den = (jnp.sqrt(jnp.sum(jnp.abs(wc) ** 2))
                       + jnp.finfo(cdtype).tiny)
                res = jnp.max(num / den)
                return jnp.stack([finite.astype(jnp.float32),
                                  res.astype(jnp.float32)])[:, None]

            body = jax.vmap(self._one_factor)
            probe = jax.vmap(lambda A0: probe_row(w, A0))

            def f(A):
                self._bump("factor_health")  # trace-time, not per call
                F = body(A)
                wA = probe(A)
                return F, wA, check(F, wA, A)

            return jax.jit(f, out_shardings=(fac_shard, spec2, None))

        return self._memo(self._factor_cache, ("factor_health_mesh",),
                          build)

    def _factor_once(self, A):
        """Factor ONE system (or one (B, N, N) batch for batched plans)
        through the bucket-1 slot of the stacked factor program —
        `factor()`, `refactor()` and the drift-policy `_refactor` all
        route here, so every session of a non-mesh plan carries factors
        from the SAME program family as the engine's coalesced factor
        lane (bitwise, see :meth:`_stacked_factor_fn`). Mesh plans keep
        the batch-sharded unvmapped program."""
        if self.mesh is not None:
            return self._factor_fn(A)
        F = self._stacked_factor_fn(1)(A[None])
        return unstack_tree(F, 1)[0]

    # ------------------------------------------------------------------ #
    # checked (health-guarded) solve programs — the resilience layer
    # ------------------------------------------------------------------ #

    @property
    def probe_w(self):
        """The plan's fixed Rademacher probe w (`update.probe_vector`):
        one vector per plan size keeps every checked program and every
        session's cached probe row wA = w^T A0 consistent."""
        w = getattr(self, "_probe_w_cache", None)
        if w is None:
            w = jnp.asarray(probe_vector(self.N))
            self._probe_w_cache = w
        return w

    def _probe_fn(self):
        """Jitted wA = w^T A0 program — the once-per-base half of the
        Freivalds-style residual check (`update.probe_row`); sessions
        cache its output next to the factors and invalidate on
        refactor. kind='qr' plans cache the LEAST-SQUARES probe pair
        (u, uA) = `update.probe_lstsq` instead (u in range(A0), so the
        LS residual's orthogonality makes the same projected check
        work — §33)."""
        w = self.probe_w

        def build():
            if self.key.kind == "qr":
                from conflux_tpu.update import probe_lstsq

                one = lambda A0: probe_lstsq(w, A0)  # noqa: E731
            else:
                one = lambda A0: probe_row(w, A0)  # noqa: E731
            f = jax.vmap(one) if self.batched else one
            if self.mesh is None:
                return jax.jit(f)
            return jax.jit(f, out_shardings=_batch_spec(self.mesh, 2))

        return self._memo(self._solve_cache, ("probe",), build)

    def _checked(self, inner):
        """Wrap a per-system (factors, A0, b2) solve body into the
        checked-program shape (factors, A0, wA, b2) -> (x, (2,) verdict).
        The body is vmapped for batched plans; the verdict
        (`update.health_spot_check`) is computed OUTSIDE the vmap on the
        whole batched block — XLA CPU charges fixed per-op overhead next
        to these small dispatches, so the check stays a handful of
        batched reductions, and the clean path pays no extra dispatch
        (the verdict rides the same program as the answer)."""
        w = self.probe_w
        qr = self.key.kind == "qr"
        body = jax.vmap(inner) if self.batched else inner

        def f(factors, A0, wA, b2):
            self._bump("health")  # trace-time, not per call
            x = body(factors, A0, b2)
            if qr:
                # the session's probe is the (u, uA) pair: u ∈ range(A0)
                # is orthogonal to the least-squares residual, so the
                # SAME projected check u·b − uA·x vanishes at min||Ax−b||
                # (§33) — health_spot_check consumes it verbatim
                u, uA = wA
                return x, health_spot_check(u, uA, x, b2)
            return x, health_spot_check(w, wA, x, b2)

        return f

    def _jit_checked(self, f):
        if self.mesh is None:
            return jax.jit(f)
        return jax.jit(f, out_shardings=(_batch_spec(self.mesh, 3),
                                         None))

    @property
    def _fused_probe(self) -> bool:
        """True when this plan's checked programs fuse the Freivalds
        probe epilogue into the blocked back-substitution's final block
        steps (`ops.batched_trsm.blocked_solve_probe`, DESIGN §27) —
        blocked plans without IR sweeps (`refine` re-reads x per sweep,
        so only the refine-free shape has a 'final' block step to fuse
        into). Fused programs live in `_trsm_cache`; everything about
        the bucket lifecycle (`bucket_ready`, `release_buckets`,
        `_warm_devices`) treats the two families uniformly."""
        return self.key.substitution == "blocked" and not self.key.refine

    def _blocked_probe_body(self, factors, wA, b2):
        """Per-system blocked solve with the probe epilogue fused into
        the final (back-substitution) block loop: returns (x, xsum,
        wAx) where the finite accumulator and the probe projection
        accumulate as each x block is produced — no separate verdict
        pass over x (`update.health_verdict_from_stats` assembles the
        (2,) verdict from these plus two O(N) b-side dots). Traceable;
        vmapped for batched plans and the gang's stacked programs."""
        from conflux_tpu.ops.batched_trsm import (
            blocked_solve,
            blocked_solve_probe,
        )

        k = self.key
        cdtype = blas.compute_dtype(jnp.dtype(k.dtype))

        def tile(b):
            if k.spd:
                L, Dl = factors
                Lc = L.astype(Dl.dtype)
                y = blocked_solve(Lc, Dl, b.astype(Dl.dtype), lower=True)
                Du = jnp.swapaxes(Dl.conj(), -1, -2)
                return blocked_solve_probe(
                    Lc.conj().T, Du, y, wA, lower=False, stats_dtype=cdtype)
            LU, Dl, Du, perm = factors
            LUc = LU.astype(Dl.dtype)
            y = blocked_solve(LUc, Dl, b.astype(Dl.dtype)[perm], lower=True)
            return blocked_solve_probe(
                LUc, Du, y, wA, lower=False, stats_dtype=cdtype)

        x, xsum, wAx = _rhs_tiles(tile, b2)
        # column 0, the probe's column, lives in tile 0
        return (_untile(x, b2.shape[-1]).astype(cdtype), jnp.sum(xsum),
                wAx[0])

    def _solve_health_fn(self, nrhs: int):
        """The checked substitution program per RHS bucket — what
        `SolveSession.solve_checked` (and the engine with output guards
        on) dispatches instead of `_solve_fn`. Signature:
        (factors, A0, wA, b2) -> (x, verdict); A0 feeds the plan's
        `refine` sweeps exactly like the plain program's `A`, wA is the
        session's cached probe row."""
        if nrhs & (nrhs - 1) or nrhs < 1:
            raise AssertionError(
                f"_solve_health_fn takes power-of-two RHS buckets, got "
                f"{nrhs} — route request widths through solve_checked")
        if self._fused_probe:
            from conflux_tpu.update import health_verdict_from_stats

            def build():
                w = self.probe_w
                body = (jax.vmap(self._blocked_probe_body)
                        if self.batched else self._blocked_probe_body)

                def f(factors, A0, wA, b2):
                    self._bump("health")  # trace-time, not per call
                    x, xsum, wAx = body(factors, wA, b2)
                    return x, health_verdict_from_stats(w, xsum, wAx, b2)

                return self._jit_checked(f)

            return self._memo(self._trsm_cache, ("health", nrhs), build)
        return self._memo(
            self._solve_cache, ("health", nrhs),
            lambda: self._jit_checked(self._checked(self._one_solve)))

    def _update_solve_health_fn(self, kb: int, nrhs: int, sweeps: int):
        """Checked Woodbury solve program: the projected residual routes
        through the DRIFTED matrix (w^T A1 = wA + (w^T Up) Vp^H, padded
        columns inert), so SMW garbage from an ill-conditioned
        capacitance trips the verdict."""
        def build():
            import functools

            one = functools.partial(self._one_update_solve, sweeps)
            w = self.probe_w
            body = jax.vmap(one) if self.batched else one

            def f(factors, A0, Up, Vp, Y, Cinv, wA, b2):
                self._bump("health")  # trace-time, not per call
                x = body(factors, A0, Up, Vp, Y, Cinv, b2)
                return x, health_spot_check(w, wA, x, b2, Up, Vp)

            return self._jit_checked(f)

        return self._memo(self._update_cache,
                          ("uhealth", kb, nrhs, sweeps), build)

    def _one_refine(self, factors, A0, x, b2):
        """One iterative-refinement sweep against the CURRENT base
        factors — escalation rung 2's body (the forced refactor of rung
        1 already absorbed any drift, so the TRUE residual matvec runs
        against A0; only the re-check verdict rides the probe)."""
        self._bump("refine")
        corr = self._base_corr(factors)
        cdtype = blas.compute_dtype(jnp.dtype(self.key.dtype))

        def tile(x, b):
            xc = x.astype(cdtype)
            r = (b.astype(cdtype)
                 - jnp.matmul(A0.astype(cdtype), xc,
                              precision=lax.Precision.HIGHEST))
            return xc + corr(r).astype(cdtype)

        return _untile(_rhs_tiles(tile, x, b2), b2.shape[-1])

    def _refine_fn(self, nrhs: int):
        def build():
            w = self.probe_w
            qr = self.key.kind == "qr"
            one = self._one_refine
            body = jax.vmap(one) if self.batched else one

            def f(factors, A0, wA, x, b2):
                x2 = body(factors, A0, x, b2)
                if qr:
                    u, uA = wA
                    return x2, health_spot_check(u, uA, x2, b2)
                return x2, health_spot_check(w, wA, x2, b2)

            return self._jit_checked(f)

        return self._memo(self._solve_cache, ("refine", nrhs), build)

    # ------------------------------------------------------------------ #
    # served precision tiers — the per-request ladder (DESIGN §33)
    # ------------------------------------------------------------------ #

    def _tier_spec(self, tier: str):
        """(factor dtype, fused IR sweep count) for a served tier.
        'bf16_ir' factors in bfloat16 — half the resident factor bytes —
        and always fuses at least one refinement sweep (the IR half of
        the name; the residual matvec runs against the f32 base, so one
        sweep recovers working-precision accuracy for well-conditioned
        systems, §15). 'f32'/'f64' factor at that storage dtype with the
        plan's own sweep count; 'f64' canonicalizes to f32 when x64 is
        off (same programs, documented in TUNING)."""
        if tier not in PRECISION_TIERS:
            raise ValueError(
                f"unknown served tier {tier!r} — one of {PRECISION_TIERS}")
        if tier == "bf16_ir":
            return jnp.dtype(jnp.bfloat16), max(int(self.key.refine), 1)
        if tier == "f32":
            return jnp.dtype(jnp.float32), int(self.key.refine)
        return (jnp.dtype(jax.dtypes.canonicalize_dtype(jnp.float64)),
                int(self.key.refine))

    def _check_tier(self, what: str, tier: str) -> None:
        if tier not in PRECISION_TIERS:
            raise ValueError(
                f"{what} takes a served tier from {PRECISION_TIERS}, "
                f"got {tier!r}")
        if self.mesh is not None:
            raise AssertionError(
                "mesh-sharded plans serve their native precision only — "
                "per-request tiers are validated away at submit "
                "(engine._prepare)")

    def _tier_stacked_factor_fn(self, tier: str, bb: int):
        """The served tiers' coalesced factor program: `bb` systems
        factor at the TIER's dtype in one dispatch — the
        `("tier_factor", tier, bb)` family next to the native
        `("factor", bb)` one, same power-of-two buckets, same per-slot
        bitwise bucket/pad-invariance (vmapped `_one_factor` with the
        dtype override; always the XLA body — the §29 Pallas kernels
        carry no bf16 grid, and tier traffic is routed, not default)."""
        self._check_tier("_tier_stacked_factor_fn", tier)
        if bb & (bb - 1) or bb < 1:
            raise AssertionError(
                f"_tier_stacked_factor_fn takes power-of-two batch "
                f"buckets, got {bb} — route requests through ServeEngine")

        def build():
            fd, _ = self._tier_spec(tier)
            one = lambda A: self._one_factor(A, fdtype=fd)  # noqa: E731
            f = jax.vmap(jax.vmap(one)) if self.batched else jax.vmap(one)
            return jax.jit(f)

        return self._memo(self._factor_cache, ("tier_factor", tier, bb),
                          build)

    def _tier_factor_once(self, tier: str, A):
        """Factor ONE system at a served tier through the bucket-1 slot
        of the tier's stacked program — `factor(precision=...)`, the
        cross-tier derived cache (`SolveSession._tier_factor`), and the
        tier-aware revive path all route here, mirroring
        :meth:`_factor_once`'s one-program-family contract."""
        F = self._tier_stacked_factor_fn(tier, 1)(A[None])
        return unstack_tree(F, 1)[0]

    def _tier_solve_fn(self, tier: str, nrhs: int):
        """The served tiers' substitution program per RHS bucket: the
        tier's factors + the tier's fused sweep count against the f32
        base — the `("tier", tier, nrhs)` family in `_solve_cache`,
        warmed/retired through the same `bucket_ready`/`release_buckets`
        lifecycle as the native width buckets. Signature
        (factors, A0, b2) -> x; A0 is always consumed (bf16_ir fuses at
        least one residual sweep)."""
        self._check_tier("_tier_solve_fn", tier)
        if nrhs & (nrhs - 1) or nrhs < 1:
            raise AssertionError(
                f"_tier_solve_fn takes power-of-two RHS buckets, got "
                f"{nrhs} — route request widths through SolveSession.solve")
        _, sweeps = self._tier_spec(tier)

        def build():
            def one(factors, A0, b2):
                return self._one_solve(factors, A0, b2, sweeps=sweeps)

            f = jax.vmap(one) if self.batched else one
            return jax.jit(f)

        return self._memo(self._solve_cache, ("tier", tier, nrhs), build)

    def _tier_solve_health_fn(self, tier: str, nrhs: int):
        """Checked tier substitution per RHS bucket — what 'auto'
        requests dispatch (the verdict IS the ladder's escalation
        signal) and what explicit-tier requests ride under engine
        output guards. Always the unfused `_checked` shape — the §27
        fused-probe epilogue belongs to the native blocked family; the
        tiers keep one program shape across substitution modes."""
        self._check_tier("_tier_solve_health_fn", tier)
        if nrhs & (nrhs - 1) or nrhs < 1:
            raise AssertionError(
                f"_tier_solve_health_fn takes power-of-two RHS buckets, "
                f"got {nrhs} — route widths through solve_checked")
        _, sweeps = self._tier_spec(tier)

        def build():
            def one(factors, A0, b2):
                return self._one_solve(factors, A0, b2, sweeps=sweeps)

            return jax.jit(self._checked(one))

        return self._memo(self._solve_cache, ("tier_health", tier, nrhs),
                          build)

    # ------------------------------------------------------------------ #
    # incremental (Woodbury) update programs — compiled once per bucket
    # ------------------------------------------------------------------ #

    def _bump(self, name: str) -> None:
        """Trace-time counter for the update-path programs: keys appear
        lazily so plans that never update keep the original
        {'factor', 'solve'} counter shape."""
        self.trace_counts[name] = self.trace_counts.get(name, 0) + 1

    def _one_update(self, factors, Up, Vp):
        self._bump("update")
        Y, Cinv, cond1 = capacitance(self._base_corr(factors), Up, Vp)
        return Y, Cinv, cond1

    def _one_update_solve(self, sweeps, factors, A0, Up, Vp, Y, Cinv, b2):
        """Woodbury-corrected substitution + `sweeps` IR backstop sweeps
        against the DRIFTED matrix (A0 x + U (V^H x) residual matvec,
        the serve layer's refinement-loop discipline)."""
        self._bump("update_solve")
        corr = self._base_corr(factors)
        cdtype = blas.compute_dtype(jnp.dtype(self.key.dtype))

        def tile(b):
            x = woodbury_apply(corr, Y, Cinv, Vp, b).astype(cdtype)
            bc = b.astype(cdtype)
            for _ in range(sweeps):
                r = bc - updated_matvec(A0, Up, Vp, x)
                x = x + woodbury_apply(corr, Y, Cinv, Vp, r).astype(cdtype)
            return x

        return _untile(_rhs_tiles(tile, b2), b2.shape[-1])

    def _update_fn(self, kb: int):
        """Jitted capacitance-assembly program per rank bucket kb:
        (factors, Up, Vp) -> (Y, Cinv, cond1)."""
        def build():
            f = jax.vmap(self._one_update) if self.batched \
                else self._one_update
            if self.mesh is None:
                return jax.jit(f)
            return jax.jit(f, out_shardings=(
                _batch_spec(self.mesh, 3), _batch_spec(self.mesh, 3),
                _batch_spec(self.mesh, 1)))

        return self._memo(self._update_cache, ("update", kb), build)

    def _update_solve_fn(self, kb: int, nrhs: int, sweeps: int):
        """Jitted Woodbury solve program per (rank bucket, RHS bucket,
        backstop sweeps)."""
        def build():
            import functools

            one = functools.partial(self._one_update_solve, sweeps)
            f = jax.vmap(one) if self.batched else one
            if self.mesh is None:
                return jax.jit(f)
            return jax.jit(f, out_shardings=_batch_spec(self.mesh, 3))

        return self._memo(self._update_cache, ("usolve", kb, nrhs, sweeps),
                          build)

    def _refresh_fn(self, kb: int, donate: bool = False):
        """Jitted A0 + U V^H materialization per rank bucket — the
        refactor trigger's input, feeding the existing factor program.

        `donate=True` hands the superseded A0 buffer to XLA (the output
        replaces it), so a long-lived drifting session holds ONE resident
        base matrix at the refactor peak instead of two. Only safe when
        the session owns A0 — i.e. it came from a previous refactor, not
        from the caller, who may still hold the array — so the session
        tracks ownership and the donating and non-donating programs cache
        separately."""
        from conflux_tpu.update import apply_update

        def build():
            f = jax.vmap(apply_update) if self.batched else apply_update
            donate_argnums = (0,) if donate else ()
            if self.mesh is None:
                return jax.jit(f, donate_argnums=donate_argnums)
            return jax.jit(f, out_shardings=_batch_spec(self.mesh, 3),
                           donate_argnums=donate_argnums)

        return self._memo(self._update_cache, ("refresh", kb, donate),
                          build)

    # ------------------------------------------------------------------ #
    # serving surface
    # ------------------------------------------------------------------ #

    def _check_A(self, A):
        want = self.key.shape
        if tuple(A.shape) != want:
            raise ValueError(f"A shape {A.shape} does not match the plan's "
                             f"{want}")
        if A.dtype != jnp.dtype(self.key.dtype):
            raise ValueError(f"A dtype {A.dtype} does not match the plan's "
                             f"{self.key.dtype}")

    def factor(self, A, *, policy: DriftPolicy | None = None,
               device=None, sid=None,
               precision: str | None = None) -> "SolveSession":
        """Run the factor program on A and open a device-resident session.

        The returned session holds the factors (and A itself — the
        refinement residual matvec and the incremental-update/refactor
        path both consume it) on device; every `session.solve` afterwards
        is substitution-only. `policy` governs when `session.update`
        drifts trigger a true refactorization (default
        :class:`DriftPolicy`).

        `device` pins the session to one device of the serve fleet: A is
        committed there before factoring, so the factors (and every
        later substitution) live and run on that device — the mesh-
        sharded engine's per-lane placement (DESIGN §25). None keeps the
        default device (byte-identical to the pre-fleet behavior).
        `sid` is an optional STABLE session id; the engine's consistent-
        hash placement (`engine.place_session`) maps equal sids to equal
        devices across engine restarts. For mesh plans a `device` INSIDE
        the plan's mesh is a placement no-op (the state is batch-sharded
        across the whole mesh already — the session stays unpinned);
        a device outside the mesh is refused, since sharded state
        cannot migrate off its mesh.

        `precision` opens the session AT a served tier (DESIGN §33):
        the factors are built at that tier's dtype directly — a
        bf16-tier session never pays the f32 factorization — and
        subsequent solves default to the tier's program family.
        'auto' opens on the cheapest rung (bf16+IR) with the session's
        sticky escalation rung at 0. None (the default) is the native
        path, bitwise-identical to pre-§33 behavior. Mesh-sharded
        plans serve native precision only.
        """
        tier0 = check_precision_request(precision)
        if tier0 is not None and self.mesh is not None:
            raise ValueError(
                "mesh-sharded plans serve their native precision only — "
                "precision= does not compose with mesh plans (§33)")
        if tier0 == "auto":
            tier0 = PRECISION_TIERS[0]
        if device is not None and self.mesh is not None:
            if not any(device == d for d in self.mesh.devices.flat):
                from conflux_tpu.resilience import MeshPlanUnsupported

                raise MeshPlanUnsupported(
                    "device= names a device outside this plan's mesh — "
                    "a mesh-sharded session's state cannot migrate off "
                    "its mesh", surface="factor")
            device = None  # in-mesh pin: state already spans the mesh
        A = jnp.asarray(A)
        self._check_A(A)
        if self.mesh is not None:
            (A,) = _shard_batch((A,), self.mesh)
        elif device is not None:
            A = jax.device_put(A, device)
        with profiler.region("serve.factor"):
            factors = (self._factor_once(A) if tier0 is None
                       else self._tier_factor_once(tier0, A))
        # tier sessions always retain the base — their solve programs
        # fuse residual sweeps against A0 (bf16_ir at minimum one)
        keep_A = A if (self.key.refine or tier0 is not None) else None
        return SolveSession(self, factors, keep_A, A, policy,
                            device=device, sid=sid, served_tier=tier0)

    def solve(self, A, b):
        """One-shot convenience: factor + solve in one call (a fresh
        session per call — serving code should hold the session)."""
        return self.factor(A).solve(b)


class SolveSession:
    """Device-resident factors + the compiled substitution program.

    Sessions are cheap handles: the heavy state lives on device. `solves`
    and `factorizations` count what this session actually ran — the
    serving invariant (`factorizations == 1` under solve-only traffic,
    `solves` growing) is asserted by tests/test_serve.py.

    `update(U, V)` applies a rank-k drift A <- A + U V^H WITHOUT
    refactoring: subsequent solves ride the base factors plus a k x k
    capacitance correction (Sherman-Morrison-Woodbury, see
    `conflux_tpu.update`), all device-resident and compiled once per
    (rank bucket, RHS bucket). The session's :class:`DriftPolicy` decides
    when accumulated rank/conditioning stops paying and triggers ONE true
    refactorization through the plan's existing factor program
    (`factorizations`/`refactors` record it).
    """

    def __init__(self, plan: FactorPlan, factors, A, A_base=None,
                 policy: DriftPolicy | None = None, *,
                 device=None, sid=None, served_tier=None,
                 auto_rung: int = 0):
        self.plan = plan
        # fleet placement (DESIGN §25): the device this session's state
        # lives on (None = default device — the pre-fleet behavior,
        # byte-identical) and an optional STABLE id the engine's
        # consistent-hash placement keys on. Both write-once-ish: the
        # engine pins an unplaced session at first submit (under the
        # session lock) and never re-pins a placed one.
        self.device = device
        self.sid = sid
        # resilience + concurrency state: every factor/drift mutation
        # and every read of the resident state happens under this
        # re-entrant lock (conflint CFX-LOCK enforces the guarded-by
        # annotations below) — a drain-thread escalation's factor swap
        # (`self._factors = None` then the fresh dispatch) is atomic
        # against any dispatcher or direct-caller solve. The RLock
        # makes the engine's outer hold (`_solve_session`) and the
        # escalation ladder's (`resilience.escalate`) re-enter cleanly.
        self._lock = threading.RLock()
        self._factors = factors    # guarded-by: _lock
        self._A = A                # guarded-by: _lock
        self._A0 = A if A_base is None else A_base  # guarded-by: _lock
        self.policy = DriftPolicy() if policy is None else policy
        self._upd = None  # guarded-by: _lock — dict(k, kb, Up, Vp, Y, Cinv)
        # the base matrix is the CALLER's array until the first refactor
        # replaces it with an engine-built one; only owned bases may be
        # donated to the refresh program (see FactorPlan._refresh_fn)
        self._owns_base = False    # guarded-by: _lock
        # the breaker is attached lazily by resilience.breaker_for
        # (write-once under its own attach lock); last_cond is the
        # latest capacitance condition estimate — SolveUnhealthy
        # evidence
        self._breaker = None
        self.last_cond = None      # guarded-by: _lock
        # wA = w^T A0, the once-per-base half of the projected-residual
        # check — computed lazily on the first checked solve, dropped
        # whenever a refactor replaces the base
        self._probe = None         # guarded-by: _lock
        # served precision tier (DESIGN §33): `_served_tier` names the
        # tier the resident `_factors` were built at (None = the plan's
        # native factor dtype — bitwise the pre-ladder behavior);
        # `_auto_rung` is the sticky 'auto' ladder position (escalations
        # ratchet it up, so a session that needed f32 once starts there
        # next time); `_tier_factors` is the DERIVED per-tier factor
        # cache for cross-tier requests — rebuildable from `_A0`, so it
        # is excluded from nbytes, spill records and checkpoints, and
        # cleared on every base swap / device move / spill
        self._served_tier = served_tier  # guarded-by: _lock
        self._auto_rung = int(auto_rung)  # guarded-by: _lock
        self._tier_factors: dict = {}  # guarded-by: _lock
        self.precision_escalations = 0  # guarded-by: _lock
        self.precision_fallbacks = 0  # guarded-by: _lock
        self.factorizations = 1    # guarded-by: _lock
        self.solves = 0            # guarded-by: _lock
        self.updates = 0           # guarded-by: _lock
        self.refactors = 0         # guarded-by: _lock
        # tiered residency (conflux_tpu.tier): `_residency` is the
        # managing ResidentSet (write-once at adopt; None = untiered,
        # zero behavioral change), `_spill` holds the spill record
        # while the session's state lives off-device — every
        # state-touching method faults it back in first
        # (`_ensure_resident`, under this same lock). `_tier_stamp` is
        # the LRU clock: a single int write per touch, read only by the
        # manager's eviction scan — benign staleness by design
        self._residency = None
        self._spill = None         # guarded-by: _lock
        self._tier_stamp = 0
        # gang residency (conflux_tpu.gang, DESIGN §26): `_gang` is the
        # SessionGang holding this session's stacked slot (None =
        # unganged, zero behavioral change), `_gang_slot` its slot
        # index. Both are written by the gang under ITS protocol (the
        # gang lock orders after this session lock, so they are plain
        # attribute writes here — racy reads tolerated by design).
        # `_gang_ver` is the write-back sync: every state mutation
        # below bumps it under this lock, and the engine's dispatcher
        # re-syncs a stale slot before the next stacked dispatch —
        # write-back is LAZY, so no mutation path ever needs the gang
        # lock while holding this one.
        self._gang = None
        self._gang_slot = None
        self._gang_ver = 0         # guarded-by: _lock
        # checkpoint dirty clock (DESIGN §35): bumped by every mutation
        # that changes what `tier.save_fleet` would persist (update /
        # refactor / device move / precision escalation / adopt).
        # Solve-only traffic leaves it untouched, so the incremental
        # checkpointer can skip clean sessions. Counters that only
        # solves advance (solve/residual tallies) lag in carried
        # records by design — they are observability, not state.
        self._ckpt_ver = 0         # guarded-by: _lock

    @property
    def factors(self):
        """The device-resident factor pytree: (LU, perm) / (L,) for
        'trsm' plans, (Li, Ui, perm) / (Li,) triangular inverses for
        'inv' plans."""
        with self._lock:
            return self._factors

    @property
    def served_tier(self):
        """The served precision tier the resident factors carry (None =
        the plan's native factor dtype)."""
        with self._lock:
            return self._served_tier

    @property
    def auto_rung(self) -> int:
        """The sticky 'auto' ladder position (index into
        `PRECISION_TIERS`) — escalations ratchet it up."""
        with self._lock:
            return self._auto_rung

    @property
    def update_rank(self) -> int:
        """Accumulated drift rank since the last (re)factorization."""
        with self._lock:
            if self._spill is not None and self._spill.meta:
                # spilled: report the record's drift rank without the
                # cost of faulting the session back in
                u = self._spill.meta.get("upd")
                return 0 if u is None else u["k"]
            return 0 if self._upd is None else self._upd["k"]

    # ------------------------------------------------------------------ #
    # tiered residency (conflux_tpu.tier)
    # ------------------------------------------------------------------ #

    # requires-lock: _lock
    def _ensure_resident(self) -> None:
        """Fault a spilled session back in and stamp the LRU clock —
        the transparent-revival hook every state-touching method runs
        first, under the session RLock (so a request never observes a
        half-restored factor pytree). Untiered sessions pay two
        attribute reads."""
        if self._spill is not None:
            if self._residency is None:
                from conflux_tpu.resilience import SessionSpilled

                raise SessionSpilled(
                    "session is spilled but no ResidentSet manages it "
                    "(the manager detached or the record was grafted) — "
                    "revive through ResidentSet.fault_in")
            self._residency.fault_in(self)
        rs = self._residency
        if rs is not None:
            self._tier_stamp = rs._tick()

    @property
    def tier(self) -> str:
        """'device' (resident), 'host' or 'disk' (spilled), or
        'corrupt' (a spill record that failed its integrity check —
        permanently failed, see `resilience.RestoreCorrupt`)."""
        with self._lock:
            return "device" if self._spill is None else self._spill.tier

    @property
    def nbytes(self) -> int:
        """Device-resident footprint in bytes: factors + base matrix +
        Woodbury correction state + the cached probe row, deduplicated
        by buffer identity (`_A` aliases `_A0` whenever the plan keeps
        it). 0 while spilled — the spill record accounts its own
        host/disk bytes. The byte-bounded tier policy
        (`tier.ResidentSet(max_bytes=...)`) and `engine.stats()` read
        this."""
        with self._lock:
            seen: dict[int, int] = {}
            # tree_leaves: the probe is a (u, uA) TUPLE for kind='qr'
            # plans; `_tier_factors` is derived state (rebuildable from
            # _A0) and deliberately unaccounted
            leaves = jax.tree_util.tree_leaves(
                (self._factors, self._A, self._A0, self._probe))
            if self._upd is not None:
                leaves += [self._upd[k] for k in
                           ("Up", "Vp", "Y", "Cinv")]
            for leaf in leaves:
                if leaf is not None:
                    seen[id(leaf)] = int(leaf.nbytes)
            return sum(seen.values())

    def to_device(self, device) -> "SolveSession":
        """Move the session's resident state to `device` and pin it
        there — the engine's placement hook (a not-yet-placed session
        submitted to a mesh-sharded fleet lands on its consistent-hash
        lane through this). One `jax.device_put` per UNIQUE buffer
        (`batched.put_tree` preserves the `_A is _A0` alias, so the
        byte accounting stays deduplicated); `device=None` or an
        already-there session is a no-op. Runs under the session RLock
        — a concurrent solve never observes half-moved state. For mesh
        plans a device INSIDE the mesh is a no-op (the state already
        spans the mesh — the session stays unpinned); a device outside
        the mesh is refused, the genuine cross-device-migration
        residue (DESIGN §32)."""
        if device is None:
            return self
        if self.plan.mesh is not None:
            if any(device == d for d in self.plan.mesh.devices.flat):
                return self
            from conflux_tpu.resilience import MeshPlanUnsupported

            raise MeshPlanUnsupported(
                "a mesh-sharded session's state is batch-sharded "
                "across the whole mesh — it cannot move off its mesh "
                "to one device", surface="to_device")
        with self._lock:
            self._ensure_resident()
            moved = put_tree(
                {"f": self._factors, "A": self._A, "A0": self._A0,
                 "probe": self._probe,
                 "upd": (None if self._upd is None else
                         {k: self._upd[k]
                          for k in ("Up", "Vp", "Y", "Cinv")})},
                device)
            self._factors = moved["f"]
            self._A = moved["A"]
            self._A0 = moved["A0"]
            self._probe = moved["probe"]
            self._tier_factors = {}  # derived state stays device-local
            if self._upd is not None:
                self._upd = {**self._upd, **moved["upd"]}
            self.device = device
            self._gang_ver += 1
            self._ckpt_ver += 1
            if self._gang is not None:
                # the gang's stack lives on the OLD device — leave it
                # (release requires this held session lock; the session
                # re-adopts into its new lane's gang at next dispatch)
                self._gang.release(self)
        return self

    def _rhs(self, b):
        plan = self.plan
        b = jnp.asarray(b)
        if plan.batched:
            if b.ndim == 2:
                want = (plan.B, plan.N)
                if b.shape != want:
                    raise ValueError(f"rhs {b.shape}, session needs {want}")
                return b[:, :, None], True
            want = (plan.B, plan.N)
            if b.ndim != 3 or b.shape[:2] != want:
                raise ValueError(
                    f"rhs {b.shape}, session needs {want} (+ rhs axis)")
            return b, False
        if b.ndim == 1:
            if b.shape[0] != plan.M:
                raise ValueError(f"rhs {b.shape}, session needs ({plan.M},)")
            return b[:, None], True
        if b.ndim != 2 or b.shape[0] != plan.M:
            raise ValueError(f"rhs {b.shape}, session needs ({plan.M}, k)")
        return b, False

    # requires-lock: _lock
    def _resolve_tier(self, precision):
        """Resolve a per-request ``precision=`` to a served tier (or
        None = the native program family). None defers to the tier the
        session was OPENED at (`_served_tier` — so a bf16-tier session's
        plain solves ride its own factors); 'auto' reads the sticky
        ladder rung. Drifted sessions (`_upd` set) fall back to their
        resident (Woodbury-corrected) path for CROSS-tier requests —
        a derived-tier factor set carries no drift state, so routing
        there would answer against the un-drifted base; the fallback is
        counted (`precision_fallbacks`), never an error."""
        tier = check_precision_request(precision)
        if tier is None:
            return self._served_tier
        if tier == "auto":
            tier = PRECISION_TIERS[
                min(self._auto_rung, len(PRECISION_TIERS) - 1)]
        if self._upd is not None and tier != self._served_tier:
            self.precision_fallbacks += 1
            return self._served_tier
        return tier

    # requires-lock: _lock
    def _tier_factor(self, tier):
        """The derived per-tier factor cache: factors of `_A0` at a
        tier OTHER than the session's served one, built lazily through
        the plan's tier factor family and dropped on any base swap."""
        F = self._tier_factors.get(tier)
        if F is None:
            F = self.plan._tier_factor_once(tier, self._A0)
            self._tier_factors[tier] = F
        return F

    # requires-lock: _lock
    def _factor_base(self, A):
        """(Re)build the session's RESIDENT factors from base `A` at
        the session's serving configuration — the native program family
        for untier'd sessions, the served tier's for tier'd ones. Every
        refactor path routes here so a bf16-tier session never silently
        reverts to f32 factors."""
        if self._served_tier is None:
            return self.plan._factor_once(A)
        return self.plan._tier_factor_once(self._served_tier, A)

    def solve(self, b, *, precision=None):  # hot-path
        """Solve against the resident factors: O(N^2) substitution plus
        the plan's `refine` sweeps (plus the Woodbury correction when the
        session carries an un-refactored drift). b is (N,)/(N, k) for
        single plans, (B, N)/(B, N, k) for batched ones ((M,)/(M, k)
        for kind='qr' least-squares plans — x comes back with N rows);
        otherwise x comes back in b's shape. RHS widths are padded up to
        power-of-two buckets and sliced back, so a width mix compiles
        O(log) programs. The dispatch rides the session lock
        (uncontended RLock, ~100ns) so a concurrent drift update or
        escalation refactor can never show this solve half-swapped
        factors.

        `precision` routes THIS request through a served tier's program
        family (§33): None keeps the session's own serving config
        (bitwise pre-§33 for native sessions), a tier name dispatches
        that tier (factors derived lazily when it isn't the session's
        own), 'auto' starts at the session's sticky rung."""
        plan = self.plan
        b2, squeeze = self._rhs(b)
        nrhs = b2.shape[-1]
        nb = rank_bucket(nrhs)
        if nb != nrhs:
            pad = [(0, 0)] * (b2.ndim - 1) + [(0, nb - nrhs)]
            b2 = jnp.pad(b2, pad)
        if plan.mesh is not None:
            (b2,) = _shard_batch((b2,), plan.mesh)
        with self._lock:
            self._ensure_resident()
            tier = self._resolve_tier(precision)
            with profiler.region("serve.solve"):
                if self._upd is not None:
                    u = self._upd
                    sweeps = plan.key.refine + self.policy.refine
                    x = plan._update_solve_fn(u["kb"], nb, sweeps)(
                        self._factors, self._A0, u["Up"], u["Vp"],
                        u["Y"], u["Cinv"], b2)
                elif tier is None:
                    x = plan._solve_fn(nb)(self._factors, self._A, b2)
                else:
                    F = (self._factors if tier == self._served_tier
                         else self._tier_factor(tier))
                    x = plan._tier_solve_fn(tier, nb)(F, self._A0, b2)
            self.solves += 1
        if nb != nrhs:
            x = x[..., :nrhs]
        if squeeze:
            return x[..., 0]
        return x

    # ------------------------------------------------------------------ #
    # checked solves + escalation rungs (the resilience layer's surface)
    # ------------------------------------------------------------------ #

    def _rhs_bucketed(self, b):
        plan = self.plan
        b2, squeeze = self._rhs(b)
        nrhs = b2.shape[-1]
        nb = rank_bucket(nrhs)
        if nb != nrhs:
            pad = [(0, 0)] * (b2.ndim - 1) + [(0, nb - nrhs)]
            b2 = jnp.pad(b2, pad)
        if plan.mesh is not None:
            (b2,) = _shard_batch((b2,), plan.mesh)
        return b2, nb, nrhs, squeeze

    def _probe_row(self):
        """The session's cached probe row wA = w^T A0 (device-resident,
        like the factors; O(N^2) once per base, invalidated by
        refactors)."""
        with self._lock:
            self._ensure_resident()
            if self._probe is None:
                self._probe = self.plan._probe_fn()(self._A0)
            return self._probe

    def solve_checked(self, b, *, precision=None):  # hot-path
        """`solve` plus the fused finite/projected-residual health
        verdict, in the SAME dispatched program. Returns (x, verdict)
        with verdict a (2,) float32 device array
        [finite_flag, residual] — nothing here blocks; the engine's
        drain thread (or `resilience.evaluate`) reads the verdict with
        the answer. The answer keeps `solve`'s shape contract (bucket
        pad + slice, squeeze)."""
        plan = self.plan
        b2, nb, nrhs, squeeze = self._rhs_bucketed(b)
        with self._lock:
            self._ensure_resident()
            tier = self._resolve_tier(precision)
            wA = self._probe_row()
            with profiler.region("serve.solve"):
                if self._upd is not None:
                    u = self._upd
                    sweeps = plan.key.refine + self.policy.refine
                    x, verdict = plan._update_solve_health_fn(
                        u["kb"], nb, sweeps)(
                        self._factors, self._A0, u["Up"], u["Vp"],
                        u["Y"], u["Cinv"], wA, b2)
                elif tier is None:
                    x, verdict = plan._solve_health_fn(nb)(
                        self._factors, self._A0, wA, b2)
                else:
                    F = (self._factors if tier == self._served_tier
                         else self._tier_factor(tier))
                    x, verdict = plan._tier_solve_health_fn(tier, nb)(
                        F, self._A0, wA, b2)
            self.solves += 1
        if nb != nrhs:
            x = x[..., :nrhs]
        if squeeze:
            x = x[..., 0]
        return x, verdict

    def refine_checked(self, b, x):
        """One iterative-refinement sweep of a previous answer `x`
        against the CURRENT base factors, re-checked — escalation rung 2
        (`resilience.escalate`). `b` and `x` carry the same (bucketed)
        solve shapes; sessions with un-refactored drift must refactor
        first (rung 1 always precedes this one)."""
        plan = self.plan
        b2, nb, nrhs, squeeze = self._rhs_bucketed(b)
        x2 = jnp.asarray(x)
        if squeeze:
            x2 = x2[..., None]
        if nb != nrhs:
            pad = [(0, 0)] * (x2.ndim - 1) + [(0, nb - nrhs)]
            x2 = jnp.pad(x2, pad)
        if plan.mesh is not None:
            (x2,) = _shard_batch((x2,), plan.mesh)
        with self._lock:
            self._ensure_resident()
            if self._upd is not None:
                raise AssertionError(
                    "refine_checked rides the base factors — refactor() "
                    "the drifted session first (escalation rung order)")
            with profiler.region("serve.solve"):
                x2, verdict = plan._refine_fn(nb)(
                    self._factors, self._A0, self._probe_row(), x2, b2)
        if nb != nrhs:
            x2 = x2[..., :nrhs]
        if squeeze:
            x2 = x2[..., 0]
        return x2, verdict

    def refactor(self):
        """Force one true refactorization through the plan's CACHED
        factor program — escalation rung 1. Absorbs any accumulated
        drift into a fresh base (the `_refactor` path, donation and
        all); an un-drifted session re-runs the factor program on its
        resident base, replacing possibly-corrupt factors. Chainable."""
        with self._lock:
            self._ensure_resident()
            if self._upd is not None:
                u = self._upd
                k = u["k"]
                self._refactor(u["Up"][..., :k], u["Vp"][..., :k])
                return self
            with profiler.region("serve.refactor"):
                from conflux_tpu import resilience

                resilience.maybe_fault(None, "refresh")
                self._factors = None  # release before the factor dispatch
                self._factors = self._factor_base(self._A0)
                # possibly-corrupt derived factors die with the rung-1
                # rebuild — they'd be rebuilt from the same A0, but a
                # transient-corruption escalation must not trust them
                self._tier_factors = {}
            self.factorizations += 1
            self.refactors += 1
            self._gang_ver += 1  # the gang slot is stale; lazy re-sync
            self._ckpt_ver += 1
            return self

    # ------------------------------------------------------------------ #
    # incremental drift
    # ------------------------------------------------------------------ #

    def _check_uv(self, U, V):
        plan = self.plan
        if U.shape != V.shape:
            raise ValueError(f"U {U.shape} and V {V.shape} must agree")
        lead = (plan.B, plan.N) if plan.batched else (plan.N,)
        want_nd = len(lead) + 1
        if U.ndim != want_nd or U.shape[:-1] != lead:
            raise ValueError(
                f"update factors {U.shape}, session needs {lead} (+ rank "
                "axis)")
        if U.shape[-1] < 1:
            raise ValueError("update rank must be >= 1")

    def update(self, U, V, *, replace: bool = False):
        """Apply the rank-k drift A <- A + U V^H without refactoring.

        U, V are (N, k) for single plans, (B, N, k) for batched ones
        (k << N). Updates ACCUMULATE (rank adds) unless `replace=True`,
        which measures the drift from the current base factors instead —
        the steady-state "rank-k drift per request" traffic shape.
        Subsequent `solve` calls apply the base factors plus the k x k
        capacitance correction; the drift policy refactors through the
        plan's cached factor program once accumulated rank exceeds
        `policy.max_rank` or the capacitance conditioning exceeds
        `policy.cond_limit`. Returns self (chainable:
        `session.update(U, V).solve(b)`).
        """
        plan = self.plan
        if plan.key.kind == "qr":
            raise ValueError(
                "incremental (Woodbury) drift updates apply to square "
                "plans — a kind='qr' least-squares session re-factors "
                "on base change (the SMW identity corrects A^-1, not "
                "the pseudoinverse; DESIGN §33)")
        dtype = jnp.dtype(plan.key.dtype)
        U = jnp.asarray(U, dtype)
        V = jnp.asarray(V, dtype)
        self._check_uv(U, V)
        with self._lock, profiler.region("serve.update"):
            self._ensure_resident()
            if self._upd is not None:
                if replace:
                    # the superseded Woodbury state (Up/Vp/Y/Cinv) is dead
                    # the moment the drift is re-measured — drop it before
                    # the new dispatch so it never doubles peak memory
                    self._upd = None
                else:
                    k0 = self._upd["k"]
                    U = jnp.concatenate([self._upd["Up"][..., :k0], U],
                                        axis=-1)
                    V = jnp.concatenate([self._upd["Vp"][..., :k0], V],
                                        axis=-1)
                    # the concatenated copies carry the history now
                    self._upd = None
            k = U.shape[-1]
            if k > self.policy.resolved_max_rank(plan.N):
                self._refactor(U, V)
                return self
            kb = rank_bucket(k)
            if kb != k:
                pad = [(0, 0)] * (U.ndim - 1) + [(0, kb - k)]
                U, V = jnp.pad(U, pad), jnp.pad(V, pad)
            if plan.mesh is not None:
                U, V = _shard_batch((U, V), plan.mesh)
            Y, Cinv, cond1 = plan._update_fn(kb)(self._factors, U, V)
            # the scalar readback is deliberate (and why update() is
            # not a hot-path function): the drift policy's refactor
            # decision is host control flow
            cond = float(jnp.max(cond1))
            self.last_cond = cond
            if not (cond <= self.policy.cond_limit):  # catches NaN/inf too
                from conflux_tpu import resilience

                resilience.bump("cond_refactors")
                self._refactor(U, V)
                return self
            self._upd = {"k": k, "kb": kb, "Up": U, "Vp": V,
                         "Y": Y, "Cinv": Cinv}
            self.updates += 1
            self._gang_ver += 1  # the gang slot is stale; lazy re-sync
            self._ckpt_ver += 1
            if self._residency is not None:
                # footprint grew by the Woodbury state: refresh the
                # manager's byte gauge (nbytes under this held lock,
                # the gauge store under the manager's — the tier
                # layer's session->manager lock order)
                self._residency._note_bytes(self)
        return self

    def _refactor(self, Up, Vp):
        """Drift-policy trigger: materialize A0 + U V^H and pay one true
        refactorization through the plan's cached factor program; the
        session's base then absorbs the drift and the correction resets.
        Callers (`update`, `refactor`) already hold the session lock;
        the re-entrant acquire here keeps the swap atomic regardless."""
        plan = self.plan
        with self._lock, profiler.region("serve.refactor"):
            from conflux_tpu import resilience

            resilience.maybe_fault(None, "refresh")
            k = Up.shape[-1]
            kb = rank_bucket(k)
            if kb != k:  # zero columns leave A0 + U V^H unchanged
                pad = [(0, 0)] * (Up.ndim - 1) + [(0, kb - k)]
                Up, Vp = jnp.pad(Up, pad), jnp.pad(Vp, pad)
            if plan.mesh is not None:
                Up, Vp = _shard_batch((Up, Vp), plan.mesh)
            # the superseded drift state is dead the moment the new base
            # exists — drop it before dispatching, and donate the old base
            # once the session owns it, so the refactor peak holds one
            # resident base + one factor set, not two of each
            self._upd = None
            A_new = plan._refresh_fn(kb, donate=self._owns_base)(
                self._A0, Up, Vp)
            self._A0 = A_new
            self._probe = None  # wA was against the superseded base
            self._tier_factors = {}  # derived from the superseded base
            self._owns_base = True
            if self._A is not None:
                self._A = A_new
            self._factors = None  # release before the factor dispatch
            self._factors = self._factor_base(A_new)
            self.factorizations += 1
            self.refactors += 1
            self._gang_ver += 1  # the gang slot is stale; lazy re-sync
            self._ckpt_ver += 1
            if self._residency is not None:
                self._residency._note_bytes(self)
