"""The §33 precision ladder: per-request tier routing on the
kind-generic PlanKey.

Contracts asserted here (ISSUE 18):

- `precision=None` stays BITWISE-identical to the pre-§33 path — the
  default route never even looks at the tier machinery.
- Per-request tiers select distinct compiled program families under ONE
  plan (`("tier", tier, wb)` keyspace in `_solve_cache`), warmed and
  retired through the same `bucket_ready`/`release_buckets` lifecycle
  as the native buckets, with ZERO compiles after `prewarm(...,
  precisions=)`.
- `"auto"` starts on bf16+IR and the fused §20 Freivalds verdict climbs
  the ladder (`resilience.escalate_precision`) — sticky per session,
  counted, and falling through to the native escalation rungs at the
  top.
- The fleet codec speaks `kind`, decodes pre-§33 `"spd"` checkpoints,
  and refuses non-representable precision payloads with the offending
  value named (encode AND decode).
- Tier-opened sessions ride spill/revive and checkpoint/restore
  bitwise, serving their tier after every round trip.
"""

import json
import os

import numpy as np
import pytest

from conflux_tpu import resilience, serve, tier
from conflux_tpu.engine import ServeEngine

N, V = 256, 256


def _system(n=N, seed=0, scale=None):
    rng = np.random.default_rng(seed)
    A = (rng.standard_normal((n, n))
         + (n if scale is None else scale) * np.eye(n)).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    return A, b


def _ill_conditioned(n=N, seed=3, cond=1e6):
    rng = np.random.default_rng(seed)
    U, _ = np.linalg.qr(rng.standard_normal((n, n)))
    sv = np.logspace(0, -np.log10(cond), n)
    return ((U * sv) @ U.T).astype(np.float32)


def _resid(A, x, b):
    x = np.asarray(x, np.float64)
    return (np.linalg.norm(A.astype(np.float64) @ x - b)
            / np.linalg.norm(b))


# --------------------------------------------------------------------------- #
# the kind-generic key + request validation
# --------------------------------------------------------------------------- #


def test_plan_key_kind_replaces_spd():
    lu = serve.FactorPlan.create((N, N), np.float32, kind="lu")
    ch = serve.FactorPlan.create((N, N), np.float32, kind="chol")
    legacy = serve.FactorPlan.create((N, N), np.float32, spd=True)
    assert lu.key.kind == "lu" and not lu.key.spd
    assert ch.key.kind == "chol" and ch.key.spd
    # spd=True is the pre-§33 spelling of kind='chol': same key, same
    # cached plan object
    assert legacy is ch
    with pytest.raises(ValueError, match="kind"):
        serve.FactorPlan.create((N, N), np.float32, kind="qz")


def test_check_precision_request_names_offender():
    for ok in (None, "auto") + serve.PRECISION_TIERS:
        assert serve.check_precision_request(ok) == ok
    with pytest.raises(ValueError, match="fp8"):
        serve.check_precision_request("fp8")
    with pytest.raises(ValueError):
        serve.check_precision_request(16)


# --------------------------------------------------------------------------- #
# codec hardening: _encode_precision / _decode_precision
# --------------------------------------------------------------------------- #


def test_encode_precision_rejects_non_enum_objects():
    from jax import lax

    assert serve._encode_precision(None) is None
    assert serve._encode_precision("highest") == "highest"
    assert serve._encode_precision(lax.Precision.HIGHEST) == \
        ["precision", "HIGHEST"]
    # a non-enum object must be refused while the checkpoint is still
    # writable — with the offending value in the message
    with pytest.raises(ValueError, match=r"\('highest', 'highest'\)"):
        serve._encode_precision(("highest", "highest"))
    with pytest.raises(ValueError, match="float32"):
        serve._encode_precision(np.float32)


def test_decode_precision_rejects_malformed_payloads():
    from jax import lax

    assert serve._decode_precision(None) is None
    assert serve._decode_precision("highest") == "highest"
    assert serve._decode_precision(["precision", "HIGHEST"]) == \
        lax.Precision.HIGHEST
    # the decode-rejection cases: payloads no encoder produced must
    # raise with the value named, never flow into a mismatched PlanKey
    with pytest.raises(ValueError, match="NOPE"):
        serve._decode_precision(["precision", "NOPE"])
    with pytest.raises(ValueError, match="3"):
        serve._decode_precision(["precision", "HIGHEST", 3])
    with pytest.raises(ValueError, match="17"):
        serve._decode_precision(17)
    with pytest.raises(ValueError, match="dict"):
        serve._decode_precision({"precision": "HIGHEST"})


def test_plan_spec_roundtrip_and_spd_migration_shim():
    plan = serve.FactorPlan.create((N, N), np.float32, kind="chol")
    spec = serve.plan_spec(plan)
    assert spec["kind"] == "chol" and "spd" not in spec
    assert serve.plan_from_spec(json.loads(json.dumps(spec))) is plan
    # the §33 migration shim: a pre-refactor spec spelling the family
    # as a bare boolean decodes to the same plan
    old = {k: v for k, v in spec.items() if k != "kind"}
    old["spd"] = True
    assert serve.plan_from_spec(old) is plan
    old["spd"] = False
    assert serve.plan_from_spec(old) is \
        serve.FactorPlan.create((N, N), np.float32, kind="lu")
    bad = dict(spec)
    bad["kind"] = "qz"
    with pytest.raises(ValueError, match="qz"):
        serve.plan_from_spec(bad)


def test_pre_refactor_fleet_checkpoint_restores(tmp_path):
    """Round-trip against a pre-§33 fleet.json fixture: the snapshot is
    rewritten to the old on-disk dialect ('spd' boolean in the plan
    spec, none of the new meta keys in the record manifests) and must
    restore bitwise through the migration shim."""
    A, b = _system(seed=11)
    spd = (A @ A.T + N * np.eye(N)).astype(np.float32)
    lu = serve.FactorPlan.create((N, N), np.float32, kind="lu").factor(A)
    ch = serve.FactorPlan.create((N, N), np.float32,
                                 kind="chol").factor(spd)
    x_lu = np.asarray(lu.solve(b))
    x_ch = np.asarray(ch.solve(b))
    path = os.path.join(tmp_path, "fleet")
    tier.save_fleet(path, [lu, ch], names=["lu", "ch"])
    # rewrite to the pre-refactor dialect
    fj = os.path.join(path, "fleet.json")
    with open(fj) as f:
        fleet = json.load(f)
    for e in fleet["sessions"]:
        e["plan"]["spd"] = e["plan"].pop("kind") == "chol"
    with open(fj, "w") as f:
        json.dump(fleet, f)
    for name in ("lu", "ch"):
        mp = os.path.join(path, name, "manifest.json")
        with open(mp) as f:
            man = json.load(f)
        for k in ("precision", "auto_rung", "probe_parts"):
            man["meta"].pop(k, None)
        with open(mp, "w") as f:
            json.dump(man, f)
    r_lu, r_ch = tier.load_fleet(path)
    assert r_lu.plan.key.kind == "lu" and r_ch.plan.key.kind == "chol"
    assert r_lu.served_tier is None and r_ch.served_tier is None
    assert np.array_equal(x_lu, np.asarray(r_lu.solve(b)))
    assert np.array_equal(x_ch, np.asarray(r_ch.solve(b)))


# --------------------------------------------------------------------------- #
# per-request tier routing (session surface)
# --------------------------------------------------------------------------- #


def test_default_precision_bitwise_and_tier_routing():
    A, b = _system(seed=1)
    plan = serve.FactorPlan.create((N, N), np.float32, kind="lu",
                                   refine=1)
    s = plan.factor(A)
    x0 = np.asarray(s.solve(b))
    # default None is the pre-§33 program, bitwise
    assert np.array_equal(x0, np.asarray(s.solve(b, precision=None)))
    # the f32 tier of an f32-native plan computes the same factors at
    # the same dtype/sweeps — same answer
    xf = np.asarray(s.solve(b, precision="f32"))
    assert _resid(A, xf, b) < 1e-5
    xb = np.asarray(s.solve(b, precision="bf16_ir"))
    assert _resid(A, xb, b) < 1e-2  # bf16 factors + 1 IR sweep
    with pytest.raises(ValueError, match="fp8"):
        s.solve(b, precision="fp8")


def test_factor_at_tier_opens_smaller_session():
    A, b = _system(seed=2)
    plan = serve.FactorPlan.create((N, N), np.float32, kind="lu",
                                   refine=1)
    native = plan.factor(A)
    tiered = plan.factor(A, precision="bf16_ir")
    assert native.served_tier is None
    assert tiered.served_tier == "bf16_ir"
    # the capacity mechanism: bf16 factors are ~half the resident bytes
    assert tiered.nbytes < 0.85 * native.nbytes
    assert _resid(A, np.asarray(tiered.solve(b)), b) < 1e-2
    # an explicit native-tier request on a tiered session re-routes
    # through the derived cross-tier cache and matches the native bits
    xf = np.asarray(tiered.solve(b, precision="f32"))
    assert np.array_equal(xf, np.asarray(native.solve(b,
                                                      precision="f32")))


def test_drifted_session_cross_tier_falls_back_counted():
    A, b = _system(seed=4)
    rng = np.random.default_rng(4)
    plan = serve.FactorPlan.create((N, N), np.float32, kind="lu",
                                   refine=1)
    s = plan.factor(A)
    u = (rng.standard_normal((N, 1)) * 0.01).astype(np.float32)
    v = (rng.standard_normal((N, 1)) * 0.01).astype(np.float32)
    s.update(u, v)
    A1 = A + u @ v.T
    # a drifted session serving a cross-tier request answers against
    # the DRIFTED system on its resident path — counted, not an error
    x = np.asarray(s.solve(b, precision="bf16_ir"))
    assert _resid(A1, x, b) < 1e-4
    assert s.precision_fallbacks == 1


def test_bucket_lifecycle_tier_families():
    A, _b = _system(seed=5)
    plan = serve.FactorPlan.create((N, N), np.float32, kind="lu",
                                   refine=1)
    s = plan.factor(A)
    eng = ServeEngine(max_batch_delay=0.001)
    try:
        assert not plan.bucket_ready(width=2, precision="bf16_ir")
        eng.prewarm(s, widths=(2,), factor_batches=(1, 2),
                    precisions=("bf16_ir",))
        assert plan.bucket_ready(width=2, precision="bf16_ir")
        assert plan.bucket_ready(factor_batch=2, precision="bf16_ir")
        with pytest.raises(ValueError, match="auto"):
            plan.bucket_ready(width=2, precision="auto")
        with pytest.raises(ValueError, match="gang"):
            plan.bucket_ready(stack=(2, 2), precision="bf16_ir")
        # retirement drops the tier families with their buckets
        assert plan.release_buckets(widths=(2,),
                                    factor_batches=(2,)) > 0
        assert not plan.bucket_ready(width=2, precision="bf16_ir")
        assert not plan.bucket_ready(factor_batch=2,
                                     precision="bf16_ir")
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# engine routing + the auto ladder
# --------------------------------------------------------------------------- #


def test_engine_precision_routing_and_zero_compiles():
    A, b = _system(seed=6)
    plan = serve.FactorPlan.create((N, N), np.float32, kind="lu",
                                   refine=1)
    s = plan.factor(A)
    eng = ServeEngine(max_batch_delay=0.001)
    try:
        eng.prewarm(s, widths=(1, 2), factor_batches=(1, 2),
                    precisions=("auto",))
        t0 = dict(plan.trace_counts)
        x0 = eng.submit(s, b).result(timeout=60)
        xa = eng.submit(s, b, precision="auto").result(timeout=60)
        xb = eng.submit(s, b, precision="bf16_ir").result(timeout=60)
        s2 = eng.submit_factor(plan, A, precision="auto") \
                .result(timeout=60)
        x2 = eng.submit(s2, b, precision="auto").result(timeout=60)
        assert {k: v - t0.get(k, 0) for k, v in plan.trace_counts.items()
                if v - t0.get(k, 0)} == {}, "steady state recompiled"
        assert np.array_equal(np.asarray(x0), np.asarray(s.solve(b)))
        assert np.array_equal(np.asarray(xa), np.asarray(xb))
        assert s2.served_tier == "bf16_ir"
        assert _resid(A, x2, b) < 1e-2
        with pytest.raises(ValueError, match="fp8"):
            eng.submit(s, b, precision="fp8")
    finally:
        eng.close()
    # a checked prewarm of "auto" warms every rung's CHECKED program, an
    # f32-opened session's own included; a healthy system never climbs
    eng = ServeEngine(max_batch_delay=0.001,
                      health=resilience.HealthPolicy())
    try:
        eng.prewarm(s2, widths=(1,), precisions=("auto",))
    finally:
        eng.close()
    sf = plan.factor(A, precision="f32")
    t0 = dict(plan.trace_counts)
    xa, _va = s2.solve_checked(b, precision="auto")
    xf, _vf = sf.solve_checked(b, precision="f32")
    assert {k: v - t0.get(k, 0) for k, v in plan.trace_counts.items()
            if v - t0.get(k, 0)} == {}, "a ladder rung compiled"
    assert _resid(A, xa, b) < 1e-2 and _resid(A, xf, b) < 1e-5
    assert s2.precision_escalations == sf.precision_escalations == 0


def test_auto_ladder_escalates_and_sticks():
    Abad = _ill_conditioned()
    b = np.random.default_rng(7).standard_normal(N).astype(np.float32)
    plan = serve.FactorPlan.create((N, N), np.float32, kind="lu",
                                   refine=1)
    s = plan.factor(Abad)
    eng = ServeEngine(max_batch_delay=0.001)
    try:
        x = eng.submit(s, b, precision="auto").result(timeout=120)
        # the bf16 rung's verdict trips on a cond~1e6 system; the
        # ladder climbs to f32 and answers there
        assert _resid(Abad, x, b) < 1e-2
        assert s.precision_escalations >= 1
        assert s.auto_rung >= 1
        rung = s.auto_rung
        # sticky: the next auto request starts AT the learned rung
        # (no repeated bf16 failures)
        esc0 = s.precision_escalations
        x2 = eng.submit(s, b, precision="auto").result(timeout=120)
        assert _resid(Abad, x2, b) < 1e-2
        assert s.auto_rung == rung
        assert s.precision_escalations == esc0
        st = eng.stats()
        assert st["precision_escalations"] >= 1
    finally:
        eng.close()


def test_mesh_plans_reject_precision():
    from conflux_tpu import batched
    from conflux_tpu.resilience import MeshPlanUnsupported

    mesh = batched.batch_mesh()
    plan = serve.FactorPlan.create((8, 64, 64), np.float32, v=32,
                                   kind="lu", mesh=mesh)
    rng = np.random.default_rng(8)
    A = (rng.standard_normal((8, 64, 64)) / 8
         + 2 * np.eye(64)).astype(np.float32)
    # the plan surface refuses before any factor work (serve layer
    # speaks ValueError; the engine surfaces MeshPlanUnsupported)
    with pytest.raises(ValueError, match="native precision"):
        plan.factor(A, precision="bf16_ir")
    eng = ServeEngine(max_batch_delay=0.001)
    try:
        s = plan.factor(A)
        b = rng.standard_normal((8, 64)).astype(np.float32)
        with pytest.raises(MeshPlanUnsupported):
            eng.submit(s, b, precision="auto")
        with pytest.raises(MeshPlanUnsupported):
            eng.submit_factor(plan, A, precision="bf16_ir")
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# tiering: spill/revive + checkpoint keep the served tier
# --------------------------------------------------------------------------- #


def test_tier_session_spill_revive_checkpoint_bitwise(tmp_path):
    A, b = _system(seed=9)
    plan = serve.FactorPlan.create((N, N), np.float32, kind="lu",
                                   refine=1)
    s = plan.factor(A, precision="bf16_ir")
    x0 = np.asarray(s.solve(b))
    rs = tier.ResidentSet(max_sessions=4).adopt(s)
    rs.spill(s)
    assert np.array_equal(x0, np.asarray(s.solve(b)))
    assert s.served_tier == "bf16_ir"
    path = os.path.join(tmp_path, "fleet")
    tier.save_fleet(path, [s])
    (r,) = tier.load_fleet(path)
    assert r.served_tier == "bf16_ir"
    assert np.array_equal(x0, np.asarray(r.solve(b)))


def test_escalate_precision_ladder_direct():
    """The resilience rung sequence without an engine: bf16 verdict
    evidence -> escalate_precision climbs to f32, evidence chain
    carries the tier rung."""
    Abad = _ill_conditioned(seed=10)
    b = np.random.default_rng(10).standard_normal(N).astype(np.float32)
    plan = serve.FactorPlan.create((N, N), np.float32, kind="lu",
                                   refine=1)
    s = plan.factor(Abad)
    x, verdict = s.solve_checked(b, precision="auto")
    finite, res = (float(np.asarray(verdict)[0]),
                   float(np.asarray(verdict)[1]))
    pol = resilience.HealthPolicy()
    limit = pol.resolved_residual_limit(np.dtype(np.float32), N)
    assert res > limit  # the bf16 rung really is unhealthy here
    b2 = b[:, None]
    out = resilience.escalate_precision(
        s, b2, "auto", pol, limit,
        evidence0={"rung": "bf16_ir", "finite": finite,
                   "residual": res})
    assert _resid(Abad, out[..., 0], b) < 1e-2
    assert s.auto_rung >= 1 and s.precision_escalations >= 1
