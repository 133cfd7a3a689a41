"""Smoke run of the main paths on one TPU, in one process.

    python chip_smoke.py [--seed S]             # one chip: LU, Cholesky, serving
    python chip_smoke.py --chips 4 [--seed S]   # four chips: the 2.5D LU grids

Every phase prints one line: its name, compile seconds (first call minus a
steady call: set-up), steady seconds, a rate, and its residual next to the
bound it must stay under. The last line of standard output is one JSON
object, ``{"ok": ..., "device": {"platform", "kind", "count"}}``. The script
exits non-zero when a phase fails or misses its bound, and before any phase
(printing no result) when JAX's default device is not a TPU: there is no CPU
fallback. Matrices are generated on the device from --seed
(`validation.seeded_shards`); serving traffic comes from a NumPy generator
with the same seed. The sizes are module constants; the CPU test of this
script shrinks them.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

# one chip
LU_N, LU_V = 32768, 1024            # the lu_n32768 cell's config
CHOL_N, CHOL_V = 32768, 1024
FLEET, FLEET_N, FLEET_V = 256, 1024, 256   # ~1 GiB of resident factors
PALLAS_SESSIONS, PALLAS_N = 16, 512        # the factor kernels' VMEM limit
PANEL_ROWS = 2048                          # blas._PALLAS_MAX_ROWS
# four chips
MESH_LU_N = 65536                   # 2x2x1: 4 GiB of shards per chip
MESH_PZ_N = 75776                   # 2x1x2: largest N whose v5e compile fits
MESH_V = 1024


class PhaseFailed(RuntimeError):
    pass


def _line(name, compile_s, steady_s, rate, unit, resid, bound, extra=""):
    ok = resid <= bound
    print(f"{name}: compile_s={compile_s:.2f} steady_s={steady_s:.4f} "
          f"{unit}={rate:.1f} residual={resid:.3e} bound={bound:.3e} "
          f"{'PASS' if ok else 'FAIL'}{extra}", flush=True)
    if not ok:
        raise PhaseFailed(f"{name}: residual {resid:.3e} > bound {bound:.3e}")


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def phase_lu(devices, seed, n=None, v=None, grid=None, exact=True):
    """Distributed LU through `lu_factor_distributed`, donated, on a
    matrix generated on the devices. `exact` picks the blockwise exact
    residual (one device) over the projected one (any mesh)."""
    import jax.numpy as jnp

    from conflux_tpu import validation
    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh

    n = LU_N if n is None else n
    v = LU_V if v is None else v
    grid = Grid3(1, 1, 1) if grid is None else grid
    geom = LUGeometry.create(n, n, v, grid)
    mesh = make_mesh(grid, devices=devices[:grid.P])
    if len({d.id for d in mesh.devices.flat}) != grid.P:
        raise PhaseFailed(f"mesh {grid} does not span {grid.P} devices")

    def run():
        A = validation.seeded_shards(geom, mesh, seed)
        A.block_until_ready()
        return _timed(lambda: lu_factor_distributed(A, geom, mesh,
                                                    donate=True))

    (out, perm), first = run()
    del out, perm
    (out, perm), steady = run()
    if exact:
        LU = out[0, 0]
        del out  # the strips need the room: LU + 5.5 GiB at N=32768
        resid = validation.lu_residual_strips(LU, perm, seed)
        kind = "exact"
    else:
        resid = validation.lu_residual_projected(out, perm, geom, mesh, seed)
        kind = "projected k=16"
    del perm
    _line(f"lu grid={grid} N={n} v={v}", first - steady, steady,
          (2 / 3) * n ** 3 / steady / 1e9, "GFLOP/s", resid,
          validation.residual_bound(n, jnp.float32), f" ({kind})")


def phase_cholesky(devices, seed, n=None, v=None):
    import jax.numpy as jnp

    from conflux_tpu import validation
    from conflux_tpu.cholesky.distributed import cholesky_factor_distributed
    from conflux_tpu.geometry import CholeskyGeometry, Grid3
    from conflux_tpu.parallel.mesh import make_mesh

    n = CHOL_N if n is None else n
    v = CHOL_V if v is None else v
    grid = Grid3(1, 1, 1)
    geom = CholeskyGeometry.create(n, v, grid)
    mesh = make_mesh(grid, devices=devices[:1])

    def run():
        S = validation.seeded_shards(geom, mesh, seed, spd=True)
        S.block_until_ready()
        return _timed(lambda: cholesky_factor_distributed(
            S, geom, mesh, donate=True))

    L, first = run()
    del L
    L, steady = run()
    L = L[0, 0]
    resid = validation.cholesky_residual_strips(L, seed)
    del L
    _line(f"cholesky grid={grid} N={n} v={v}", first - steady, steady,
          n ** 3 / 3 / steady / 1e9, "GFLOP/s", resid,
          validation.residual_bound(n, jnp.float32))


def _systems(rng, count, n):
    """Well-conditioned users' systems: G / sqrt(n) + 2I."""
    A = rng.standard_normal((count, n, n), dtype=np.float32)
    A /= np.float32(np.sqrt(n))
    A[:, np.arange(n), np.arange(n)] += np.float32(2.0)
    return A


def _forward_error(A, b, x):
    ref = np.linalg.solve(A.astype(np.float64), b.astype(np.float64))
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def phase_serving(seed, fleet=None, n=None, v=None, timeout=600.0):
    """A stacked fleet behind `ServeEngine`: prewarm, then one solve per
    session with zero compiles, each answer checked against a float64
    NumPy solve; plus the bitwise contract of coalesced answers."""
    import jax.numpy as jnp

    from conflux_tpu import FactorPlan, ServeEngine, profiler, validation

    fleet = FLEET if fleet is None else fleet
    n = FLEET_N if n is None else n
    v = FLEET_V if v is None else v
    rng = np.random.default_rng(seed)
    A = _systems(rng, fleet, n)
    plan = FactorPlan.create((n, n), jnp.float32, v=v)
    if plan.key.substitution != "blocked":
        raise PhaseFailed(f"default substitution is {plan.key.substitution}")
    bound = validation.residual_bound(n, jnp.float32)
    t0 = time.perf_counter()
    with ServeEngine(stack_sessions=True, max_stack=fleet) as eng:
        sessions = [eng.factor(plan, A[i], sid=f"user-{i}", timeout=timeout)
                    for i in range(fleet)]

        def round_():
            bs = rng.standard_normal((fleet, n), dtype=np.float32)
            order = rng.permutation(fleet)
            t = time.perf_counter()
            futs = [(i, eng.submit(sessions[i], bs[i])) for i in order]
            xs = {i: f.result(timeout) for i, f in futs}
            return bs, xs, time.perf_counter() - t

        round_()  # the gang grows to the whole fleet
        eng.prewarm(sessions[0], widths=(1, 2, 4, 8, 16, 32),
                    stacks=(fleet,))
        setup = time.perf_counter() - t0
        c0 = profiler.compile_count()
        bs, xs, steady = round_()
        compiles = profiler.compile_count() - c0
        gang = eng.stats().get("gang_batches", 0)
    err = max(_forward_error(A[i], bs[i], xs[i]) for i in range(fleet))

    # coalesced answers are BITWISE the direct solve's, at mixed widths
    direct, reqs = [], []
    for i in range(12):
        w = 1 + i % 4
        b = rng.standard_normal((n, w) if w > 1 else (n,), dtype=np.float32)
        reqs.append((sessions[i % 3], b))
        direct.append(np.asarray(sessions[i % 3].solve(jnp.asarray(b))))
    with ServeEngine(max_batch_delay=0.05, max_coalesce_width=8) as eng:
        futs = [eng.submit(s, b) for s, b in reqs]
        coalesced = [np.asarray(f.result(timeout)) for f in futs]
    bitwise = sum(np.array_equal(d, c) for d, c in zip(direct, coalesced))

    _line(f"serving fleet={fleet} N={n} v={v}", setup, steady,
          fleet / steady, "solves/s", err, bound,
          f" (max forward error vs float64; compiles_after_prewarm="
          f"{compiles} gang_batches={gang} bitwise={bitwise}/{len(reqs)})")
    if compiles:
        raise PhaseFailed(f"serving: {compiles} compiles after prewarm")
    if bitwise != len(reqs):
        raise PhaseFailed(f"serving: {len(reqs) - bitwise} coalesced "
                          "answers differ from session.solve")


def _mosaic(fn, *shapes) -> bool:
    """Whether `fn` at these shapes lowers to a Mosaic kernel (never the
    Pallas interpreter)."""
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*shapes).as_text()


def phase_pallas(seed, sessions=None, n=None, panel_rows=None,
                 timeout=600.0, require_mosaic=True):
    """The repaired Pallas kernels on their serving and ops paths: a
    `backend='pallas'` plan opens sessions through the factor lane, whose
    solves pass the float64 check; the blocked-trsm and panel kernels
    match the XLA paths."""
    import jax.numpy as jnp

    from conflux_tpu import FactorPlan, ServeEngine, validation
    from conflux_tpu.ops import batched_trsm, blas, pallas_factor

    count = PALLAS_SESSIONS if sessions is None else sessions
    n = PALLAS_N if n is None else n
    m = PANEL_ROWS if panel_rows is None else panel_rows
    rng = np.random.default_rng(seed + 1)
    plan = FactorPlan.create((n, n), jnp.float32, v=min(256, n),
                             backend="pallas")
    bound = validation.residual_bound(n, jnp.float32)
    with ServeEngine() as eng:
        def open_all(A):
            t = time.perf_counter()
            futs = [eng.submit_factor(plan, A[i]) for i in range(count)]
            return [f.result(timeout) for f in futs], time.perf_counter() - t

        _, first = open_all(_systems(rng, count, n))
        A = _systems(rng, count, n)
        opened, steady = open_all(A)
        bs = rng.standard_normal((count, n), dtype=np.float32)
        err = max(_forward_error(A[i], bs[i],
                                 np.asarray(opened[i].solve(bs[i])))
                  for i in range(count))

    T = jnp.asarray(A)
    rhs = jnp.asarray(rng.standard_normal((count, n, 8), dtype=np.float32))
    xp = batched_trsm.blocked_trsm(T, rhs, backend="pallas")
    xx = batched_trsm.blocked_trsm(T, rhs, backend="xla")
    trsm_err = float(jnp.linalg.norm(xp - xx) / jnp.linalg.norm(xx))
    panel = jnp.asarray(rng.uniform(-1, 1, (m, 128)).astype(np.float32))
    lu_p, perm_p = blas.panel_lu(panel, algo="pallas")
    panel_res = validation.lu_residual(np.asarray(panel)[:, :],
                                       np.asarray(lu_p), np.asarray(perm_p))
    mosaic = {
        "blu": _mosaic(pallas_factor.pallas_lu_factor_batched, T),
        "bchol": _mosaic(pallas_factor.pallas_cholesky_factor_batched, T),
        "btrsm": _mosaic(lambda t, b: batched_trsm.blocked_trsm(
            t, b, backend="pallas"), T, rhs),
        "lu_block": _mosaic(lambda p: blas.panel_lu(p, algo="pallas"),
                            panel),
    }
    _line(f"pallas factor-lane sessions={count} N={n}", first - steady,
          steady, count / steady, "sessions/s", max(err, trsm_err,
                                                     panel_res), bound,
          f" (solve {err:.2e}, btrsm vs xla {trsm_err:.2e}, panel "
          f"({m},128) {panel_res:.2e}; mosaic={mosaic})")
    if require_mosaic and not all(mosaic.values()):
        raise PhaseFailed(f"pallas: interpreted, not Mosaic: {mosaic}")


def _device_record():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the LU on the 2x2x1 and 2x1x2 grids")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX's default device is "
                         f"{devices[0].platform!r}, not a TPU")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX has {len(devices)}")
    from conflux_tpu import cache
    from conflux_tpu.geometry import Grid3

    cache.enable_persistent_cache()
    dev = _device_record()
    print(f"device: {dev}", flush=True)
    if args.chips == 4:
        phases = [
            ("lu 2x2x1", lambda: phase_lu(devices, args.seed, n=MESH_LU_N,
                                          v=MESH_V, grid=Grid3(2, 2, 1),
                                          exact=False)),
            ("lu 2x1x2", lambda: phase_lu(devices, args.seed, n=MESH_PZ_N,
                                          v=MESH_V, grid=Grid3(2, 1, 2),
                                          exact=False)),
        ]
    else:
        phases = [
            ("lu", lambda: phase_lu(devices, args.seed)),
            ("cholesky", lambda: phase_cholesky(devices, args.seed)),
            ("serving", lambda: phase_serving(args.seed)),
            ("pallas", lambda: phase_pallas(args.seed)),
        ]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:  # noqa: BLE001 — report and go on
            import traceback

            traceback.print_exc()
            print(f"{name}: FAILED {type(e).__name__}: {e}", flush=True)
            failed.append(name)
        print(f"{name}: wall_s={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": not failed, "device": dev}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
