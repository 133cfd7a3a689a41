"""Closed loop of dense solves on a mesh of chips: factor + solve, one
step after another, the matrix sharded over the chips.

The loop of `dense_steps` on a (Px, Py, Pz) grid of `chips` chips: each
step draws a fresh matrix from (seed, step), generated as block-cyclic
shards on the chips that hold them (`reference_mesh.shard_generator`),
factors it with the distributed LU (donated), solves with
`lu_solve_distributed` and ends when the replicated x is ready. The
window's rate is the work of the steps it completed over all of its time.
Once the window has closed, every step's x is checked against the
regenerated system, and the last step's sharded factors against A[perm]
on the mesh (`reference_mesh.lu_factor_residual`).

Traffic keys: none beyond "loop". Configuration keys: n, v, grid (its
product the cell's chips), matmul_precision ("highest" as stated, "high"
for the control), and the limits of the checks.
"""

from __future__ import annotations

import time

import numpy as np


def run(*, conf, traffic, seed, seconds, tracer, devs, chips, t_start, log):
    import jax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import common, flops, matrices, reference, reference_mesh
    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.lu.distributed import build_program
    from conflux_tpu.parallel.mesh import AXIS_X, AXIS_Y, make_mesh
    from conflux_tpu.solvers import lu_solve_distributed

    t_driver = time.perf_counter()
    n, v = int(conf["n"]), int(conf["v"])
    grid = Grid3(*conf["grid"])
    if grid.P != chips or len(devs) < chips:
        raise ValueError(f"dense_steps_mesh drives grid {grid} on {chips} "
                         f"chips; {len(devs)} devices given")
    precision = {"highest": lax.Precision.HIGHEST,
                 "high": lax.Precision.HIGH}[conf["matmul_precision"]]
    geom = LUGeometry.create(n, n, v, grid)
    mesh = make_mesh(grid, devices=devs[:chips])
    axes = (AXIS_X, AXIS_Y)
    sharding = NamedSharding(mesh, P(*axes, None, None))
    gen = reference_mesh.shard_generator(n, v, mesh, axes)
    rhs = jax.jit(lambda s: matrices.dense_rhs(n, s),
                         out_shardings=NamedSharding(mesh, P()))
    factor = build_program(geom, mesh, precision=precision, donate=True,
                           dtype=jax.numpy.float32)

    launch = []  # host seconds from a step's start to its last dispatch

    def step(s32):
        t = time.perf_counter()
        s32 = np.uint32(s32)
        with common.span("generate"):
            A = gen(s32)
            b = rhs(s32)
        with common.span("factor"):
            F, perm = factor(A)
        with common.span("solve"):
            x = lu_solve_distributed(F, perm, geom, mesh, b)
        launch.append(time.perf_counter() - t)
        with common.span("wait"):
            x.block_until_ready()
        return F, perm, x

    # set-up: compile (or load) and run every program of the window once
    t_warm = time.perf_counter()
    F, perm, x = step(matrices.fold_seed(seed, 1, 0))
    del F, perm, x
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.3f}: start to driver {t_driver - t_start:.3f} s,"
        f" build {t_warm - t_driver:.3f} s, first step (compile or cache "
        f"load, run) {t_start + setup_s - t_warm:.3f} s")

    seeds, xs, ends = [], [], []
    del launch[:]
    compiles = common.compile_count()
    with tracer:
        t0 = time.perf_counter()
        while True:
            s32 = matrices.fold_seed(seed, 0, len(seeds))
            with common.span("step"):
                F, perm, x = step(s32)
            ends.append(time.perf_counter())
            seeds.append(s32)
            xs.append(x)
            if ends[-1] - t0 >= seconds:
                break
            del F, perm
        window_s = time.perf_counter() - t0
    compiles = common.compile_count() - compiles
    step_s = np.diff([t0] + ends)
    log(f"steps={len(seeds)} window_s={window_s:.4f} compiles={compiles} "
        "step_s: " + " ".join(f"{t:.4f}" for t in step_s))
    log("launch_ms: " + " ".join(f"{1e3 * t:.1f}" for t in launch))
    mem = common.memory_peak(devs[:chips])

    programs = {}
    if tracer.on:  # the compiled texts, for the trace's scopes
        shape = jax.ShapeDtypeStruct(F.shape, F.dtype, sharding=sharding)
        programs["factor"] = factor.lower(shape).compile().as_text()
    t_check = time.perf_counter()
    fres = reference_mesh.lu_factor_residual(F, perm, n, v, seeds[-1], axes)
    del F, perm
    serr = [reference.solve_backward_error(x, n, s) for x, s in zip(xs, seeds)]
    log(f"check_s={time.perf_counter() - t_check:.3f}")
    lim_f, lim_s = conf["limit_factor_residual"], conf["limit_backward_error"]
    bad = sum(e > lim_s for e in serr)
    return {
        "correct": fres <= lim_f and bad == 0,
        "attempted": len(seeds), "failed": bad,
        "checks": {"factor_resid": (fres, lim_f),
                   "backward_err": (max(serr), lim_s)},
        "memory_peak_bytes": mem,
        "setup_s": setup_s, "window_s": window_s, "steps": len(seeds),
        "step_flops": flops.dense_step_flops(n),
        "trailing": flops.trailing_update(n, v),
        "programs": programs,
        "scope_tables": {"factor": r"step\d+_[a-z0-9]+(?:/comm_[a-z0-9_]+)?"},
    }
