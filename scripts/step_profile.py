"""Per-phase device-time table for the distributed LU hot loop.

The production factorization is ONE jitted program, so the host-side
`profiler.region` table can only show init/factor/validate totals (the
reference's per-step table, `README.md:120-165`, needs phase splits). This
harness recovers those splits from the device itself: run the program under
`jax.profiler.trace`, then join each HLO op's measured device duration with
the `jax.named_scope` recorded in the op's `op_name` metadata
(`profiler.phase_table`). No staged sub-jits, no perturbation — the timed
program is the production program.

Usage:  python scripts/step_profile.py [-N 16384] [-v 1024] [--grid 1,1,1]
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-N", type=int, default=16384)
    ap.add_argument("-v", type=int, default=1024)
    ap.add_argument("--grid", default="1,1,1")
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--panel-chunk", type=int, default=None)
    ap.add_argument("--top-other", type=int, default=0,
                    help="also list the N heaviest ops that carry no phase "
                    "scope (the '(other)' row), with their HLO op kinds")
    args = ap.parse_args()

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from conflux_tpu import profiler
    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.lu import distributed as D
    from conflux_tpu.ops import blas
    from conflux_tpu.parallel.mesh import (
        AXIS_X, AXIS_Y, make_mesh, mesh_cache_key,
    )

    Px, Py, Pz = (int(t) for t in args.grid.split(","))
    grid = Grid3(Px, Py, Pz)
    geom = LUGeometry.create(args.N, args.N, args.v, grid)
    mesh = make_mesh(grid, devices=jax.devices()[: grid.P])
    chunk = args.panel_chunk or blas.single_call_rows(args.v)
    fn = D._build(geom, mesh_cache_key(mesh), blas.matmul_precision(),
                  blas.get_backend(), chunk, False)

    sharding = NamedSharding(mesh, P(AXIS_X, AXIS_Y, None, None))

    import jax.numpy as jnp

    # generated on device: a host-side (M, M) build + transfer would
    # dominate the whole session at bench sizes
    @jax.jit
    def make():
        a = jax.random.normal(jax.random.PRNGKey(0), (geom.M, geom.M),
                              jnp.float32)
        return (a + 2 * jnp.eye(geom.M, dtype=jnp.float32))[None, None]

    if grid.P == 1:
        shards = jax.device_put(make(), sharding)
    else:
        rng = np.random.default_rng(0)
        A = (rng.standard_normal((geom.M, geom.M)).astype(np.float32)
             + 2 * np.eye(geom.M, dtype=np.float32))
        shards = jax.device_put(geom.scatter(A), sharding)

    compiled = fn.lower(shards).compile()
    out, _ = compiled(shards)  # warm-up outside the trace
    out.block_until_ready()

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="conflux-phase-")
    with profiler.trace(trace_dir):
        out, _ = compiled(shards)
        out.block_until_ready()

    print(f"# distributed LU N={geom.M} v={args.v} grid={args.grid} "
          f"steps={geom.n_steps} chunk={chunk}")
    agg = profiler.phase_table(trace_dir, compiled.as_text())
    # _trace_durations sums self time over every device plane, so divide
    # by the device count for a per-device (~wall) figure on meshes
    total_ms = sum(t for t, _ in agg.values()) / max(1, grid.P)
    flops = (2 / 3) * geom.M**3
    print(f"# per-device total {total_ms:.1f} ms -> "
          f"{flops / total_ms / 1e6:.1f} GFLOP/s aggregate")

    if args.top_other:
        hlo = compiled.as_text()
        scope = profiler._scope_map(hlo, profiler._PHASE_RE)
        durs = profiler._trace_durations(trace_dir)
        # op_name metadata (when present at all) for unattributed ops shows
        # WHICH jaxpr eqn the op came from even without a phase scope
        meta = profiler.op_name_map(hlo)
        rows = sorted(((ms, tok) for tok, ms in durs.items()
                       if tok not in scope), reverse=True)
        print(f"# top {args.top_other} unattributed ops:")
        for ms, tok in rows[: args.top_other]:
            print(f"  {ms:9.3f} ms  {tok:<40} {meta.get(tok, '')[:80]}")


if __name__ == "__main__":
    main()
