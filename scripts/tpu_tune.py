"""Single-chip tuning sweep (run on real TPU) for all three cores.

Times the distributed program for the selected algorithm at bench scale
across the knobs the phase table (`conflux_miniapp --profile`) identified as
the levers:

  - matmul precision: HIGHEST (6-pass f32) vs HIGH (bf16x3) for the
    trailing GEMMs — ~40% of device time in the LU loop; HIGH roughly
    halves it at some residual cost (the IR solve absorbs factor-quality
    loss, solvers.py);
  - panel_chunk (LU only): the nomination chunk height (VMEM-bounded);
  - v: tile size (election work ~ N^2 v; GEMM efficiency grows with v).

Prints one line per config: GFLOP/s + an on-device or host residual.
Exits at once when JAX's default device is not a TPU.

Usage:
    python scripts/tpu_tune.py [-N 32768] [--reps 2] [--algo lu]
    python scripts/tpu_tune.py --algo cholesky -N 32768
    python scripts/tpu_tune.py --algo qr -N 16384 --configs highest:0:1024
"""

from __future__ import annotations

import argparse
import functools
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _qr_residual_on_device(Qs, Rs, geom):
    """Blockwise ||Q R - A||_F / ||A||_F on the chip for 1x1x1-mesh QR
    outputs (the `validation.lu_residual_strips` pattern: strips keep
    peak HBM at Q + R + O(strip) while A strips are regenerated by
    `validation.seeded_entries`, bit-identical to the factored input)."""
    import math

    import jax
    import jax.numpy as jnp
    from jax import lax

    from conflux_tpu.validation import seeded_entries

    n = geom.M
    Q = jnp.asarray(Qs)[0, 0]
    R = jnp.triu(jnp.asarray(Rs)[0, 0][:n])
    blk = math.gcd(n, 4096)

    @jax.jit
    def ssq(Q, R):
        rows = jnp.arange(n, dtype=jnp.int32)
        total = jnp.zeros((), jnp.float32)
        asq = jnp.zeros((), jnp.float32)
        for i in range(0, n, blk):
            Ai = seeded_entries(rows[i:i + blk, None], rows[None, :], n, 0)
            Ri = jnp.matmul(Q[i : i + blk], R,
                            precision=lax.Precision.HIGHEST) - Ai
            total = total + jnp.sum(Ri * Ri)
            asq = asq + jnp.sum(Ai * Ai)
        return total, asq

    rss, ass = ssq(Q, R)
    return float(jnp.sqrt(rss) / jnp.sqrt(ass))


def _spd_n(n):
    """Compiled once per size (bench._make_n pattern): redefining a jit
    function inside the config loop would recompile the (N, N) generator
    for every config."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=0)
    def gen(n):
        a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)
        s = (a + a.T) / 2 + n * jnp.eye(n, dtype=jnp.float32)
        return s[None, None]

    if not hasattr(_spd_n, "_fn"):
        _spd_n._fn = gen
    return _spd_n._fn(n)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-N", type=int, default=32768)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--algo", default="lu", choices=["lu", "cholesky", "qr"])
    ap.add_argument("--update", default="segments",
                    choices=["segments", "block"],
                    help="LU trailing-update partitioning: cond'd segment "
                    "lattice vs one switch-selected live-suffix block "
                    "(applies to every LU config in this invocation)")
    ap.add_argument("--lookahead", action="store_true",
                    help="software-pipelined loop (P8): overlap the next "
                    "panel's election/reduce with the trailing update "
                    "(applies to every config in this invocation; all "
                    "three cores)")
    ap.add_argument("--configs", default=None,
                    help="comma list precision:chunk:v[:RxC[:tree]], "
                    "e.g. highest:8192:1024,highest:8192:1024:16x16:flat "
                    "(chunk ignored for cholesky/qr; pass 0; RxC = LU "
                    "trailing-update row x col segment counts, '-' for the "
                    "library default; tree = pairwise|flat election "
                    "reduction — LU only)")
    args = ap.parse_args()
    if args.update != "segments" and args.algo != "lu":
        ap.error("--update applies to --algo lu only")

    # validate configs BEFORE touching the device: a malformed flag must
    # error in milliseconds. segs_arg is the same RxC grammar the miniapps use.
    from conflux_tpu.cli.common import segs_arg

    prec_names = ("high", "highest")
    if args.configs:
        configs = []
        for c in args.configs.split(","):
            parts = c.split(":")
            if not 3 <= len(parts) <= 6 or parts[0] not in prec_names:
                ap.error(f"bad config {c!r}: want "
                         "precision:chunk:v[:RxC[:tree]] with "
                         f"precision in {sorted(prec_names)}, RxC segment "
                         "counts ('-' = library default), tree in "
                         "pairwise|flat")
            p, chunk, v = parts[:3]
            segs = None  # None = the library default for the algorithm
            if len(parts) > 3 and parts[3] not in ("", "-"):
                try:
                    segs = segs_arg(parts[3])
                except argparse.ArgumentTypeError as e:
                    ap.error(f"bad segment field in config {c!r}: {e}")
            tree = parts[4] if len(parts) > 4 else "pairwise"
            if tree in ("", "-"):  # same default placeholder as RxC
                tree = "pairwise"
            if tree not in ("pairwise", "flat"):
                ap.error(f"bad tree field {tree!r} in config {c!r}: "
                         "want pairwise|flat (or '-' for the default)")
            if len(parts) > 5:
                ap.error(f"config {c!r}: the 6th (swap) field was removed "
                         "in round 4 — the DMA swap kernel was deleted "
                         "unadopted")
            if args.algo != "lu" and tree != "pairwise":
                # known at parse time: say so before touching the device
                ap.error(f"config {c!r}: the tree field is LU-only "
                         f"(algo={args.algo})")
            if not re.fullmatch(r"\d+", chunk) or not re.fullmatch(r"\d+", v) \
                    or int(v) < 1:
                ap.error(f"bad config {c!r}: chunk must be a non-negative "
                         "integer (0 = the library default) and v a "
                         "positive integer")
            # chunk 0 means "library default": panel_chunk=None downstream
            # (passing 0 through would clamp to v-tall chunks — a silently
            # pathological nomination, not the default)
            configs.append((p, int(chunk) or None, int(v), segs, tree))
    else:
        configs = None

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from conflux_tpu import cache, validation
    from conflux_tpu.cli.common import require_tpu
    from conflux_tpu.geometry import CholeskyGeometry, Grid3, LUGeometry
    from conflux_tpu.parallel.mesh import AXIS_X, AXIS_Y, make_mesh

    require_tpu()
    cache.enable_persistent_cache()

    N = args.N
    grid = Grid3(1, 1, 1)
    mesh = make_mesh(grid, devices=jax.devices()[:1])
    sharding = NamedSharding(mesh, P(AXIS_X, AXIS_Y, None, None))
    prec = {"highest": lax.Precision.HIGHEST, "high": lax.Precision.HIGH}
    # qr times geqrf + explicit thin Q formation (orgqr), ~8/3 N^3 total,
    # so its rate line is comparable to the LU/Cholesky MXU utilization
    flop_coeff = {"lu": 2 / 3, "cholesky": 1 / 3, "qr": 8 / 3}[args.algo]

    if configs is not None:
        pass
    elif args.algo == "lu":
        configs = [
            ("highest", 8192, 1024, None, "pairwise"),
            ("high", 8192, 1024, None, "pairwise"),
            ("highest", 12288, 1024, None, "pairwise"),
            ("highest", 4096, 1024, None, "pairwise"),
            ("highest", 8192, 2048, None, "pairwise"),
            ("high", 8192, 2048, None, "pairwise"),
            ("highest", 8192, 512, None, "pairwise"),
        ]
    else:
        configs = [
            ("highest", 0, 1024, None, "pairwise"),
            ("high", 0, 1024, None, "pairwise"),
            ("highest", 0, 512, None, "pairwise"),
            ("highest", 0, 2048, None, "pairwise"),
        ]

    for pname, chunk, v, segs, tree in configs:
        chunk_lbl = "default" if chunk is None else chunk
        cfg_lbl = (f"algo={args.algo} precision={pname} chunk={chunk_lbl} "
                   f"v={v}")
        if args.algo == "qr":
            # qr segments columns only: the 4th field is a single csegs
            # count written as 1xC (row part must be 1)
            if segs is not None and segs[0] != 1:
                print(f"algo=qr: segs {segs} not supported (qr has no row "
                      "segmentation); write the 4th field as 1xC", flush=True)
                continue
            seg_kw = {} if segs is None else {"csegs": segs[1]}
            seg_lbl = "lib" if segs is None else f"1x{segs[1]}"
        else:
            seg_kw = {} if segs is None else {"segs": segs}
            seg_lbl = "lib" if segs is None else f"{segs[0]}x{segs[1]}"
        try:
            if args.algo == "lu":
                from conflux_tpu.lu.distributed import lu_factor_distributed

                geom = LUGeometry.create(N, N, v, grid)

                def factor(s, geom=geom, chunk=chunk, pname=pname,
                           seg_kw=seg_kw, tree=tree):
                    return lu_factor_distributed(
                        s, geom, mesh, precision=prec[pname],
                        panel_chunk=chunk, donate=True, tree=tree,
                        update=args.update, lookahead=args.lookahead,
                        **seg_kw)

                def make(geom=geom):
                    # the residual oracle regenerates A through the same
                    # seeded entries, so the two are the same matrix
                    return validation.seeded_shards(geom, mesh, 0)

                def residual(out, aux):
                    return validation.lu_residual_strips(out[0, 0], aux, 0)

            elif args.algo == "cholesky":
                from conflux_tpu.cholesky.distributed import (
                    cholesky_factor_distributed,
                )
                from conflux_tpu.validation import (
                    cholesky_residual_distributed,
                )

                geom = CholeskyGeometry.create(N, v, grid)

                def factor(s, geom=geom, pname=pname, seg_kw=seg_kw):
                    # donate like the LU/QR branches: without it the loop
                    # pays a full-buffer copy per superstep and the rates
                    # are not comparable across cores
                    return cholesky_factor_distributed(
                        s, geom, mesh, precision=prec[pname],
                        donate=True, lookahead=args.lookahead,
                        **seg_kw), None

                def make(geom=geom):
                    return jax.device_put(_spd_n(geom.N), sharding)

                def residual(out, aux, geom=geom):
                    return float(cholesky_residual_distributed(
                        make(), out, geom, mesh))

            else:  # qr
                from conflux_tpu.qr.distributed import qr_factor_distributed

                geom = LUGeometry.create(N, N, v, grid)

                def factor(s, geom=geom, pname=pname, seg_kw=seg_kw):
                    return qr_factor_distributed(
                        s, geom, mesh, precision=prec[pname], donate=True,
                        lookahead=args.lookahead, **seg_kw)

                def make(geom=geom):
                    return validation.seeded_shards(geom, mesh, 0)

                def residual(out, aux, geom=geom):
                    return _qr_residual_on_device(out, aux, geom)

            out, aux = factor(make())  # warm-up
            jnp.asarray(out).block_until_ready()
            float(jnp.asarray(out)[(0,) * jnp.asarray(out).ndim])
            times = []
            for _ in range(args.reps):
                s = make()
                float(jnp.asarray(s)[(0,) * jnp.asarray(s).ndim])
                t0 = time.time()
                out, aux = factor(s)
                float(jnp.asarray(out)[(0,) * jnp.asarray(out).ndim])
                times.append(time.time() - t0)
            dim = geom.N if args.algo == "cholesky" else geom.M
            gflops = flop_coeff * dim**3 / (sum(times) / len(times)) / 1e9
            la = "on" if args.lookahead else "off"
            print(f"{cfg_lbl} segs={seg_lbl} tree={tree} "
                  f"lookahead={la} "
                  f"update={args.update}: {gflops:.1f} GFLOP/s", flush=True)
            try:  # residual separately: never discard a good timing
                res = residual(out, aux)
                print(f"    residual={res:.3e}", flush=True)
            except Exception as e:
                print(f"    residual FAILED: {e}", flush=True)
        except Exception as e:  # OOM / VMEM overflow at some configs
            print(f"{cfg_lbl} segs={seg_lbl} tree={tree}: "
                  f"FAILED {e}", flush=True)


if __name__ == "__main__":
    main()
