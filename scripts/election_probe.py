"""On-chip cost model for the pivot-election building blocks.

The round-2 phase table says step1_pivoting is 31.9% of wall at N=32768
(713.9 ms over 32 supersteps) while its flops are negligible — the cost is
the XLA LU custom call's serial column sweep, i.e. per-CALL latency, not
arithmetic. This probe measures, inside ONE jitted fori_loop per config
(no per-call dispatch in the measurement):

  1. single (m, v) LU calls across heights — the nomination primitive;
  2. batched (b, c, v) LU calls — the batched-nomination alternative;
  3. full tournament_winners variants at the bench panel shape
     (Ml=32768, v=1024): chunk x {pairwise, flat} trees.

Each measurement reports ms/iteration; the loop carries a data dependence
(input perturbed by the previous output) so XLA cannot hoist or elide the
calls. Writes one line per config; run on a healthy chip:

    python scripts/election_probe.py [--reps 8]

The full matrix is long (it took over 40 minutes in round 5); run it
alone, inside the chip tool's time limit.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--m", type=int, default=32768,
                    help="full panel height for the tournament variants")
    ap.add_argument("--v", type=int, default=1024)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from conflux_tpu import cache
    from conflux_tpu.cli.common import require_tpu
    from conflux_tpu.ops import blas

    require_tpu()
    cache.enable_persistent_cache()
    reps = args.reps
    v = args.v

    def timed(label, make_input, step):
        """ms per `step` application, measured as one jitted fori_loop of
        `reps` data-dependent applications (minus a 1-iteration loop to
        cancel the fixed dispatch+sync overhead)."""

        def loop(n):
            @jax.jit
            def f(x):
                def body(i, x):
                    out = step(x)
                    # fold a scalar of the output back in: keeps a true
                    # data dependence at ~zero cost; the perturbation is
                    # at f32 epsilon scale so pivot paths stay realistic
                    return x * (1.0 + 1e-12 * out)
                return lax.fori_loop(0, n, body, x)
            return f

        x = make_input()
        f_full, f_one = loop(reps), loop(1)
        r = f_full(x)
        float(r[(0,) * r.ndim])  # compile + warm
        r = f_one(x)
        float(r[(0,) * r.ndim])
        t0 = time.time()
        r = f_one(x)
        float(r[(0,) * r.ndim])
        t_one = time.time() - t0
        t0 = time.time()
        r = f_full(x)
        float(r[(0,) * r.ndim])
        t_full = time.time() - t0
        ms = (t_full - t_one) / (reps - 1) * 1e3
        print(f"{label}: {ms:.2f} ms/iter", flush=True)

    def make(shape):
        def gen():
            key = jax.random.PRNGKey(0)
            return jax.random.normal(key, shape, jnp.float32)
        return gen

    # 1. single-call heights: the latency model a + b*m
    for m in (1024, 2048, 4096, 8192, 12288):
        timed(f"lu single ({m},{v})", make((m, v)),
              lambda p: lax.linalg.lu(p)[0][0, 0])

    # 2. batched calls: does batching amortize the per-call latency?
    for b, c in ((2, 2048), (4, 2048), (2, 4096), (4, 4096), (8, 4096),
                 (2, 8192)):
        try:
            timed(f"lu batched ({b}x{c},{v})", make((b, c, v)),
                  lambda p: lax.linalg.lu(p)[0][0, 0, 0])
        except Exception as e:
            print(f"lu batched ({b}x{c},{v}): FAILED {type(e).__name__}",
                  flush=True)

    # 3. full election variants at the bench shape (all rows live = the
    # worst-case step; liveness only shrinks these numbers)
    m_full = args.m
    for chunk in (8192, 12288):
        for tree in ("pairwise", "flat"):
            c_h, nch = blas.chunk_layout(m_full, v, chunk)
            if tree == "flat" and nch * v > 8192:
                continue

            def elect(p, chunk=chunk, tree=tree):
                lu00, wid = blas.tournament_winners(p, chunk=chunk,
                                                    tree=tree)
                return lu00[0, 0]

            try:
                timed(f"election m={m_full} chunk={chunk} tree={tree} "
                      f"(nch={nch})", make((m_full, v)), elect)
            except Exception as e:
                print(f"election chunk={chunk} tree={tree}: FAILED "
                      f"{type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main()
