"""The plain references that decide `correct`, owned by the benchmark.

Nothing here imports the program or takes anything it made but the
answers under test. Matrices are regenerated from the seed with the
benchmark's own hash (`matrices`).

Dense LU (one step = factor + solve of A x = b):
- `lu_factor_residual`: ||A[perm] - L U||_F / ||A||_F of the packed
  factors, A[perm]'s rows regenerated strip by strip, L U formed in
  (blk, blk) tiles at HIGHEST precision (2 n^3 flops, peak memory the
  factors plus a few strips).
- `solve_backward_error`: ||A x - b||_inf / (||A||_inf ||x||_inf + ||b||_inf),
  A regenerated in row strips (O(n^2)).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import matrices


@functools.lru_cache(maxsize=4)
def _factor_ssq(n: int, blk: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    nb = n // blk
    cols = jnp.arange(n, dtype=jnp.int32)[None, :]
    tile = jnp.arange(blk, dtype=jnp.int32)

    @jax.jit
    def ssq(F4, perm, seed32):
        F = F4.reshape(F4.shape[-2:]).astype(jnp.float32)

        def row_strip(i, carry):
            rss, ass = carry
            r0 = i * blk
            ri = (r0 + tile)[:, None]
            Fi = lax.dynamic_slice(F, (r0, 0), (blk, n))
            Li = jnp.where(ri > cols, Fi, 0.0) + (ri == cols)
            pi = lax.dynamic_slice(perm, (r0,), (blk,))[:, None]
            Api = (matrices.uniform_jnp(pi, cols, n, seed32)
                   + 2.0 * (pi == cols))

            def col_strip(j, acc):
                c0 = j * blk
                Fj = lax.dynamic_slice(F, (0, c0), (n, blk))
                Uj = jnp.where(cols.T <= (c0 + tile)[None, :], Fj, 0.0)
                return lax.dynamic_update_slice(
                    acc, jnp.matmul(Li, Uj, precision=hi), (0, c0))

            LU = lax.fori_loop(0, nb, col_strip,
                               jnp.zeros((blk, n), jnp.float32))
            R = Api - LU
            return rss + jnp.sum(R * R), ass + jnp.sum(Api * Api)

        z = jnp.zeros((), jnp.float32)
        return lax.fori_loop(0, nb, row_strip, (z, z))

    return ssq


def lu_factor_residual(F4, perm, n: int, seed32: int,
                       blk: int = 4096) -> float:
    """||A[perm] - L U||_F / ||A||_F for packed factors F4 (any leading
    singleton axes) of the dense test matrix of `seed32`."""
    import jax.numpy as jnp

    blk = int(np.gcd(n, blk))
    rss, ass = _factor_ssq(n, blk)(F4, jnp.asarray(perm, jnp.int32),
                                   jnp.uint32(seed32))
    return float(np.sqrt(float(rss)) / max(np.sqrt(float(ass)), 1e-30))


@functools.lru_cache(maxsize=4)
def _backward(n: int, blk: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST

    @jax.jit
    def err(x, seed32):
        b = matrices.dense_rhs(n, seed32)
        x = x.astype(jnp.float32)

        def strip(i, carry):
            rmax, amax = carry
            r0 = i * blk
            Ai = matrices.dense_rows(r0, blk, n, seed32)
            r = (jnp.matmul(Ai, x, precision=hi)
                 - lax.dynamic_slice(b, (r0,), (blk,)))
            return (jnp.maximum(rmax, jnp.max(jnp.abs(r))),
                    jnp.maximum(amax, jnp.max(jnp.sum(jnp.abs(Ai), axis=1))))

        z = jnp.zeros((), jnp.float32)
        rmax, amax = lax.fori_loop(0, n // blk, strip, (z, z))
        return rmax / (amax * jnp.max(jnp.abs(x)) + jnp.max(jnp.abs(b)))

    return err


def solve_backward_error(x, n: int, seed32: int, blk: int = 2048) -> float:
    """Normwise backward error of x for the dense step of `seed32`."""
    import jax.numpy as jnp

    blk = int(np.gcd(n, blk))
    return float(_backward(n, blk)(x, jnp.uint32(seed32)))
