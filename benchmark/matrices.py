"""Seeded inputs, owned by the benchmark.

A copy of the program's `validation.seeded_entries` hash (fmix32 of
(seed, i, j)): any process, on any device or on the host, regenerates any
entry of any test matrix without the matrix ever being stored, so the
reference regenerates what the timed path was given. The copy lives here
so that the yardstick does not move when the program's generator does.

The device form (jnp) makes the LU cell's 4 GiB matrix on the chip; the
host form (numpy) gives the same bits for the same (seed, i, j) and is
the tests' witness.
"""

from __future__ import annotations

import numpy as np

_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35


def fold_seed(*parts: int) -> int:
    """A 32-bit seed from any whole numbers (the driver's seeds pass 2**31)."""
    return int(np.random.SeedSequence([int(p) for p in parts])
               .generate_state(1, dtype=np.uint32)[0])


def _fmix32_np(h):
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(_M1)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(_M2)
    return h ^ (h >> np.uint32(16))


def _fmix32_jnp(h):
    import jax.numpy as jnp

    h = h ^ (h >> 16)
    h = h * jnp.uint32(_M1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_M2)
    return h ^ (h >> 16)


def uniform_np(rows, cols, n: int, seed32: int) -> np.ndarray:
    """Hash-uniform [-1, 1) float32 at (rows, cols) of an n-column matrix."""
    with np.errstate(over="ignore"):
        r = np.asarray(rows, np.uint32)
        c = np.asarray(cols, np.uint32)
        h = _fmix32_np((r * np.uint32(n) + c)
                       ^ _fmix32_np(np.uint32(seed32)))
    return ((h >> np.uint32(8)).astype(np.float32)
            * np.float32(2.0 ** -23) - np.float32(1.0))


def uniform_jnp(rows, cols, n: int, seed32):
    """`uniform_np` on the device; `seed32` may be a traced uint32."""
    import jax.numpy as jnp

    r = jnp.asarray(rows).astype(jnp.uint32)
    c = jnp.asarray(cols).astype(jnp.uint32)
    h = _fmix32_jnp((r * jnp.uint32(n) + c)
                    ^ _fmix32_jnp(jnp.asarray(seed32, jnp.uint32)))
    return (h >> 8).astype(jnp.float32) * np.float32(2.0 ** -23) - 1.0


# ---- the dense LU cell: uniform[-1, 1) + 2I (the repo's test family) ----

def dense_rows(r0, nrows: int, n: int, seed32):
    """Rows r0..r0+nrows of the dense n x n test matrix, on the device."""
    import jax.numpy as jnp

    rows = r0 + jnp.arange(nrows, dtype=jnp.int32)[:, None]
    cols = jnp.arange(n, dtype=jnp.int32)[None, :]
    return uniform_jnp(rows, cols, n, seed32) + 2.0 * (rows == cols)


def dense_rhs(n: int, seed32):
    """The right-hand side b of one dense step: uniform[-1, 1), (n,)."""
    import jax.numpy as jnp

    i = jnp.arange(n, dtype=jnp.int32)
    return uniform_jnp(i, 0, 1, jnp.asarray(seed32, jnp.uint32)
                       ^ jnp.uint32(0x9E3779B9))
