"""The plain references of the mesh LU cell, owned by the benchmark.

Nothing here imports the program. The block-cyclic layout is the
benchmark's own arithmetic: on a mesh of Px x Py chips, chip (px, py)
holds the v x v tiles (i, j) with i % Px == px and j % Py == py, tile i
at local slot i // Px (rows) and tile j at j // Py (columns), so local
index l on coordinate p is global index ((l // v) * P + p) * v + l % v.
The caller names the mesh axes that carry the rows and the columns; the
residual takes the mesh off the answer under test.

- `shard_generator`: the dense test matrix (`matrices.dense_rows`'
  family, uniform[-1, 1) + 2I from the benchmark's hash at global (i, j))
  as (Px, Py, Ml, Nl) shards, each chip computing its own tiles.
- `lu_factor_residual`: ||A[perm] - L U||_F / ||A||_F of sharded packed
  factors, exact, on the mesh: for each v-row strip of L, the strip is
  gathered from its device row, each chip multiplies it against its own
  tiles of U at HIGHEST, a psum over x completes the strip of L U, and
  A[perm]'s strip is regenerated beside it (2 n^3 flops over the chips;
  peak memory the factors, one masked copy of them and a few strips).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import matrices


def global_index(p, local, v: int, P: int):
    """Global row (column) index of local index `local` on mesh
    coordinate `p` of an axis of P chips, tile size v."""
    return ((local // v) * P + p) * v + local % v


def shard_generator(n: int, v: int, mesh, axes: tuple):
    """A jitted `seed32 -> (Px, Py, Ml, Nl)` array sharded over the
    `axes` = (row axis, column axis) of `mesh`: the shards of the dense
    n x n test matrix of `seed32`, each generated on the chip that holds
    it."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    ax, ay = axes
    Px, Py = mesh.shape[ax], mesh.shape[ay]
    Ml, Nl = n // Px, n // Py

    def mesh_shards(seed32):
        rows = global_index(lax.axis_index(ax),
                            jnp.arange(Ml, dtype=jnp.int32), v, Px)[:, None]
        cols = global_index(lax.axis_index(ay),
                            jnp.arange(Nl, dtype=jnp.int32), v, Py)[None, :]
        u = matrices.uniform_jnp(rows, cols, n, seed32)
        return jnp.where(rows == cols, u + 2.0, u)[None, None]

    return jax.jit(jax.shard_map(mesh_shards, mesh=mesh, in_specs=P(),
                                 out_specs=P(ax, ay, None, None)))


@functools.lru_cache(maxsize=4)
def _mesh_ssq(n: int, v: int, mesh, ax: str, ay: str):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    hi = lax.Precision.HIGHEST
    Px, Py = mesh.shape[ax], mesh.shape[ay]
    Ml, Nl = n // Px, n // Py
    Mtl, Ntl = Ml // v, Nl // v

    def residual_sums(Fb, perm, seed32):
        F = Fb[0, 0].astype(jnp.float32)
        px, py = lax.axis_index(ax), lax.axis_index(ay)
        grow = global_index(px, jnp.arange(Ml, dtype=jnp.int32), v, Px)
        gcol = global_index(py, jnp.arange(Nl, dtype=jnp.int32), v, Py)
        U = jnp.where(grow[:, None] <= gcol[None, :], F, 0.0)  # my U tiles
        cols = jnp.arange(n, dtype=jnp.int32)[None, :]
        first = px == 0  # after the psum over x every x-rank holds the strip

        def strip(s, carry):
            rss, ass = carry
            r0 = s * v
            mine = jnp.where(px == s % Px,
                             lax.dynamic_slice(F, ((s // Px) * v, 0), (v, Nl)),
                             0.0)
            Ls = lax.psum(mine, ax)  # (v, Nl): the strip at my columns
            Lg = (lax.all_gather(Ls, ay)  # (Py, v, Nl) -> global columns
                  .reshape(Py, v, Ntl, v).transpose(1, 2, 0, 3).reshape(v, n))
            ri = (r0 + jnp.arange(v, dtype=jnp.int32))[:, None]
            Lg = jnp.where(ri > cols, Lg, 0.0) + (ri == cols)
            # the strip's columns at the global indices of my U rows
            Lx = lax.dynamic_index_in_dim(Lg.reshape(v, Mtl, Px, v), px,
                                          axis=2, keepdims=False)
            LU = lax.psum(jnp.matmul(Lx.reshape(v, Ml), U, precision=hi), ax)
            pr = lax.dynamic_slice(perm, (r0,), (v,))[:, None]
            u = matrices.uniform_jnp(pr, gcol[None, :], n, seed32)
            Ap = jnp.where(pr == gcol[None, :], u + 2.0, u)
            R = Ap - LU
            return (rss + jnp.where(first, jnp.sum(R * R), 0.0),
                    ass + jnp.where(first, jnp.sum(Ap * Ap), 0.0))

        z = lax.pcast(jnp.zeros((), jnp.float32), (ax, ay), to="varying")
        rss, ass = lax.fori_loop(0, n // v, strip, (z, z))
        return lax.psum(rss, (ax, ay)), lax.psum(ass, (ax, ay))

    return jax.jit(jax.shard_map(
        residual_sums, mesh=mesh, in_specs=(P(ax, ay, None, None), P(), P()),
        out_specs=(P(), P())))


def lu_factor_residual(F, perm, n: int, v: int, seed32: int,
                       axes: tuple) -> float:
    """||A[perm] - L U||_F / ||A||_F for the (Px, Py, Ml, Nl) packed
    factors F, sharded over the `axes` = (row axis, column axis) of their
    mesh, of the dense test matrix of `seed32` (tile size v), with perm
    (n,) the original row at each position."""
    import jax.numpy as jnp

    rss, ass = _mesh_ssq(n, v, F.sharding.mesh, *axes)(
        F, jnp.asarray(perm, jnp.int32), jnp.uint32(seed32))
    return float(np.sqrt(float(rss)) / max(np.sqrt(float(ass)), 1e-30))
