"""The 3D device mesh and the six communicator patterns.

The reference builds one 3D Cartesian communicator plus five sub-communicators
(`lu_params.hpp:84-108`): lu (xyz), jk (yz), ik (xz), ij (xy), k (z), i (x).
On TPU these are not objects — they are *names*: collectives take mesh axis
names, and a "sub-communicator" is just a subset of axes. `comm` maps the
reference's communicator vocabulary onto axis-name tuples so algorithm code
can speak in the same terms the reference does.

| reference comm | axes      | used for                                        |
|----------------|-----------|-------------------------------------------------|
| `lu_comm`      | x, y, z   | whole-grid ops                                  |
| `jk_comm`      | y, z      | panel broadcast / A10 slab scatter              |
| `ik_comm`      | x, z      | pivot-row reduce + distribute / A01 slab scatter|
| `ij_comm`      | x, y      | validation-layout assembly                      |
| `k_comm`       | z         | 2.5D partial-sum reduction                      |
| `i_comm`       | x         | tournament pivoting butterfly                   |
"""

from __future__ import annotations

import numpy as np

import jax

from conflux_tpu.geometry import Grid3

AXIS_X = "x"  # row dimension of the tile grid (Px)
AXIS_Y = "y"  # column dimension of the tile grid (Py)
AXIS_Z = "z"  # 2.5D replication depth (Pz)

shard_map = jax.shard_map  # every shard_map program routes through here


def pvary(val, axes):
    """Mark a literal as varying over mesh `axes` — `lax.pcast`'s
    "varying manual axes" vocabulary, needed so `lax.cond` branch output
    types match mask-dependent compute branches."""
    from jax import lax

    for ax in axes:
        val = lax.pcast(val, ax, to="varying")
    return val

comm = {
    "lu": (AXIS_X, AXIS_Y, AXIS_Z),
    "jk": (AXIS_Y, AXIS_Z),
    "ik": (AXIS_X, AXIS_Z),
    "ij": (AXIS_X, AXIS_Y),
    "k": (AXIS_Z,),
    "i": (AXIS_X,),
}


_MESH_REGISTRY: dict = {}


def mesh_cache_key(mesh: jax.sharding.Mesh):
    """Hashable identity for a mesh, and register it for `lookup_mesh`.

    Compiled program builders are lru_cached on geometry + this key; keying
    by (device ids, axis names) means two equivalent Mesh objects share one
    compiled program, and the registry holds one canonical mesh per key
    (bounded by the number of distinct device layouts, so no growth over
    repeated calls).
    """
    key = (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)
    _MESH_REGISTRY[key] = mesh
    return key


def lookup_mesh(key) -> jax.sharding.Mesh:
    return _MESH_REGISTRY[key]


def initialize_multihost(coordinator_address: str | None = None,
                         num_processes: int | None = None,
                         process_id: int | None = None, **kwargs) -> None:
    """Bring up the multi-host runtime — the role of `MPI_Init`
    (`examples/conflux_miniapp.cpp:90`) for TPU pods.

    Call once per host process before any mesh/array work;
    `jax.distributed.initialize` discovers the coordinator automatically on
    Cloud TPU (all arguments optional there). After this, `jax.devices()`
    spans every host's chips and `make_mesh` builds pod-wide meshes; the
    collectives in the factorization loops ride ICI within a slice and DCN
    across slices without code changes.
    """
    jax.distributed.initialize(coordinator_address, num_processes,
                               process_id, **kwargs)


def distribute_shards(shards, mesh: jax.sharding.Mesh, *,
                      shape: tuple | None = None, dtype=None,
                      spec=None) -> jax.Array:
    """Build the (Px, Py, Ml, Nl) device-sharded global array from host data.

    Two forms:

    - `shards` is the full (Px, Py, Ml, Nl) host array: single-host
      convenience, equivalent to a device_put with the block-cyclic sharding
      (every process must hold the whole thing — fine on one host).
    - `shards` is a callable `(px, py) -> (Ml, Nl) ndarray` and
      `shape`/`dtype` give the global spec: it is invoked only for the
      shards owned by THIS process's addressable devices, so on a multi-host
      pod no host ever materializes the global matrix — the role of the
      reference's per-rank `InitMatrix` fill (`lu_params.hpp:141-376`).

    `spec` overrides the default block-cyclic (x, y, None, None)
    partitioning — e.g. PartitionSpec('x', None, None) for the QR
    family's (Px, Ml, n) row-block shards; the callable then takes one
    coordinate per sharded dimension.
    """
    from jax.sharding import PartitionSpec

    if spec is None:
        spec = PartitionSpec(AXIS_X, AXIS_Y, None, None)
    sharding = jax.sharding.NamedSharding(mesh, spec)
    # dims carrying a mesh axis must be leading index dims (size == axis
    # extent), so a shard's slice start IS its mesh coordinate — true for
    # both supported layouts: block-cyclic (Px, Py, Ml, Nl) and the QR
    # family's row-block (Px, Ml, n)
    sharded_dims = [i for i, ax in enumerate(spec) if ax is not None]
    if callable(shards):
        if shape is None or dtype is None:
            raise ValueError("callable form requires shape= and dtype=")

        def cb(idx):
            coords = tuple(idx[i].start or 0 for i in sharded_dims)
            blk = np.asarray(shards(*coords), dtype=dtype)
            return blk[(None,) * len(sharded_dims)]

        return jax.make_array_from_callback(tuple(shape), sharding, cb)
    shards = np.asarray(shards)
    return jax.make_array_from_callback(
        shards.shape, sharding, lambda idx: shards[idx]
    )


def replicate(val, axes):
    """Re-establish replication over mesh axes for an already-identical
    value (the out_spec replication proof, see the LU loop's perm
    output). pmax is the cheapest identity-preserving collective but has
    no complex reduction on any backend, so complex values ride as their
    real/imag parts."""
    import jax.numpy as jnp
    from jax import lax

    if jnp.issubdtype(val.dtype, jnp.complexfloating):
        return lax.complex(lax.pmax(val.real, axes),
                           lax.pmax(val.imag, axes)).astype(val.dtype)
    return lax.pmax(val, axes)


def butterfly_allreduce(vals: tuple, Px: int, axis: str, reduce_pair):
    """Hypercube all-reduce over a mesh axis (the reference's tournament
    butterfly shape, `conflux_opt.hpp:220-336`): each round, partners
    exchange `vals` via ppermute and `reduce_pair(top, bot)` combines
    the two tuples into the next `vals`.

    The correctness-critical invariant lives here ONCE: the pair is
    ordered by the LOWER coordinate, so both partners reduce the
    bit-identical inputs and the result converges replicated across the
    axis without a broadcast (tie-stable for order-dependent reducers
    like the CALU tournament).

    Non-power-of-two Px is handled the way the reference patches odd
    grids with compensating sends (`conflux_opt.hpp:266-280`, partner
    math `conflux_opt.cpp:59-72`), recast for SPMD: with p the largest
    power of two <= Px and r = Px - p, a pre-round folds each overflow
    rank p+i into rank i (i < r), the log2(p) butterfly runs over the
    [0, p) subcube, and a post-round sends the replicated result back to
    the overflow ranks — 2 extra ppermute rounds total, still only one
    `vals` payload per rank per round. All ranks execute every round
    (SPMD); the off-subcube reductions operate on ppermute's zero fill
    and are discarded by coordinate selects, so reducers must tolerate
    (not crash on) all-zero inputs — true of the CALU/TSQR reducers,
    whose zero-stack factorizations are well-defined garbage.

    The exchanges run in a `comm_butterfly` named scope, so a trace tells
    them from the reducer's compute.
    """
    import jax.numpy as jnp
    from jax import lax

    x = lax.axis_index(axis)
    p = 1 << (Px.bit_length() - 1)  # largest power of two <= Px
    r = Px - p
    if r:
        # fold: overflow rank p+i's contribution joins rank i's, ordered
        # by the lower coordinate (rank i's own vals first)
        perm = [(p + i, i) for i in range(r)]
        with jax.named_scope("comm_butterfly"):
            recv = tuple(lax.ppermute(v, axis, perm) for v in vals)
        folded = tuple(reduce_pair(vals, recv))
        vals = tuple(jnp.where(x < r, f, v)
                     for f, v in zip(folded, vals))
    for rnd in range(p.bit_length() - 1):
        bit = 1 << rnd
        perm = [(i, i ^ bit) for i in range(p)]
        with jax.named_scope("comm_butterfly"):
            others = tuple(lax.ppermute(v, axis, perm) for v in vals)
        low_first = (x & bit) == 0
        top = tuple(jnp.where(low_first, a, b)
                    for a, b in zip(vals, others))
        bot = tuple(jnp.where(low_first, b, a)
                    for a, b in zip(vals, others))
        vals = tuple(reduce_pair(top, bot))
    if r:
        # unfold: the subcube result is replicated over [0, p); hand the
        # overflow ranks their copy
        perm = [(i, p + i) for i in range(r)]
        with jax.named_scope("comm_butterfly"):
            recv = tuple(lax.ppermute(v, axis, perm) for v in vals)
        vals = tuple(jnp.where(x >= p, o, v)
                     for o, v in zip(recv, vals))
    return vals


def make_mesh(grid: Grid3, devices=None) -> jax.sharding.Mesh:
    """Build the ('x', 'y', 'z') mesh for a Grid3.

    On real hardware, axis order matters for ICI locality: jax.make_mesh
    chooses a device assignment that keeps the fastest-varying axes on
    physically adjacent chips. For tests, pass an explicit device list.
    """
    if devices is None:
        return jax.make_mesh((grid.Px, grid.Py, grid.Pz), (AXIS_X, AXIS_Y, AXIS_Z))
    devs = np.asarray(devices).reshape(grid.Px, grid.Py, grid.Pz)
    return jax.sharding.Mesh(devs, (AXIS_X, AXIS_Y, AXIS_Z))
