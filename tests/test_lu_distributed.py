"""Distributed LU on a simulated multi-device CPU mesh.

Covers the reference's multi-rank correctness strategy (SURVEY.md §4): the
residual oracle ||PA - LU||_F on small deterministic matrices, across the
grid shapes the algorithm must handle (1D, 2D, 2.5D with z replication).
"""

import numpy as np
import pytest

from conflux_tpu.geometry import Grid3
from conflux_tpu.lu.distributed import lu_distributed_host
from conflux_tpu.validation import lu_residual, make_test_matrix, residual_bound


GRIDS = [
    Grid3(1, 1, 1),
    Grid3(2, 1, 1),
    Grid3(1, 2, 1),
    Grid3(2, 2, 1),
    Grid3(1, 1, 2),
    Grid3(2, 2, 2),
    Grid3(4, 2, 1),
    Grid3(2, 2, 1),
]


@pytest.mark.parametrize("grid", GRIDS, ids=str)
def test_lu_distributed_residual(grid):
    N, v = 64, 8
    A = make_test_matrix(N, N, seed=grid.P + grid.Px)
    LU, perm, geom = lu_distributed_host(A, grid, v)
    assert geom.M == N
    res = lu_residual(A, LU[perm], perm)
    assert res < residual_bound(N, np.float64), (grid, res)


def test_lu_distributed_matches_single_device():
    """Same matrix, different grids -> same residual-level factorization."""
    N, v = 32, 8
    A = make_test_matrix(N, N, seed=77)
    LU1, perm1, _ = lu_distributed_host(A, Grid3(1, 1, 1), v)
    LU2, perm2, _ = lu_distributed_host(A, Grid3(2, 2, 2), v)
    # pivot choices can differ only by value ties; residuals must both be tiny
    assert lu_residual(A, LU1[perm1], perm1) < residual_bound(N, np.float64)
    assert lu_residual(A, LU2[perm2], perm2) < residual_bound(N, np.float64)


def test_lu_distributed_padding():
    """Non-divisible N exercises the identity-padded corner."""
    N, v = 50, 8
    A = make_test_matrix(N, N, seed=5)
    LU, perm, geom = lu_distributed_host(A, Grid3(2, 2, 1), v)
    assert geom.M == 64
    Ap = np.eye(geom.M, dtype=A.dtype)
    Ap[:N, :N] = A
    res = lu_residual(Ap, LU[perm], perm)
    assert res < residual_bound(geom.M, np.float64)


def test_lu_distributed_chunked_election():
    """Ml larger than the panel chunk: the local nomination must run the
    chunked tournament (multiple chunks + reduction tree), and the cross-x
    election tree must handle Px·v taller than one chunk — the scaling
    regime the production grids in BASELINE.md hit (Ml = N/Px >> chunk)."""
    N, v = 128, 8
    A = make_test_matrix(N, N, seed=31)
    for grid in (Grid3(2, 2, 1), Grid3(4, 2, 1)):
        # Ml = N/Px = 64 or 32; chunk=16 forces 4+/2+ chunks locally and a
        # (Px*v=32 or 16, v) election through the same chunked tree
        LU, perm, _ = lu_distributed_host(A, grid, v, panel_chunk=16)
        res = lu_residual(A, LU[perm], perm)
        assert res < residual_bound(N, np.float64), (grid, res)
        assert sorted(perm.tolist()) == list(range(N))


def test_lu_distributed_bench_ratios():
    """Structural pin of the lu_n32768 benchmark cell's config (N=32768,
    v=1024, chunk=8192 on 1x1x1) at 1/128 scale: the same N/v = 32
    supersteps and Ml/chunk = 4 nomination chunks, through the same
    single-device mesh program. Small-N grid tests can't see bugs that
    need many supersteps of live/dead segment transitions or a
    multi-chunk nomination on one device; this shape does."""
    N, v = 256, 8
    A = make_test_matrix(N, N, seed=2, dtype=np.float32)
    LU, perm, _ = lu_distributed_host(A, Grid3(1, 1, 1), v, panel_chunk=64)
    assert sorted(perm.tolist()) == list(range(N))
    res = lu_residual(A, LU[perm], perm)
    # the f32 factor's 1e-5 bar, tighter than the generic bound here
    assert res < min(residual_bound(N, np.float32), 1e-5), res


def test_lu_distributed_election_height_bound():
    """Structural guarantee: NO lu primitive in the traced distributed
    program is taller than max(panel_chunk, 2v) — the scoped-VMEM safety
    contract of the TPU LU custom call (ops/blas.py). This is what the
    reference's log-depth butterfly provides (`conflux_opt.hpp:220-336`:
    every factorization is at most 2v rows)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import _build
    from conflux_tpu.parallel.mesh import make_mesh, mesh_cache_key

    grid = Grid3(4, 2, 1)
    v, chunk = 8, 16
    geom = LUGeometry.create(256, 256, v, grid)
    mesh = make_mesh(grid, devices=jax.devices()[: grid.P])

    def walk(jx, heights):
        for eqn in jx.eqns:
            if eqn.primitive.name == "lu":
                heights.append(eqn.invars[0].aval.shape[-2])
            for p in eqn.params.values():
                for q in (p if isinstance(p, (list, tuple)) else [p]):
                    if hasattr(q, "eqns"):
                        walk(q, heights)
                    elif hasattr(q, "jaxpr"):
                        walk(q.jaxpr, heights)

    for election in ("gather", "butterfly"):
        fn = _build(geom, mesh_cache_key(mesh), lax.Precision.HIGHEST,
                    "xla", chunk, election=election)
        jaxpr = jax.make_jaxpr(fn)(jnp.zeros((4, 2, geom.Ml, geom.Nl)))
        heights = []
        walk(jaxpr.jaxpr, heights)
        assert heights, "expected lu primitives in the traced program"
        assert max(heights) <= max(chunk, 2 * v), (election, heights)


def test_lu_distributed_chunked_matches_unchunked():
    """Chunk size changes pivot *order* only within tournament ties; the
    factorization must stay residual-correct and a pure permutation."""
    N, v = 64, 8
    A = make_test_matrix(N, N, seed=41)
    for chunk in (8, 16, 4096):
        LU, perm, _ = lu_distributed_host(A, Grid3(2, 1, 1), v,
                                          panel_chunk=chunk)
        res = lu_residual(A, LU[perm], perm)
        assert res < residual_bound(N, np.float64), (chunk, res)


def test_lu_distributed_flat_tree():
    """The flat election tree (one stacked LU instead of the pairwise
    reduction tree — fewer sequential latency-bound custom calls on TPU)
    is a valid CALU election: correct residual, pure permutation, across
    the chunked single-rank path (Px=1, multi-chunk nomination), the
    cross-x gather election, and rectangular shapes."""
    N, v = 128, 8
    A = make_test_matrix(N, N, seed=13)
    for grid in (Grid3(1, 1, 1), Grid3(2, 2, 1), Grid3(4, 2, 1)):
        LU, perm, _ = lu_distributed_host(A, grid, v, panel_chunk=16,
                                          tree="flat")
        res = lu_residual(A, LU[perm], perm)
        assert res < residual_bound(N, np.float64), (grid, res)
        assert sorted(perm.tolist()) == list(range(N))
    # bench-shape ratios (32 supersteps, 4 nomination chunks) as in
    # test_lu_distributed_bench_ratios, now through the flat tree
    N2 = 256
    A2 = make_test_matrix(N2, N2, seed=2, dtype=np.float32)
    LU, perm, _ = lu_distributed_host(A2, Grid3(1, 1, 1), v, panel_chunk=64,
                                      tree="flat")
    assert sorted(perm.tolist()) == list(range(N2))
    assert lu_residual(A2, LU[perm], perm) < residual_bound(N2, np.float32)


def test_lu_flat_tree_vmem_guard():
    """tree='flat' must refuse configurations whose nominee stack exceeds
    the single-call VMEM-safe height instead of failing at compile time
    on the chip."""
    import jax

    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import build_program
    from conflux_tpu.parallel.mesh import make_mesh

    grid = Grid3(1, 1, 1)
    geom = LUGeometry.create(32768, 32768, 1024, grid)
    mesh = make_mesh(grid, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="flat"):
        build_program(geom, mesh, panel_chunk=2048, tree="flat")
    with pytest.raises(ValueError, match="tree"):
        build_program(geom, mesh, tree="bogus")


def test_lu_flat_tree_vmem_guard_dtype_aware():
    """The flat-tree guard must evaluate with the COMPUTE dtype's chunk
    ceilings: an f64 run's single-call-safe height is half f32's, so a
    config that passes for f32 can be unbuildable for f64 (ADVICE r3).
    panel_chunk=4096 at Ml=32768/v=1024 stacks 8 nominees = 8192 rows:
    exactly the f32 ceiling (passes), double the f64 one (must raise)."""
    import jax

    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import build_program
    from conflux_tpu.ops import blas
    from conflux_tpu.parallel.mesh import make_mesh

    if blas.scoped_vmem_bytes() != blas._MODEL_VMEM_BYTES:
        pytest.skip("scoped-VMEM override active; pinned heights differ")
    grid = Grid3(1, 1, 1)
    geom = LUGeometry.create(32768, 32768, 1024, grid)
    mesh = make_mesh(grid, devices=jax.devices()[:1])
    # passes with f32 compute (stack == the 8192-row f32 ceiling) ...
    build_program(geom, mesh, panel_chunk=4096, tree="flat",
                  dtype=np.float32)
    # ... and must refuse the same stack for f64 compute, naming the dtype
    with pytest.raises(ValueError, match="float64"):
        build_program(geom, mesh, panel_chunk=4096, tree="flat",
                      dtype=np.float64)


def test_lu_build_program_dtype_resolves_default_chunk():
    """build_program(dtype=...) must resolve the same default panel_chunk
    as lu_factor_distributed does from its shards, so a --profile build
    returns the SAME cached program the timed run used (ADVICE r3: the
    dtype-blind default built and profiled a different f64 program)."""
    import jax

    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import build_program
    from conflux_tpu.ops import blas
    from conflux_tpu.parallel.mesh import make_mesh

    grid = Grid3(1, 1, 1)
    geom = LUGeometry.create(256, 256, 64, grid)
    mesh = make_mesh(grid, devices=jax.devices()[:1])
    for dt in (np.float32, np.float64):
        explicit = build_program(
            geom, mesh,
            panel_chunk=blas.single_call_rows(64, blas.compute_dtype(dt)))
        assert build_program(geom, mesh, dtype=dt) is explicit


def test_lu_distributed_segs_invariant():
    """Trailing-update segmentation partitions the same per-element math:
    any (row, col) segment counts — coarse, odd/ragged, tile-granular —
    must produce the same permutation and a correct factorization."""
    N, v = 64, 8
    A = make_test_matrix(N, N, seed=9)
    base = None
    for segs in [(4, 8), (1, 1), (3, 5), (16, 16)]:
        LU, perm, _ = lu_distributed_host(A, Grid3(2, 2, 2), v, segs=segs)
        res = lu_residual(A, LU[perm], perm)
        assert res < residual_bound(N, np.float64), (segs, res)
        if base is None:
            base = perm
        else:
            np.testing.assert_array_equal(base, perm)


@pytest.mark.parametrize("grid", [Grid3(2, 2, 1), Grid3(4, 2, 1)], ids=str)
@pytest.mark.parametrize("shape", [(64, 32), (32, 64)], ids=["tall", "wide"])
def test_lu_distributed_rectangular(shape, grid):
    """M = 2N and N = 2M (reference `lu_params.hpp:21-47` supports ratio-
    driven rectangular problems; round 1 never tested them distributed)."""
    M, N = shape
    A = make_test_matrix(M, N, seed=M + grid.Px)
    LU, perm, geom = lu_distributed_host(A, grid, 8)
    assert (geom.M, geom.N) == (M, N)
    assert sorted(perm.tolist()) == list(range(M))
    res = lu_residual(A, LU[perm], perm)
    assert res < residual_bound(max(M, N), np.float64), (shape, grid, res)


def test_choose_grid_ratio():
    """Grid auto-pick follows the reference's semantics
    (`lu_params.hpp:21-47`): the 2D plane is stretched toward the matrix
    aspect ratio max(M,N)/min(M,N), orientation-agnostic, Px >= Py >= Pz."""
    from conflux_tpu.geometry import choose_grid

    g = choose_grid(8, 2048, 1024)  # ratio 2
    assert (g.Px, g.Py) == (4, 2), g
    assert choose_grid(8, 1024, 2048) == g  # max/min, like the reference
    g16 = choose_grid(16, 4096, 1024)  # ratio 4
    assert (g16.Px, g16.Py) == (8, 2), g16
    sq = choose_grid(16, 4096, 4096)
    assert sq.Px == sq.Py, sq
    for g in (choose_grid(P, 4096, 1024) for P in (2, 4, 8, 12, 24)):
        assert g.Px >= g.Py >= g.Pz, g


def test_lu_distributed_pivots_are_permutation():
    N, v = 64, 8
    A = make_test_matrix(N, N, seed=9)
    _, perm, _ = lu_distributed_host(A, Grid3(2, 2, 1), v)
    assert sorted(perm.tolist()) == list(range(N))


def test_lu_distributed_needs_pivoting():
    """Zero leading diagonal forces cross-rank pivot movement."""
    N, v = 32, 8
    A = make_test_matrix(N, N, seed=13)
    A[0, 0] = 0.0
    A[9, 9] = 0.0  # row owned by x-rank 1 under 2x2
    LU, perm, _ = lu_distributed_host(A, Grid3(2, 2, 1), v)
    assert np.isfinite(LU).all()
    assert lu_residual(A, LU[perm], perm) < residual_bound(N, np.float64)


def test_lu_distributed_f32():
    N, v = 64, 16
    A = make_test_matrix(N, N, seed=21, dtype=np.float32)
    LU, perm, _ = lu_distributed_host(A, Grid3(2, 2, 1), v)
    assert LU.dtype == np.float32
    assert lu_residual(A, LU[perm], perm) < residual_bound(N, np.float32)


def test_lu_distributed_bf16():
    """bf16 storage with f32 panel math: residual at bf16-eps scale."""
    import jax.numpy as jnp
    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh
    import jax

    N, v = 64, 16
    grid = Grid3(2, 2, 1)
    A = make_test_matrix(N, N, seed=3, dtype=np.float32)
    geom = LUGeometry.create(N, N, v, grid)
    mesh = make_mesh(grid, devices=jax.devices()[: grid.P])
    shards = jnp.asarray(geom.scatter(A)).astype(jnp.bfloat16)
    out, perm = lu_factor_distributed(shards, geom, mesh)
    assert out.dtype == jnp.bfloat16
    LUp = geom.gather(np.asarray(out, dtype=np.float64))
    perm = np.asarray(perm)
    res = lu_residual(A, LUp, perm)
    # bf16 eps is ~7.8e-3: accept c*eps*sqrt(N) with modest pivot-growth
    # headroom, reject the f32 regime from below
    eps = 2.0 ** -7
    assert res < 0.5 * eps * np.sqrt(N), res
    assert res > 1e-6  # and it genuinely ran in bf16, not f32


def test_distribute_shards_multihost_entry():
    """`distribute_shards` (the multi-host array-construction entry point)
    must produce shards the factorization consumes identically to a plain
    device_put — single-host semantics of jax.make_array_from_callback."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import distribute_shards, make_mesh
    from conflux_tpu.validation import make_test_matrix

    grid = Grid3(2, 2, 2)
    geom = LUGeometry.create(32, 32, 8, grid)
    mesh = make_mesh(grid, devices=jax.devices()[:8])
    A = make_test_matrix(32, 32, seed=3)
    shards = geom.scatter(A)

    arr = distribute_shards(shards, mesh)
    assert isinstance(arr, jax.Array)
    out_a, piv_a = lu_factor_distributed(arr, geom, mesh)
    out_b, piv_b = lu_factor_distributed(jnp.asarray(shards), geom, mesh)
    np.testing.assert_array_equal(np.asarray(piv_a), np.asarray(piv_b))
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b), rtol=0, atol=0)


def test_distribute_shards_callable_form():
    """Callable form: only per-shard data is requested (the multi-host
    per-rank fill); result must equal the full-array form."""
    import jax
    import numpy as np

    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.parallel.mesh import distribute_shards, make_mesh
    from conflux_tpu.validation import make_test_matrix

    grid = Grid3(2, 2, 2)
    geom = LUGeometry.create(32, 32, 8, grid)
    mesh = make_mesh(grid, devices=jax.devices()[:8])
    A = make_test_matrix(32, 32, seed=4)
    shards = geom.scatter(A)

    calls = []

    def fill(px, py):
        calls.append((px, py))
        return shards[px, py]

    arr = distribute_shards(fill, mesh, shape=shards.shape, dtype=shards.dtype)
    np.testing.assert_array_equal(np.asarray(arr), shards)
    assert set(calls) <= {(px, py) for px in range(2) for py in range(2)}


def test_lu_residual_distributed_matches_host():
    """The on-mesh residual oracle must agree with the host oracle."""
    import jax
    import jax.numpy as jnp

    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh
    from conflux_tpu.validation import lu_residual_distributed

    N, v = 64, 8
    for grid in (Grid3(2, 2, 1), Grid3(2, 2, 2), Grid3(4, 2, 1)):
        geom = LUGeometry.create(N, N, v, grid)
        mesh = make_mesh(grid, devices=__import__("jax").devices()[: grid.P])
        A = make_test_matrix(N, N, seed=grid.P)
        A_shards = jnp.asarray(geom.scatter(A))
        out, perm = lu_factor_distributed(A_shards, geom, mesh)
        res_mesh = lu_residual_distributed(A_shards, out, perm, geom, mesh)
        LUp = geom.gather(np.asarray(out))
        res_host = lu_residual(A, LUp, np.asarray(perm))
        assert abs(res_mesh - res_host) < 1e-12 + 0.05 * res_host, (
            grid, res_mesh, res_host)
        assert res_mesh < residual_bound(N, np.float64)


def test_lu_residual_distributed_detects_corruption():
    """The oracle must actually look at the factors: corrupting one tile
    must blow the residual up."""
    import jax.numpy as jnp

    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh
    from conflux_tpu.validation import lu_residual_distributed

    N, v = 32, 8
    grid = Grid3(2, 2, 1)
    geom = LUGeometry.create(N, N, v, grid)
    mesh = make_mesh(grid, devices=__import__("jax").devices()[: grid.P])
    A = make_test_matrix(N, N, seed=5)
    A_shards = jnp.asarray(geom.scatter(A))
    out, perm = lu_factor_distributed(A_shards, geom, mesh)
    bad = np.array(out)  # writable copy
    bad[0, 0, :4, :4] += 7.0
    res = lu_residual_distributed(A_shards, jnp.asarray(bad), perm, geom, mesh)
    assert res > 1e-2


@pytest.mark.skipif(
    not __import__("os").environ.get("CONFLUX_SLOW_TESTS"),
    reason="~4 min at-scale run; set CONFLUX_SLOW_TESTS=1 to enable",
)
def test_lu_residual_distributed_at_scale():
    """VERDICT round-1 item 6 'done' bar: validation at N=16384 on the
    8-device CPU mesh without materializing (M, N) on the host — every
    host/device array in the flow is a shard or a scalar."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import AXIS_X, AXIS_Y, make_mesh
    from conflux_tpu.validation import lu_residual_distributed

    N, v = 16384, 256
    grid = Grid3(4, 2, 1)
    geom = LUGeometry.create(N, N, v, grid)
    mesh = make_mesh(grid, devices=jax.devices()[: grid.P])
    sh = NamedSharding(mesh, P(AXIS_X, AXIS_Y, None, None))

    @jax.jit
    def make_shards():
        # deterministic shards generated directly in block-cyclic form
        a = jax.random.normal(jax.random.PRNGKey(0),
                              (N, N), jnp.float32)
        a = a + 2 * jnp.eye(N, dtype=jnp.float32)
        return jnp.asarray(geom.scatter_blocks(a))

    A_shards = jax.device_put(make_shards(), sh)
    out, perm = lu_factor_distributed(A_shards, geom, mesh)
    res = lu_residual_distributed(A_shards, out, perm, geom, mesh)
    assert res < 1e-3, res


def test_lu_distributed_rank_deficient_leading_block_valid():
    """The documented degenerate contract (`lu_factor_distributed`): once a
    superstep's candidates are exactly zero, that block's outputs are
    unspecified — but everything eliminated BEFORE the degeneracy must be
    correct and frozen. A = blockdiag(B, 0) goes degenerate exactly at
    step r/v; the first r positions must still reconstruct A's rows."""
    import jax

    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh
    import jax.numpy as jnp

    grid = Grid3(2, 2, 1)
    v, r, N = 8, 16, 32  # B is (r, r); trailing (N-r) block is zero
    rng = np.random.default_rng(11)
    A = np.zeros((N, N), np.float32)
    A[:r, :r] = (rng.standard_normal((r, r)) + 2 * np.eye(r)).astype(np.float32)
    geom = LUGeometry.create(N, N, v, grid)
    mesh = make_mesh(grid, devices=jax.devices()[: grid.P])
    out, perm = lu_factor_distributed(jnp.asarray(geom.scatter(A)), geom, mesh)
    LUp = geom.gather(np.asarray(out))
    p = np.asarray(perm)
    # valid prefix: positions < r hold frozen factor rows of A[p[:r]]
    L = np.tril(LUp, -1) + np.eye(N, dtype=np.float64)
    U = np.triu(LUp).astype(np.float64)
    lead = (L[:r, :r] @ U[:r, :]).astype(np.float64)
    num = np.linalg.norm(A[p[:r]] - lead)
    assert num / np.linalg.norm(A) < 1e-5, num
    # and those perm entries name distinct rows of the nonzero block
    assert sorted(p[:r]) == list(range(r))


@pytest.mark.parametrize("gridspec", [(1, 1, 1), (2, 2, 1), (2, 2, 2),
                                      (4, 2, 1)])
def test_lu_distributed_lookahead_bitwise_equal(gridspec):
    """The software-pipelined (lookahead) loop must be bitwise identical
    to the plain loop: the carried panel is computed from the same
    operands with the same contraction depth as the recomputed one."""
    import jax
    import jax.numpy as jnp

    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh

    grid = Grid3(*gridspec)
    v, N = 8, 64
    geom = LUGeometry.create(N, N, v, grid)
    mesh = make_mesh(grid, devices=jax.devices()[: grid.P])
    A = make_test_matrix(N, N, dtype=np.float32)
    shards = jnp.asarray(geom.scatter(A))

    out_a, perm_a = lu_factor_distributed(shards, geom, mesh)
    out_b, perm_b = lu_factor_distributed(shards, geom, mesh,
                                          lookahead=True)
    np.testing.assert_array_equal(np.asarray(perm_a), np.asarray(perm_b))
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               rtol=0, atol=0)


def test_lu_distributed_butterfly_election():
    """The ppermute hypercube election (reference `conflux_opt.hpp:220-336`
    structure: log2(Px) rounds of (2v, v) reductions) must produce a
    residual-correct factorization with a valid permutation — also under
    lookahead (the miniapp exposes the combination) and on
    non-power-of-two Px, where the overflow ranks fold in/out of the
    subcube (the reference's odd-grid compensating sends,
    `conflux_opt.hpp:266-280`). CALU pivot sets are bracket-dependent,
    so butterfly and gather may elect different, equally valid pivots."""
    import jax
    import jax.numpy as jnp

    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh

    N, v = 128, 8
    A = make_test_matrix(N, N, seed=97)
    for gridspec, la in [((2, 2, 1), False), ((4, 2, 1), False),
                         ((2, 1, 2), False), ((4, 2, 1), True),
                         ((3, 1, 1), False), ((3, 2, 1), False),
                         ((5, 1, 1), False), ((3, 2, 1), True)]:
        grid = Grid3(*gridspec)
        geom = LUGeometry.create(N, N, v, grid)
        mesh = make_mesh(grid, devices=jax.devices()[: grid.P])
        host_shards = geom.scatter(A)
        # odd grids pad (e.g. Px=3: M 128 -> 144 with an identity tail);
        # validate the padded problem the kernel actually factors
        Ap = geom.gather(host_shards)
        shards = jnp.asarray(host_shards)
        out, perm = lu_factor_distributed(shards, geom, mesh,
                                          election="butterfly",
                                          lookahead=la)
        perm = np.asarray(perm)
        assert sorted(perm.tolist()) == list(range(geom.M)), (gridspec, la)
        LUp = geom.gather(np.asarray(out))
        res = lu_residual(Ap, LUp, perm)
        assert res < residual_bound(N, np.float64), (gridspec, la, res)
        res_g = None
        if not la:
            out_g, perm_g = lu_factor_distributed(shards, geom, mesh)
            res_g = lu_residual(Ap, geom.gather(np.asarray(out_g)),
                                np.asarray(perm_g))
            assert res_g < residual_bound(N, np.float64), (gridspec, res_g)


@pytest.mark.parametrize("grid", [Grid3(1, 1, 1), Grid3(2, 2, 1),
                                  Grid3(2, 2, 2), Grid3(4, 2, 1)], ids=str)
def test_lu_distributed_block_update(grid):
    """update='block' (one lax.switch live-suffix GEMM per step instead of
    the cond'd segment lattice) partitions the same per-element math:
    same pivots, residual-correct factors, across grids incl. 2.5D and
    many-superstep shapes."""
    import jax
    import jax.numpy as jnp

    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh

    N, v = 128, 8
    geom = LUGeometry.create(N, N, v, grid)
    mesh = make_mesh(grid, devices=jax.devices()[: grid.P])
    A = make_test_matrix(N, N, seed=7, dtype=np.float32)
    shards = jnp.asarray(geom.scatter(A))

    out_s, perm_s = lu_factor_distributed(shards, geom, mesh, segs=(4, 4))
    out_b, perm_b = lu_factor_distributed(shards, geom, mesh, segs=(4, 4),
                                          update="block")
    np.testing.assert_array_equal(np.asarray(perm_s), np.asarray(perm_b))
    LUp = geom.gather(np.asarray(out_b))
    p = np.asarray(perm_b)
    res = lu_residual(A, LUp, p)
    assert res < residual_bound(N, np.float32), (grid, res)


def test_lu_distributed_block_update_bench_ratios():
    """The block update at the headline bench's structural ratios (32
    supersteps, multi-chunk nomination, 16x16 boundaries) — the shape
    where bucket transitions and the final fully-dead clamp all occur."""
    N, v = 256, 8
    A = make_test_matrix(N, N, seed=2, dtype=np.float32)
    LU, perm, _ = lu_distributed_host(A, Grid3(1, 1, 1), v, panel_chunk=64,
                                      update="block")
    assert sorted(perm.tolist()) == list(range(N))
    assert lu_residual(A, LU[perm], perm) < residual_bound(N, np.float32)


def test_lu_distributed_block_update_lookahead():
    """update='block' composes with the software-pipelined loop. Unlike
    segments (whose lookahead mirror is bitwise-identical, asserted in
    test_lu_distributed_lookahead_bitwise_equal), the block path's ONE
    wide suffix GEMM may round differently from the mirror's narrow slab
    GEMM (shape-dependent kernel accumulation) — so the contract here is
    value-level: identical pivots, f32-noise-level factors, correct
    residual."""
    import jax
    import jax.numpy as jnp

    from conflux_tpu.geometry import LUGeometry
    from conflux_tpu.lu.distributed import lu_factor_distributed
    from conflux_tpu.parallel.mesh import make_mesh

    grid = Grid3(2, 2, 1)
    N, v = 64, 8
    geom = LUGeometry.create(N, N, v, grid)
    mesh = make_mesh(grid, devices=jax.devices()[: grid.P])
    A = make_test_matrix(N, N, dtype=np.float32)
    shards = jnp.asarray(geom.scatter(A))

    out_a, perm_a = lu_factor_distributed(shards, geom, mesh,
                                          update="block")
    out_b, perm_b = lu_factor_distributed(shards, geom, mesh,
                                          update="block", lookahead=True)
    np.testing.assert_array_equal(np.asarray(perm_a), np.asarray(perm_b))
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               atol=1e-4)
    LUp = geom.gather(np.asarray(out_b))
    p = np.asarray(perm_b)
    assert lu_residual(A, LUp, p) < residual_bound(N, np.float32)
