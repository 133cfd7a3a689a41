"""Tile-op layer tests: backend registry and Pallas kernels (interpret mode
on the CPU test platform)."""

import jax.numpy as jnp
import numpy as np
import pytest

from conflux_tpu.ops import blas
from conflux_tpu.ops import pallas_kernels


def test_backend_registry():
    assert blas.get_backend() == "xla"
    with pytest.raises(ValueError):
        blas.set_backend("cuda")
    blas.set_backend("pallas")
    assert blas.get_backend() == "pallas"
    blas.set_backend("xla")


def test_gemm_alpha_beta():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((8, 4)))
    b = jnp.asarray(rng.standard_normal((4, 8)))
    c = jnp.asarray(rng.standard_normal((8, 8)))
    out = blas.gemm(a, b, c=c, alpha=-1.0, beta=1.0)
    np.testing.assert_allclose(np.asarray(out), c - a @ b, rtol=1e-12)


@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 128, 384), (100, 60, 130)])
def test_pallas_gemm_matches_xla(shape):
    M, N, K = shape
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((M, K)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32))
    out = pallas_kernels.gemm(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b), atol=1e-4)


def test_gemm_backend_dispatch():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((128, 128)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((128, 128)).astype(np.float32))
    out = blas.gemm(a, b, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b), atol=1e-4)


def test_trsm_left_lower_unit():
    rng = np.random.default_rng(3)
    L = np.tril(rng.standard_normal((16, 16)), -1) + np.eye(16)
    B = rng.standard_normal((16, 32))
    X = blas.trsm_left_lower_unit(jnp.asarray(L), jnp.asarray(B))
    np.testing.assert_allclose(L @ np.asarray(X), B, atol=1e-10)


def test_trsm_right_upper():
    rng = np.random.default_rng(4)
    U = np.triu(rng.standard_normal((16, 16))) + 4 * np.eye(16)
    B = rng.standard_normal((32, 16))
    X = blas.trsm_right_upper(jnp.asarray(U), jnp.asarray(B))
    np.testing.assert_allclose(np.asarray(X) @ U, B, atol=1e-10)


def test_potrf():
    from conflux_tpu.validation import make_spd_matrix

    A = make_spd_matrix(32)
    L = blas.potrf(jnp.asarray(A))
    np.testing.assert_allclose(np.tril(L) @ np.tril(L).T, A, atol=1e-9)


def test_unit_lower():
    rng = np.random.default_rng(5)
    lu00 = jnp.asarray(rng.standard_normal((8, 8)))
    L = blas.unit_lower(lu00)
    assert np.allclose(np.diag(np.asarray(L)), 1.0)
    assert np.allclose(np.triu(np.asarray(L), 1), 0.0)


def test_vmem_derived_ceilings_pin_v5e():
    """The chunk ceilings derive from the scoped-VMEM budget (element-
    count model); the measured v5e values are pinned here so a budget or
    model change that silently shifts the tuned defaults fails loudly."""
    import pytest

    from conflux_tpu.ops import blas

    # default budget (32 MiB — the measured v5e figure) at the bench tile
    assert blas.single_call_rows(1024) == 8192
    assert blas.batched_call_rows(1024) == 4096
    # element model: heights scale as 1/v and 1/itemsize
    assert blas.single_call_rows(2048) == 4096
    assert blas.batched_call_rows(2048) == 2048
    assert blas.single_call_rows(1024, jnp.bfloat16) == 16384
    # never shorter than one tile
    assert blas.single_call_rows(8192) == 8192
    # chunk_layout's default chunk is the derived batched bound
    c, nch = blas.chunk_layout(32768, 1024)
    assert (c, nch) == (4096, 8)
    # override for unmeasured generations
    blas.set_scoped_vmem_bytes(16 << 20)
    try:
        assert blas.single_call_rows(1024) == 4096
        assert blas.batched_call_rows(1024) == 2048
    finally:
        blas.set_scoped_vmem_bytes(None)
    assert blas.single_call_rows(1024) == 8192
    with pytest.raises(ValueError, match="implausible"):
        blas.set_scoped_vmem_bytes(1000)


@pytest.mark.parametrize("kind,want", [
    ("TPU v5 lite", 32 << 20), ("TPU v99", None)])
def test_scoped_vmem_tpu_kind_table(monkeypatch, kind, want):
    """A TPU kind in the table gets its measured budget; one that is not
    raises instead of borrowing another generation's figure."""
    class Dev:
        platform = "tpu"
        device_kind = kind

    monkeypatch.delenv("CONFLUX_TPU_SCOPED_VMEM_BYTES", raising=False)
    monkeypatch.setattr(blas.jax, "devices", lambda: [Dev()])
    if want is None:
        with pytest.raises(ValueError, match="TPU v99"):
            blas.scoped_vmem_bytes()
    else:
        assert blas.scoped_vmem_bytes() == want


@pytest.mark.parametrize("Px", [3, 5, 7])
def test_butterfly_zero_fill_contract_real_reducers(Px):
    """The odd-Px fold/unfold path makes EVERY rank reduce ppermute's
    zero fill on off-subcube lanes; correctness rests on the reducers
    being total on all-zero inputs with the garbage discarded by the
    coordinate selects (zero-fill contract, `butterfly_allreduce`).
    Pin it with the REAL hot-loop reducers — the CALU tournament
    (lu/distributed.py) and the TSQR R-tree (qr/distributed.py) — at
    odd Px: results must be NaN/Inf-free and bitwise-replicated across
    the axis, and elected ids must come from real rows, never from the
    zero fill."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from conflux_tpu.geometry import Grid3
    from conflux_tpu.ops import blas
    from conflux_tpu.parallel.mesh import (butterfly_allreduce, make_mesh,
                                        shard_map)
    from conflux_tpu.qr.single import _tree_r

    v = 4
    mesh = make_mesh(Grid3(Px, 1, 1), devices=jax.devices()[:Px])
    rng = np.random.default_rng(100 + Px)
    data = rng.standard_normal((Px, v, v)).astype(np.float32)
    ids = np.arange(Px * v, dtype=np.int32).reshape(Px, v)

    def calu_pair(top, bot):
        stack = jnp.concatenate([top[0], bot[0]], axis=0)
        sid = jnp.concatenate([top[1], bot[1]])
        lu00, wid = blas.tournament_winners(stack, chunk=2 * v)
        return (jnp.take(stack, wid, axis=0, mode="fill", fill_value=0),
                jnp.take(sid, wid, mode="fill",
                         fill_value=np.iinfo(np.int32).max),
                lu00)

    def fn(blk, bid):
        nom, nid, lu00 = butterfly_allreduce(
            (blk[0], bid[0], jnp.zeros((v, v), jnp.float32)),
            Px, "x", calu_pair)
        (r,) = butterfly_allreduce(
            (_tree_r(blk[0], 2 * v),), Px, "x",
            lambda top, bot: (_tree_r(
                jnp.concatenate([top[0], bot[0]], axis=0), 2 * v),))
        return nom[None], nid[None], lu00[None], r[None]

    # conflint: disable=CFX-RECOMPILE one-shot test trace; nothing to reuse
    nom, nid, lu00, r = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(P("x", None, None), P("x", None)),
        out_specs=(P("x", None, None), P("x", None),
                   P("x", None, None), P("x", None, None))))(data, ids)
    nom, nid, lu00, r = map(np.asarray, (nom, nid, lu00, r))
    for out in (nom, nid, lu00, r):
        assert np.all(np.isfinite(out)), "zero-fill garbage leaked NaN/Inf"
        for px in range(1, Px):  # bitwise replication across the axis
            np.testing.assert_array_equal(out[px], out[0])
    # every elected id is a real row, never the fold's zero-fill ids
    assert set(nid[0].tolist()) <= set(range(Px * v))
    flat = data.reshape(Px * v, v)
    np.testing.assert_array_equal(nom[0], flat[nid[0]])


@pytest.mark.parametrize("Px", [1, 2, 3, 4, 5, 6, 7, 8])
def test_butterfly_allreduce_any_px(Px):
    """The hypercube all-reduce must deliver every rank's contribution to
    every rank — including non-power-of-two axes, where overflow ranks
    fold in/out of the subcube (the reference's odd-grid compensating
    sends, `conflux_opt.hpp:266-280`) — and must honor the
    lower-coordinate pair ordering (an order-sensitive keep-top reducer
    converges to rank 0's value everywhere)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from conflux_tpu.geometry import Grid3
    from conflux_tpu.parallel.mesh import (butterfly_allreduce, make_mesh,
                                        shard_map)

    mesh = make_mesh(Grid3(Px, 1, 1), devices=jax.devices()[:Px])
    rng = np.random.default_rng(Px)
    data = rng.integers(1, 1 << 20, size=(Px, 4)).astype(np.int32)

    def fn(blk):
        (s,) = butterfly_allreduce(
            (blk[0],), Px, "x", lambda top, bot: (top[0] + bot[0],))
        (w,) = butterfly_allreduce(
            (blk[0],), Px, "x", lambda top, bot: (top[0],))
        return s[None], w[None]

    # conflint: disable=CFX-RECOMPILE one-shot test trace; nothing to reuse
    ssum, wtop = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=P("x", None),
        out_specs=(P("x", None), P("x", None))))(data)
    for px in range(Px):
        # exact integer sum: replication + completeness on every rank
        np.testing.assert_array_equal(np.asarray(ssum)[px], data.sum(axis=0))
        # keep-top reducer: the lower coordinate's value wins every pair
        np.testing.assert_array_equal(np.asarray(wtop)[px], data[0])
