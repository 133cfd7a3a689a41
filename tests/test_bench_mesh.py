"""The four-chip LU cell (`lu_2x2x1_n65536`) at tiny sizes on a 2x2x1 mesh
of CPU devices: the cell end to end, its sharded generator and exact
reference against NumPy, planted faults, the readers of its per-layer
metrics, and the `comm_*` scopes every collective of the mesh programs
sits in (what `lu_mesh.collective_ms` and `profiler.phase_table` read)."""

import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import matrices, reference_mesh
from benchmark import run as R
from conflux_tpu import profiler
from conflux_tpu.geometry import Grid3, LUGeometry
from conflux_tpu.lu.distributed import build_program
from conflux_tpu.parallel.mesh import (
    AXIS_X, AXIS_Y, make_mesh, mesh_cache_key)
from conflux_tpu.solvers import _build_lu_solve

CELL = "lu_2x2x1_n65536"
GRID = Grid3(2, 2, 1)
AXES = (AXIS_X, AXIS_Y)
COLLECTIVE = re.compile(
    r"\s*(?:ROOT )?%[\w.-]+ = .*? ((?:all-reduce|all-gather|collective-permute"
    r"|all-to-all|reduce-scatter)[a-z-]*)\(")


def mesh_sharding(grid=GRID):
    mesh = make_mesh(grid, devices=jax.devices()[:grid.P])
    return NamedSharding(mesh, P(*AXES, None, None))


def run_tiny(seed=2 ** 33 + 7):
    cell = R.Cell(CELL)
    return R.run_cell(cell, seed, 0.3, False,
                      overrides=cell.config["cpu_test_sizes"])


def test_mesh_cell_runs_tiny_and_reads_correct():
    cell = R.Cell(CELL)
    assert cell.chips == 4 and cell.config["grid"] == [2, 2, 1]
    res = run_tiny()
    assert res["correct"], res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                             "memory_peak_bytes": 0}
    assert set(res["metrics"]) == {"dense_solve_gflops", "setup_s"}
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


def test_generator_tiles_are_the_hash_at_their_global_indices():
    n, v, s = 256, 32, 4_000_000_123 % 2 ** 32
    gen = reference_mesh.shard_generator(n, v, mesh_sharding().mesh, AXES)
    shards = np.asarray(gen(jnp.uint32(s)))
    assert shards.shape == (2, 2, n // 2, n // 2)
    for px in range(2):
        for py in range(2):
            for lt in range(n // (2 * v)):
                for lu in range(n // (2 * v)):
                    i0, j0 = (2 * lt + px) * v, (2 * lu + py) * v
                    r = np.arange(i0, i0 + v)[:, None]
                    c = np.arange(j0, j0 + v)[None, :]
                    want = matrices.uniform_np(r, c, n, s) + np.float32(2) * (r == c)
                    got = shards[px, py, lt * v:(lt + 1) * v,
                                 lu * v:(lu + 1) * v]
                    np.testing.assert_array_equal(got, want)


def _gather(F, v):
    """(Px, Py, Ml, Nl) shards -> the (n, n) matrix, tile by tile."""
    Px, Py, Ml, Nl = F.shape
    out = np.zeros((Px * Ml, Py * Nl), F.dtype)
    for px in range(Px):
        for py in range(Py):
            for lt in range(Ml // v):
                for lu in range(Nl // v):
                    i0, j0 = (lt * Px + px) * v, (lu * Py + py) * v
                    out[i0:i0 + v, j0:j0 + v] = F[px, py, lt * v:(lt + 1) * v,
                                                  lu * v:(lu + 1) * v]
    return out


def _numpy_residual(LU, perm, n, s):
    r = np.arange(n)
    A = (matrices.uniform_np(r[:, None], r[None, :], n, s).astype(np.float64)
         + 2.0 * np.eye(n))
    LU = LU.astype(np.float64)
    L = np.tril(LU, -1) + np.eye(n)
    U = np.triu(LU)
    return np.linalg.norm(A[perm] - L @ U) / np.linalg.norm(A)


@pytest.fixture(scope="module")
def factored():
    n, v, s = 512, 64, 987654321
    sharding = mesh_sharding()
    A = reference_mesh.shard_generator(n, v, sharding.mesh, AXES)(
        jnp.uint32(s))
    geom = LUGeometry.create(n, n, v, GRID)
    F, perm = build_program(geom, sharding.mesh, dtype=jnp.float32)(A)
    return n, v, s, F, perm


def test_exact_residual_equals_numpy_f64(factored):
    n, v, s, F, perm = factored
    LU, p = _gather(np.asarray(F), v), np.asarray(perm)
    assert sorted(p) == list(range(n))
    mine = reference_mesh.lu_factor_residual(F, perm, n, v, s, AXES)
    theirs = _numpy_residual(LU, p, n, s)
    assert theirs < 1e-5
    # at a correct factor the residual is of the order of the f32 rounding
    # of forming L U, so the f32 reference reads it only to within a third
    assert mine == pytest.approx(theirs, rel=1 / 3)
    # a factor off by more than rounding, on one tile of one chip: both
    # read the same
    bad = F.at[1, 0, 70, 3].add(0.1)
    mine = reference_mesh.lu_factor_residual(bad, perm, n, v, s, AXES)
    theirs = _numpy_residual(_gather(np.asarray(bad), v), p, n, s)
    assert theirs > 1e-3
    assert mine == pytest.approx(theirs, rel=1e-3)


def _factor_changed(monkeypatch, change):
    from conflux_tpu.lu import distributed

    real = distributed.build_program

    def build(*a, **kw):
        fn = real(*a, **kw)
        return lambda A: change(*fn(A), A)

    monkeypatch.setattr(distributed, "build_program", build)


def _perm_swapped(monkeypatch):
    # two entries of the permutation exchanged
    _factor_changed(monkeypatch, lambda F, perm, A: (
        F, perm.at[jnp.array([5, 200])].set(perm[jnp.array([200, 5])])))


def _tile_altered(monkeypatch):
    # one tile of one chip's factors altered
    _factor_changed(monkeypatch, lambda F, perm, A: (
        F.at[1, 1, 64:128, 0:64].add(0.05), perm))


def _input_returned(monkeypatch):
    # the factor returns its input as if it were the factors
    _factor_changed(monkeypatch, lambda F, perm, A: (
        A, jnp.arange(perm.shape[0], dtype=perm.dtype)))


def _half_x_zeroed(monkeypatch):
    # the second half of the answer never computed
    from conflux_tpu import solvers

    real = solvers.lu_solve_distributed
    monkeypatch.setattr(
        solvers, "lu_solve_distributed",
        lambda *a: real(*a).at[a[-1].shape[0] // 2:].set(0.0))


@pytest.mark.parametrize("fault", [_perm_swapped, _tile_altered,
                                   _input_returned, _half_x_zeroed],
                         ids=lambda f: f.__name__.strip("_"))
def test_planted_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = run_tiny(seed=31337)
    assert not res["correct"]
    failed = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    want = "backward_err" if fault is _half_x_zeroed else "factor_resid"
    assert want in failed, res["checks"]


def _collectives(hlo: str) -> list:
    out = []
    for ln in hlo.splitlines():
        m = COLLECTIVE.match(ln)
        if m:
            name = re.search(r'op_name="([^"]*)"', ln)
            out.append((m.group(1), name.group(1) if name else ""))
    return out


@pytest.mark.parametrize("election", ["gather", "butterfly"])
def test_every_collective_lies_under_a_comm_scope(election):
    n, v = 512, 64
    sharding = mesh_sharding()
    geom = LUGeometry.create(n, n, v, GRID)
    shards = jax.ShapeDtypeStruct((2, 2, geom.Ml, geom.Nl), jnp.float32,
                                  sharding=sharding)
    rep = NamedSharding(sharding.mesh, P())
    factor = build_program(geom, sharding.mesh, dtype=jnp.float32,
                           election=election).lower(shards).compile()
    solve = _build_lu_solve(geom, mesh_cache_key(sharding.mesh)).lower(
        shards, jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rep)).compile()
    comm = re.compile(profiler.COMM_RE)
    for hlo, kinds in ((factor.as_text(), {"step0_reduce", "step1_pivoting",
                                           "step2_pivotrows", "step8_perm"}),
                       (solve.as_text(), {"solve_fwd", "solve_bwd"})):
        found = _collectives(hlo)
        assert found
        seen = set()
        for op, name in found:
            parts = name.split("/")
            assert any(comm.fullmatch(p) for p in parts), (op, name)
            # nested in the phase it serves
            seen |= set(parts) & kinds
        assert seen == kinds


def _fake_trace(hlo: str, module: str):
    """A profiler trace with one device plane on which every instruction
    of `hlo` that carries an op_name ran once, 1 us each, inside one
    execution of `module`."""
    heads = [ln.strip().removeprefix("ROOT ") for ln in hlo.splitlines()
             if re.match(r"\s+(?:ROOT )?%[\w.-]+ = ", ln) and "op_name=" in ln]

    def ev(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=start,
                                     duration_ns=dur)

    ops = [ev(h.split(", metadata=")[0], 1000 * i, 1000)
           for i, h in enumerate(heads)]
    lines = [types.SimpleNamespace(name="XLA Ops", events=ops),
             types.SimpleNamespace(name="XLA Modules", events=[
                 ev(f"{module}(1)", 0, 1000 * len(ops))])]
    plane = types.SimpleNamespace(name="/device:TPU:0", lines=lines)
    return types.SimpleNamespace(planes=[plane]), len(ops)


def test_phase_table_shows_comm_rows_under_their_step(monkeypatch, capsys):
    n, v = 512, 64
    sharding = mesh_sharding()
    geom = LUGeometry.create(n, n, v, GRID)
    shards = jax.ShapeDtypeStruct((2, 2, geom.Ml, geom.Nl), jnp.float32,
                                  sharding=sharding)
    hlo = build_program(geom, sharding.mesh,
                        dtype=jnp.float32).lower(shards).compile().as_text()
    pd, n_ops = _fake_trace(hlo, "jit_lu_factor")
    monkeypatch.setattr(profiler, "_load_trace", lambda path: pd)
    table = profiler.phase_table("unused", hlo)
    out = capsys.readouterr().out.splitlines()
    comm_rows = {k: r for k, r in table.items() if "/" in k}
    assert "step0_reduce/comm_panel" in comm_rows
    assert "step1_pivoting/comm_election" in comm_rows
    # the phase rows partition the ops; a comm row is part of its phase's
    assert sum(c for k, (_, c) in table.items() if "/" not in k) == n_ops
    for key, (ms, ops) in comm_rows.items():
        step = key.split("/")[0]
        assert ops <= table[step][1] and ms <= table[step][0] + 1e-12
        # printed indented, below its phase's line
        row = next(i for i, ln in enumerate(out)
                   if ln.startswith("  " + key.split("/")[1] + " "))
        above = [ln.split()[0] for ln in out[:row] if ln and ln[0] != " "]
        assert above[-1] == step


class _Trace:
    """What the readers take from `benchmark.trace.Summary`."""

    def __init__(self, chips, scopes):
        self.chips, self._scopes = chips, scopes

    def owned_ops(self, program):
        return 1

    def scope_self_s(self, program, pattern):
        rx = re.compile(pattern)
        return sum(t for k, t in self._scopes.items() if rx.search(k))


def test_mesh_readers_average_over_the_chips():
    scopes = {"step0_reduce": 0.8, "step0_reduce/comm_panel": 0.4,
              "step1_pivoting": 1.6, "step6_dgemm": 4.0}
    obs = {"trace": _Trace(4, scopes), "steps": 2, "device_kind":
           "TPU v5 lite", "trailing": (197e12, 0.0)}
    # 0.4 s over 2 factorizations and 4 chips
    assert R.read_metric("lu_mesh.collective_ms", obs) == pytest.approx(50.0)
    assert R.read_metric("lu_mesh.election_ms", obs) == pytest.approx(350.0)
    # 2 x 197 TFLOP at one chip's peak in 2 s, over 4 s of summed chip time
    assert R.read_metric("lu_mesh.dgemm_roofline", obs) == pytest.approx(50.0)
    # a program without comm scopes: nothing to read, no error
    obs["trace"] = _Trace(4, {"step0_reduce": 0.8})
    assert R.read_metric("lu_mesh.collective_ms", obs) is None
    assert R.read_metric("lu_mesh.solve_ms", {"trace": None}) is None
