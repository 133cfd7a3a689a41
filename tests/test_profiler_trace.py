"""Device-time attribution by the program itself (`profiler.scope_map`,
`profiler.phase_table`), the named shard_map programs, and host regions as
trace spans. The recorded trace is `benchmark/fixtures/lu2048.*`: one
traced window of the LU benchmark cell at N=2048 on a TPU v5 lite, taken
when the factor and the solve were both `jit_device_fn`, with the factor's
compiled text."""

import glob
import gzip
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conflux_tpu import profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(ROOT, "benchmark", "fixtures")
TRACE = os.path.join(FIX, "lu2048.xplane.pb.gz")


@pytest.fixture(scope="module")
def factor_hlo():
    with gzip.open(os.path.join(FIX, "lu2048_factor.hlo.txt.gz"), "rt") as f:
        return f.read()


@pytest.fixture(scope="module")
def recorded(factor_hlo):
    """The benchmark's own reduction of the fixture: every op of the
    factor's executions with its self time (s)."""
    from benchmark import trace as T

    summ = T.Summary(T.load(TRACE), {"factor": factor_hlo})
    evs, own = summ.ops[0], summ._self[0]
    return [(T.token(evs[i].name), own[i] / 1e9)
            for i in summ._owned["factor"][0]]


def test_scope_map_attributes_the_generated_ops(factor_hlo, recorded):
    scope = profiler.scope_map(factor_hlo)
    direct = dict(re.findall(r"%([\w.-]+) = .*?op_name=\"([^\"]*)\"",
                             factor_hlo))
    phase = re.compile(profiler.PHASE_RE)
    total = sum(s for _, s in recorded)
    unscoped = sum(s for tok, s in recorded
                   if not any(phase.fullmatch(p)
                              for p in direct.get(tok, "").split("/")))
    missed = sum(s for tok, s in recorded if tok not in scope)
    assert unscoped / total > 0.05  # metadata alone: 5.7%
    assert missed / total <= 0.01
    # the per-row scatter loop XLA generates is step 2's
    step2 = sum(s for tok, s in recorded
                if scope.get(tok) == "step2_pivotrows")
    direct2 = sum(s for tok, s in recorded
                  if "step2_pivotrows" in direct.get(tok, ""))
    assert step2 > direct2


def test_scope_map_rules_on_hand_made_text():
    hlo = """HloModule jit_lu_factor, is_scheduled=true

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %g = f32[8]{0} get-tuple-element(%p), index=1
  ROOT %t = (s32[], f32[8]) tuple(%i, %g)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %copy-start.1 = (f32[8]{0}, f32[8]{0}) copy-start(%a)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %copy-done.1), kind=kLoop, calls=%f, metadata={op_name="jit(lu_factor)/while/body/step2_pivotrows/scatter"}
  %while.1 = (s32[], f32[8]) while(%fusion.1), condition=%c, body=%body
  %broadcast.3.clone = f32[8]{0} broadcast(%fusion.1), metadata={op_name="jit(lu_factor)/while/body"}
  %add.2 = f32[8]{0} add(%fusion.1, %fusion.1), metadata={op_name="jit(lu_factor)/add"}
  %copy.4 = f32[8]{1,0} copy(%a), metadata={op_name="a"}
  %fusion.5 = f32[8]{0} fusion(%copy.4), kind=kLoop, calls=%f, metadata={op_name="jit(lu_factor)/solve_fwd/dot"}
  ROOT %out = f32[8]{0} get-tuple-element(%while.1), index=1
}
"""
    scope = profiler.scope_map(hlo)
    # consumers first, transitively: the copy pair feeds a step-2 op
    assert scope["copy-start.1"] == scope["copy-done.1"] == "step2_pivotrows"
    # operands next: the loop and its output read the step-2 op
    assert scope["while.1"] == scope["out"] == "step2_pivotrows"
    # a layout copy named after its parameter is generated too
    assert scope["copy.4"] == "solve_fwd"
    # a clone whose inherited op_name names no phase is generated too
    assert scope["broadcast.3.clone"] == "step2_pivotrows"
    # the caller last: the loop body's ops take the loop's phase
    assert scope["g"] == scope["t"] == "step2_pivotrows"
    # user code outside every scope stays unattributed
    assert "add.2" not in scope


def test_phase_table_credits_only_the_factor(factor_hlo, recorded, capsys):
    table = profiler.phase_table(TRACE, factor_hlo)
    out = capsys.readouterr().out
    # the 8 solves share the name jit_device_fn; they, jit__lambda,
    # jit_broadcast_in_dim and jit_squeeze are all skipped
    assert "jit_device_fn: 8 execution(s) on 1 chip(s)" in out
    assert sum(n for _, n in table.values()) == len(recorded)
    total_ms = sum(t for t, _ in table.values())
    assert total_ms == pytest.approx(1e3 * sum(s for _, s in recorded),
                                     rel=0.01)
    assert table[profiler.UNATTRIBUTED][0] <= 0.01 * total_ms
    for phase in ("step0_reduce", "step1_pivoting", "step2_pivotrows",
                  "step6_dgemm"):
        assert table[phase][0] > 0
    assert "device busy" in out
    gaps = [ln for ln in out.splitlines() if ln.endswith("(no program span)")]
    assert len(gaps) == 5


def test_phase_table_reads_without_tensorflow(factor_hlo, monkeypatch,
                                              capsys):
    import builtins

    real = builtins.__import__

    def no_tf(name, *a, **kw):
        if name.split(".")[0] == "tensorflow":
            raise ImportError("no tensorflow here")
        return real(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", no_tf)
    table = profiler.phase_table(TRACE, factor_hlo)
    assert table["step1_pivoting"][0] > 0
    capsys.readouterr()


def test_programs_are_named_and_the_solve_is_one_launch(tmp_path):
    from jax.profiler import ProfileData

    from conflux_tpu.geometry import Grid3, LUGeometry
    from conflux_tpu.lu.distributed import build_program
    from conflux_tpu.parallel.mesh import make_mesh, mesh_cache_key
    from conflux_tpu.solvers import _build_lu_solve, lu_solve_distributed
    from conflux_tpu.validation import make_test_matrix

    grid = Grid3(2, 2, 1)
    geom = LUGeometry.create(256, 256, 32, grid)
    mesh = make_mesh(grid, devices=jax.devices()[:grid.P])
    A = make_test_matrix(256, 256, dtype=np.float32)
    shards = jnp.asarray(geom.scatter(A))
    factor = build_program(geom, mesh, dtype=jnp.float32)
    assert factor.lower(shards).compile().as_text().startswith(
        "HloModule jit_lu_factor")
    F, perm = factor(shards)
    b = jnp.asarray(np.random.default_rng(0).standard_normal(256),
                    jnp.float32)
    solve = _build_lu_solve(geom, mesh_cache_key(mesh))
    assert solve.lower(F, perm, b).compile().as_text().startswith(
        "HloModule jit_lu_solve")
    x = lu_solve_distributed(F, perm, geom, mesh, b)
    assert x.shape == (256,)
    np.testing.assert_allclose(A @ np.asarray(x), np.asarray(b), atol=1e-3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        lu_solve_distributed(F, perm, geom, mesh, b).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(glob.glob(
        str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    launched = {e.name for p in pd.planes for ln in p.lines
                for e in ln.events if e.name.startswith("PjitFunction(")}
    assert launched == {"PjitFunction(lu_solve)"}


def test_region_is_a_host_span_and_not_a_scope(tmp_path):
    from jax.profiler import ProfileData

    x = jnp.arange(8.0)
    with profiler.region("serve.solve"):
        first = jax.jit(lambda v: v * 2 + 1)
        text = first.lower(x).compile().as_text()

    def body(v):
        with profiler.region("serve.solve"):
            return v * 3 + 1

    inner = jax.jit(body).lower(x).compile().as_text()
    assert "serve.solve" not in text and "serve.solve" not in inner
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.region("serve.solve"):
            first(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(glob.glob(
        str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    spans = [e for p in pd.planes if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events if e.name == "serve.solve"]
    assert len(spans) == 1 and spans[0].duration_ns > 0
