"""The trace reduction on hand-made events and on a recorded chip trace
(`benchmark/fixtures/lu2048.*`: one traced window of the LU cell's program
at N=2048 on a TPU v5 lite, with that program's compiled text)."""

import gzip
import os

import pytest

from benchmark import trace as T

FIX = os.path.join(os.path.dirname(os.path.dirname(__file__)), "fixtures")


def ev(name, start, end):
    return T.Event(name, float(start), float(end))


def test_self_time_takes_out_nested_ops():
    evs = [ev("while", 0, 100), ev("a", 10, 30), ev("b", 40, 90),
           ev("c", 50, 60), ev("d", 120, 130)]
    assert T.self_times(evs) == [30, 20, 40, 10, 10]


def test_union_and_gaps_inside_the_window():
    evs = [ev("a", 0, 10), ev("b", 5, 20), ev("c", 30, 40), ev("d", 90, 120)]
    assert T.union_ns(evs, 0, 100) == 20 + 10 + 10
    assert T.gaps(evs, 0, 100) == [(20, 30), (40, 90)]
    assert T.union_ns(evs, 35, 95) == 5 + 5


def test_instruction_texts_and_scopes():
    hlo = ('HloModule jit_device_fn, is_scheduled=true\n'
           '  %fusion.6 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
           'calls=%c, metadata={op_name="jit(f)/while/body/step6_dgemm/dot"}\n'
           '  ROOT %copy.1 = f32[8]{0} copy(%fusion.6)\n')
    prog = T.Program(hlo)
    assert prog.module == "jit_device_fn"
    assert prog.scopes["fusion.6"].endswith("step6_dgemm/dot")
    assert prog.scopes["copy.1"] == ""
    name = ("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, "
            "calls=%c")
    assert prog.owns("jit_device_fn(123)", [ev(name, 0, 1)])
    assert not prog.owns("jit_other(123)", [ev(name, 0, 1)])
    assert not prog.owns("jit_device_fn(9)", [ev("%fusion.6 = other", 0, 1)])
    assert T.token(name) == "fusion.6"


@pytest.fixture(scope="module")
def recorded():
    pb = os.path.join(FIX, "lu2048.xplane.pb.gz")
    with gzip.open(os.path.join(FIX, "lu2048_factor.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    return T.Summary(T.load(pb), {"factor": hlo})


def test_recorded_trace_window_and_busy(recorded):
    s = recorded
    assert s.chips == 1
    assert 0 < s.busy_s <= s.window_s
    own = sum(sum(x) for x in s._self) / 1e9
    # self times partition the busy time: nested ops are not counted twice
    assert own == pytest.approx(s.busy_s, rel=1e-6)
    assert len(s.top_ops(10)) == 10
    assert all(g[1] > 0 for g in s.idle_gaps(5))


def test_recorded_trace_scope_join(recorded):
    s = recorded
    assert s.owned_ops("factor") > 1000
    table = s.scope_table("factor", r"step\d+_[a-z0-9]+")
    for phase in ("step0_reduce", "step1_pivoting", "step2_pivotrows",
                  "step6_dgemm"):
        assert table.get(phase, 0) > 0, table
    factor_s = sum(table.values())
    t = sum(m.end - m.start for m in s.modules[0]
            if m.name.startswith("jit_device_fn")) / 1e9
    assert factor_s <= t
    assert s.scope_self_s("factor", r"step6_dgemm") == pytest.approx(
        table["step6_dgemm"])
    assert s.scope_self_s("nothing", r"step6_dgemm") == 0.0
