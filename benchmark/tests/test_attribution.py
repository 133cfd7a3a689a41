"""The readers that take the program's own attribution, on a recorded chip
trace of the named programs (`benchmark/fixtures/lu2048_named.*`: one
traced window of the LU cell's program at N=2048 on a TPU v5 lite, with
the factor's compiled text), and on the older trace, taken before the
programs had names, where the solve's reader finds nothing."""

import gzip
import math
import os

import pytest

from benchmark import run as R
from benchmark import trace as T

FIX = os.path.join(os.path.dirname(os.path.dirname(__file__)), "fixtures")
READERS = ("lu.unattributed_ms", "lu.swaps_ms", "lu.solve_ms")


def observations(stem):
    with gzip.open(os.path.join(FIX, stem + "_factor.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    summ = T.Summary(T.load(os.path.join(FIX, stem + ".xplane.pb.gz")),
                     {"factor": hlo})
    steps = sum(m.name.split("(")[0] == summ.programs["factor"].module
                for m in summ.modules[0])
    return {"trace": summ, "steps": steps, "programs": {"factor": hlo}}


@pytest.fixture(scope="module")
def named():
    return observations("lu2048_named")


def test_named_programs_on_the_modules_line(named):
    names = {m.name.split("(")[0] for m in named["trace"].modules[0]}
    assert {"jit_lu_factor", "jit_lu_solve"} <= names
    assert not names & {"jit_device_fn", "jit_broadcast_in_dim",
                        "jit_squeeze"}


def test_readers_on_the_named_programs(named):
    vals = {m: R.read_metric(m, named) for m in READERS}
    assert all(v is not None and math.isfinite(v) for v in vals.values())
    table = named["trace"].scope_table("factor", r"step\d+_[a-z0-9]+")
    factor_ms = 1e3 * sum(table.values()) / named["steps"]
    assert 0 <= vals["lu.unattributed_ms"] <= 0.01 * factor_ms
    assert vals["lu.solve_ms"] > 0
    assert vals["lu.swaps_ms"] >= R.read_metric("lu.pivotrows_ms", named)


def test_readers_before_the_programs_had_names():
    obs = observations("lu2048")
    assert R.read_metric("lu.solve_ms", obs) is None
    assert R.read_metric("lu.unattributed_ms", obs) is not None
