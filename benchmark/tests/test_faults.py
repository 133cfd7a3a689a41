"""The harness past its look for a chip, with the timed path broken
underneath: each fault the LU cell can have must turn `correct` false.
(One chip: there is no exchange between chips to leave out.)"""

import pytest

from benchmark import run as R


def _unchanged(monkeypatch):
    # the factor returns its input as if it were the factors
    import jax.numpy as jnp

    from conflux_tpu.lu import distributed

    def build(geom, mesh, **kw):
        return lambda A: (A, jnp.arange(geom.N, dtype=jnp.int32))

    monkeypatch.setattr(distributed, "build_program", build)


def _wrap_solve(monkeypatch, change):
    from conflux_tpu import solvers

    real = solvers.lu_solve_distributed
    monkeypatch.setattr(solvers, "lu_solve_distributed",
                        lambda *a: change(real(*a)))


def _altered(monkeypatch):
    # one entry of the answer altered where it is produced
    _wrap_solve(monkeypatch, lambda x: x.at[0].add(0.1 * abs(x).max()))


def _half_left_out(monkeypatch):
    # the second half of the answer never computed
    _wrap_solve(monkeypatch, lambda x: x.at[x.shape[0] // 2:].set(0.0))


@pytest.mark.parametrize("fault", [_unchanged, _altered, _half_left_out],
                         ids=lambda f: f.__name__.strip("_"))
def test_lu_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    cell = R.Cell("lu_n32768")
    res = R.run_cell(cell, 12345, 0.4, False,
                     overrides=cell.config["cpu_test_sizes"])
    assert not res["correct"]
