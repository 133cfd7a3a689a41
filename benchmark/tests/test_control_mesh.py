"""The lower-precision control of the four-chip LU cell, on the chip, at
the cell's own size (N=65536 on a 2x2x1 grid): the configuration with the
program's bf16x3 path (`matmul_precision: high`, one step below the f32
HIGHEST the configuration states) must fail at least one check through
`dense_steps_mesh`, where the stated precision passes both. Skipped off a
TPU host with four chips: the CPU computes every f32 matmul in full
precision, so no control can show there."""

import json

import pytest

from benchmark import run as R

CELL = "lu_2x2x1_n65536"
SEED = 2 ** 31 + 101


@pytest.fixture(scope="module")
def devs():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < 4:
        pytest.skip("the control needs four chips' bf16 passes")
    R.enable_cache()
    return devs


def test_mesh_control_fails_a_check(devs):
    cell = R.Cell(CELL)
    ok = R.run_cell(cell, SEED, 3.0, False, devs=devs)
    bad = R.run_cell(cell, SEED, 3.0, False, devs=devs,
                     overrides={"matmul_precision": "high"})
    print(CELL, json.dumps({"program": ok["checks"],
                            "control": bad["checks"]}))
    assert ok["correct"]
    assert any(c["value"] > c["limit"] for c in bad["checks"].values())
    assert not bad["correct"]
