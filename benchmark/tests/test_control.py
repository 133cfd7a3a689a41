"""The lower-precision control, on the chip, at each cell's own size: the
cell's configuration with the program's bf16x3 path (`matmul_precision:
high`, one step below the f32 HIGHEST the configuration states) must come
out not correct, and the stated precision correct, on three seeds.
Skipped off a TPU: the CPU computes every f32 matmul in full precision,
so no control can show there."""

import json
import os

import pytest

from benchmark import run as R

CELLS = [w["name"] for w in json.load(open(
    os.path.join(R.ROOT, "BENCHMARK.json")))["workloads"]]
SEEDS = (101, 2 ** 31 + 17, 9_000_000_001)


@pytest.fixture(scope="module")
def devs():
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control needs the chip's bf16 passes")
    R.enable_cache()
    return jax.devices()


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(devs, name):
    cell = R.Cell(name)
    for seed in SEEDS:
        ok = R.run_cell(cell, seed, 3.0, False, devs=devs)
        bad = R.run_cell(cell, seed, 3.0, False, devs=devs,
                         overrides={"matmul_precision": "high"})
        print(name, seed, json.dumps({"program": ok["checks"],
                                      "control": bad["checks"]}))
        assert ok["correct"]
        assert not bad["correct"]
