"""Every cell end to end at tiny sizes on the CPU (each configuration
file's `cpu_test_sizes`), the refusal off a chip, and a cell, a traffic
mix and a metric added by new files alone."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run as R

BENCH = json.load(open(os.path.join(R.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


def tiny(cell):
    return cell.config["cpu_test_sizes"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_tiny_on_cpu(name):
    cell = R.Cell(name)
    res = R.run_cell(cell, 2 ** 33 + 1, 0.5, False, overrides=tiny(cell))
    assert res["correct"], res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.e2e}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_no_result_off_a_chip(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        name, "--seed", "1", "--seconds", "1", "--trace",
                        "0"], cwd=R.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_cell_traffic_and_metric_added_by_files_alone(tmp_path):
    traffic = os.path.join(R.HERE, "traffic", "_added_by_test.json")
    metric = os.path.join(R.HERE, "metrics", "_added_by_test.steps.py")
    try:
        with open(traffic, "w") as f:
            json.dump({"loop": "dense_steps", "trace_seconds": 1}, f)
        with open(metric, "w") as f:
            f.write("def read(obs):\n    return obs.get('steps')\n")
        bench = json.loads(json.dumps(BENCH))
        bench["workloads"].append({"name": "_added", "config":
                                   "hpl_lu_n32768_f32", "traffic":
                                   "_added_by_test", "chips": 1})
        bench["end_to_end"].append({"name": "_added_by_test.steps",
                                    "unit": "steps", "better": "higher",
                                    "bound": 0.25, "source": "host_clock",
                                    "workloads": ["_added"]})
        cell = R.Cell("_added", bench)
        res = R.run_cell(cell, 3, 0.3, False, overrides=tiny(cell))
        assert res["correct"]
        assert res["metrics"]["_added_by_test.steps"]["value"] >= 1
        assert "setup_s" in res["metrics"]
    finally:
        for p in (traffic, metric):
            if os.path.exists(p):
                os.remove(p)
