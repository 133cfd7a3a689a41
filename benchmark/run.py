"""Run one benchmark cell once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by name: the cell's entry in
BENCHMARK.json names its configuration (`benchmark/configs/<config>.json`,
the deployment: sizes, dtype, engine options, the limits of the
correctness check) and its traffic (`benchmark/traffic/<traffic>.json`,
which names the loop that drives it, `benchmark/drivers/<loop>.py`, and
its parameters). Each metric is read from the run's observations by
`benchmark/metrics/<metric>.py`. A new cell, mix or metric is new files
and a new BENCHMARK.json entry, never an edit.

With --trace 0 the result carries the cell's end-to-end metrics; with
--trace 1 the same window runs under the profiler, and the result carries
the per-layer metrics, the device's busy and window seconds, and a
breakdown. Progress goes to standard error; the last line of standard
output is the result. Off a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class NoChip(RuntimeError):
    pass


def log(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of BENCHMARK.json with its configuration and traffic."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or _load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; "
                             f"known: {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        confs = {c["name"]: c for c in bench["configs"]}
        self.config = _load_json(os.path.join(
            ROOT, confs[self.entry["config"]]["file"]))
        self.traffic = _load_json(os.path.join(
            HERE, "traffic", self.entry["traffic"] + ".json"))
        self.e2e = [m for m in bench["end_to_end"] if self._has(m)]
        moved = {m["name"] for m in self.e2e}
        self.per_layer = [m for m in bench["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in moved)]

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def driver(self):
        return _module(os.path.join(HERE, "drivers",
                                    self.traffic["loop"] + ".py"),
                       "bench_driver_" + self.traffic["loop"])


def read_metric(name: str, obs: dict):
    """The value of metric `name` from the run's observations, or None
    where its reader finds nothing to read."""
    mod = _module(os.path.join(HERE, "metrics", name + ".py"),
                  "bench_metric_" + name.replace(".", "_"))
    return mod.read(obs)


def check_device(chips: int):
    """The devices of this run; raises NoChip off a TPU, for a device kind
    missing from the peaks table, or for too few chips."""
    import jax

    from benchmark import flops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's default device is {devs[0].platform!r}, "
                     "not a TPU: no result")
    flops.peaks(devs[0].device_kind)
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX has {len(devs)}")
    return devs


def enable_cache() -> str:
    """JAX's persistent compile cache at the fixed <checkout>/.jax_cache,
    every program admitted (the set-up of a later run reads them all)."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from conflux_tpu import cache

    # the program's own switch keeps the directory and admission rule
    # set here (first call wins)
    cache.enable_persistent_cache(min_compile_secs=0.0)
    return CACHE_DIR


def device_record(devs, chips: int) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


class Tracer:
    """The profiler around the measured window, when --trace 1."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None
        self._ann = None

    def __enter__(self):
        import jax

        if self.on:
            jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        self._ann.__exit__(*exc)
        if self.on:
            jax.profiler.stop_trace()
        return False

    def summary(self, programs=None):
        from benchmark import trace

        return trace.Summary(trace.load(self.dir), programs)

    def cleanup(self):
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def _number(x):
    return float(x) if x is not None else None


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             devs=None, overrides: dict | None = None) -> dict:
    """Drive one run of `cell` and return its result object. `devs` is
    what `check_device` returned (tests pass the CPU's devices and
    `overrides` of the configuration's sizes)."""
    import jax

    if devs is None:
        devs = jax.devices()
    conf = dict(cell.config)
    conf.update(overrides or {})
    if traced:  # a traced run traces a short steady window of its own
        seconds = min(seconds, float(cell.traffic["trace_seconds"]))
    tracer = Tracer(traced)
    try:
        obs = cell.driver().run(conf=conf, traffic=cell.traffic, seed=seed,
                                seconds=seconds, tracer=tracer, devs=devs,
                                chips=cell.chips, t_start=T_START, log=log)
        device = device_record(devs, cell.chips)
        device["memory_peak_bytes"] = obs.pop("memory_peak_bytes")
        result = {"correct": bool(obs["correct"]),
                  "attempted": int(obs["attempted"]),
                  "failed": int(obs["failed"]),
                  "metrics": {}, "device": device}
        if traced:
            summ = tracer.summary(obs.get("programs"))
            obs["trace"] = summ
            for label, pattern in obs.get("scope_tables", {}).items():
                table = summ.scope_table(label, pattern)
                log(f"scope self time of {label}:", json.dumps(
                    dict(sorted(table.items(), key=lambda kv: -kv[1]))))
            device["busy_s"] = summ.busy_s
            device["window_s"] = summ.window_s
            result["breakdown"] = {"device_ops": summ.top_ops(10),
                                   "idle_gaps": summ.idle_gaps(10)}
        obs["device_kind"] = devs[0].device_kind
        for m in (cell.per_layer if traced else cell.e2e):
            v = read_metric(m["name"], obs)
            if v is None or not math.isfinite(v):
                log(f"metric {m['name']}: nothing to read")
                continue
            result["metrics"][m["name"]] = {"value": float(v),
                                            "unit": m["unit"]}
        result["checks"] = {k: {"value": _number(v), "limit": _number(lim)}
                            for k, (v, lim) in obs["checks"].items()}
        return result
    finally:
        tracer.cleanup()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    try:
        devs = check_device(cell.chips)
    except (NoChip, KeyError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    enable_cache()
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devs=devs)
    print(f"correct = {res['correct']}", file=sys.stderr)
    for k, c in res["checks"].items():  # the last lines: number and limit
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
