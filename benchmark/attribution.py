"""Readings that take the program's own attribution of a traced window.

`conflux_tpu.profiler.scope_map` credits every instruction of a compiled
program to a step scope, the instructions XLA generated included. These
helpers join it with the benchmark's reduction of the trace (`trace.py`):
each op inside the executions a program owns, with its self time. A
checkout whose profiler has no `scope_map`, or whose solve is not a
program of its own, reads None here, and the metric is left out of the
result line.
"""

from __future__ import annotations

from collections import defaultdict

from benchmark import trace


def scope_self_s(obs, label: str = "factor") -> dict | None:
    """Step scope (None: credited to none) -> device self time (s) per
    step, over the chips, of the ops of program `label`'s executions."""
    tr, steps = obs.get("trace"), obs.get("steps")
    hlo = (obs.get("programs") or {}).get(label)
    if tr is None or not steps or not hlo or not tr.owned_ops(label):
        return None
    try:
        from conflux_tpu.profiler import scope_map
    except ImportError:
        return None
    scope = scope_map(hlo)
    out: dict = defaultdict(float)
    for c, idx in enumerate(tr._owned[label]):
        evs, own = tr.ops[c], tr._self[c]
        for i in idx:
            out[scope.get(trace.token(evs[i].name))] += own[i] / 1e9
    return {k: t / steps for k, t in out.items()}


def module_self_s(obs, module: str) -> float | None:
    """Device self time (s) per step, over the chips, of the ops inside
    the executions named `module` on the `XLA Modules` line; None where
    the window holds no such execution."""
    tr, steps = obs.get("trace"), obs.get("steps")
    if tr is None or not steps:
        return None
    total, runs = 0.0, 0
    for c, mods in enumerate(tr.modules):
        evs, own = tr.ops[c], tr._self[c]
        for m in mods:
            if m.name.split("(")[0] != module:
                continue
            runs += 1
            total += sum(own[i] for i in tr._inside(c, m)
                         if evs[i].end <= m.end) / 1e9
    return total / steps if runs else None
