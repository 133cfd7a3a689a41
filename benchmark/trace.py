"""Reduction of a profiler trace to device times, owned by the benchmark.

Reads the `.xplane.pb` that `jax.profiler` writes with
`jax.profiler.ProfileData` (no TensorFlow import). The device's timeline
is the `XLA Ops` line of each `/device:` plane; its events nest (a while
loop spans its body's ops), so each op is credited its SELF time, its
duration less that of the events directly inside it, as the program's
`profiler._trace_durations` does. Busy time is the union of the op
intervals inside the traced window; idle time is the rest of the window,
and each idle gap is named by the benchmark's host span (`bench.*`
TraceAnnotation) that covers most of it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# the optimized-HLO line shape "%tok = ... metadata={... op_name="..." }"
_OP_NAME_RE = re.compile(r"%([\w.-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = newest_xplane(path)
    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


class Event:
    __slots__ = ("name", "start", "end")

    def __init__(self, name, start, end):
        self.name, self.start, self.end = name, start, end

    @property
    def dur(self) -> float:
        return self.end - self.start


def _events(line) -> list:
    return [Event(e.name, float(e.start_ns),
                  float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def device_planes(pd) -> list:
    return [p for p in pd.planes if p.name.startswith("/device:")
            and not p.name.startswith("/device:CUSTOM")]


def device_lines(pd, line_name: str) -> list:
    """[events of `line_name`] for every device plane."""
    out = []
    for p in device_planes(pd):
        for ln in p.lines:
            if ln.name == line_name:
                out.append(_events(ln))
    return out


def host_spans(pd, prefix: str = SPAN_PREFIX) -> list:
    """The benchmark's own TraceAnnotation spans from the host planes."""
    out = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in _events(ln):
                if ev.name.startswith(prefix):
                    out.append(ev)
    return out


def window(pd) -> tuple:
    """(start, end) ns of the `bench.window` span."""
    spans = [s for s in host_spans(pd) if s.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    return min(s.start for s in spans), max(s.end for s in spans)


def self_times(events: list) -> list:
    """Self time (ns) of each event: its duration less its direct children's."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].dur))
    own = [e.dur for e in events]
    stack: list = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= e.dur
        stack.append(i)
    return own


def clip(events: list, t0: float, t1: float) -> list:
    return [e for e in events if e.end > t0 and e.start < t1]


def union_ns(events: list, t0: float, t1: float) -> float:
    """Length of the union of the events' intervals inside [t0, t1]."""
    iv = sorted((max(e.start, t0), min(e.end, t1)) for e in events
                if e.end > t0 and e.start < t1)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in iv:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events: list, t0: float, t1: float) -> list:
    """[(start, end)] of the idle intervals of the union inside [t0, t1]."""
    iv = sorted((max(e.start, t0), min(e.end, t1)) for e in events
                if e.end > t0 and e.start < t1)
    out, cur = [], t0
    for s, e in iv:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


# "%tok = <result type> opcode(" -- the trace prints each operand with its
# type where the compiled text does not, so an op is matched by its token,
# result type and opcode
_HEAD_RE = re.compile(r"%([\w.-]+) = (.*?) ([a-z][\w-]*)\(")


def head(text: str) -> tuple:
    """(token, result type, opcode) of an instruction or a trace op name."""
    m = _HEAD_RE.match(text)
    return m.groups() if m else (token(text), "", "")


def _instructions(hlo_text: str):
    """(head, op token, op_name) of every instruction of a compiled
    program's text."""
    for ln in hlo_text.splitlines():
        ln = ln.strip()
        if ln.startswith("ROOT "):
            ln = ln[5:]
        if not ln.startswith("%") or " = " not in ln:
            continue
        m = _OP_NAME_RE.match(ln)
        yield head(ln), ln[1:].split(" ", 1)[0], m.group(2) if m else ""


def token(name: str) -> str:
    """The HLO op token of a trace event name ("%fusion.6 = ..." -> fusion.6)."""
    return name[1:].split(" ", 1)[0] if name.startswith("%") else name


class Program:
    """One compiled program: its instructions, to recognise its executions
    in the trace, and each op token's scope path (`op_name` metadata)."""

    def __init__(self, hlo_text: str):
        self.heads, self.scopes = set(), {}
        m = re.search(r"^HloModule ([\w.-]+)", hlo_text, re.M)
        self.module = m.group(1) if m else ""
        for hd, tok, op_name in _instructions(hlo_text):
            self.heads.add(hd)
            self.scopes[tok] = op_name

    def owns(self, module: str, ops: list) -> bool:
        """Whether one module execution, named `module` and running `ops`,
        is this program's: same module name, and nearly all of its ops'
        (token, result type, opcode) are among this program's own."""
        probe = ops[:64]
        return (module.split("(")[0] == self.module and bool(probe)
                and 10 * sum(head(e.name) in self.heads for e in probe)
                >= 9 * len(probe))


class Summary:
    """What the per-layer readers take from one traced window. `programs`
    maps a label to a compiled program's text, for scope queries."""

    def __init__(self, pd, programs: dict | None = None):
        self.t0, self.t1 = window(pd)
        self.window_s = (self.t1 - self.t0) / 1e9
        self.ops = [sorted(clip(evs, self.t0, self.t1), key=lambda e: e.start)
                    for evs in device_lines(pd, OPS_LINE)]
        self.modules = [clip(evs, self.t0, self.t1)
                        for evs in device_lines(pd, MODULES_LINE)]
        if not self.ops or not any(self.ops):
            raise ValueError("trace has no device op events in the window")
        self.chips = len(self.ops)
        self.busy_s = sum(union_ns(evs, self.t0, self.t1)
                          for evs in self.ops) / 1e9 / self.chips
        self.spans = [s for s in host_spans(pd) if s.name != WINDOW_SPAN]
        self._self = [self_times(evs) for evs in self.ops]
        self._starts = [[e.start for e in evs] for evs in self.ops]
        self.programs = {k: Program(t) for k, t in (programs or {}).items()}
        self._owned = {k: self._executions(p)
                       for k, p in self.programs.items()}

    def _inside(self, chip: int, m: Event) -> range:
        starts = self._starts[chip]
        return range(bisect.bisect_left(starts, m.start),
                     bisect.bisect_right(starts, m.end))

    def _executions(self, prog: Program) -> list:
        """Per chip, the op indices inside the executions `prog` owns."""
        out = []
        for c, mods in enumerate(self.modules):
            ops = self.ops[c]
            idx = []
            for m in mods:
                r = self._inside(c, m)
                if prog.owns(m.name, [ops[i] for i in r]):
                    idx.extend(i for i in r if ops[i].end <= m.end)
            out.append(idx)
        return out

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_self_s(self) -> dict:
        """Op name -> summed self time (s), over the chips."""
        out: dict = defaultdict(float)
        for evs, own in zip(self.ops, self._self):
            for e, s in zip(evs, own):
                out[e.name] += s / 1e9
        return dict(out)

    def scope_table(self, program: str, pattern: str) -> dict:
        """First match of `pattern` in each op's scope -> self time (s)."""
        prog = self.programs.get(program)
        out: dict = defaultdict(float)
        if prog is None:
            return {}
        rx = re.compile(pattern)
        for c, idx in enumerate(self._owned[program]):
            evs, own = self.ops[c], self._self[c]
            for i in idx:
                m = rx.search(prog.scopes.get(token(evs[i].name), ""))
                out[m.group(0) if m else "(other)"] += own[i] / 1e9
        return dict(out)

    def scope_self_s(self, program: str, pattern: str) -> float:
        """Summed self time (s), over the chips, of `program`'s ops whose
        scope path matches `pattern`."""
        return sum(t for k, t in self.scope_table(program, pattern).items()
                   if k != "(other)")

    def owned_ops(self, program: str) -> int:
        """Ops, over the chips, inside the executions `program` owns."""
        return sum(len(ix) for ix in self._owned.get(program, []))

    def top_ops(self, k: int = 10) -> list:
        ranked = sorted(self.op_self_s().items(), key=lambda kv: -kv[1])
        return [[n[:200], s] for n, s in ranked[:k]]

    def idle_gaps(self, k: int = 10) -> list:
        """The `k` longest idle gaps of chip 0, each named by the innermost
        benchmark span that covers most of it."""
        longest = sorted(gaps(self.ops[0], self.t0, self.t1),
                         key=lambda g: g[0] - g[1])[:k]
        out = []
        for s, e in longest:
            best, key = "host (no bench span)", (0.0, 0.0)
            for sp in self.spans:
                c = min(e, sp.end) - max(s, sp.start)
                if c > 0 and (c, -(sp.end - sp.start)) > key:
                    best, key = sp.name, (c, -(sp.end - sp.start))
            out.append([best, (e - s) / 1e9])
        return out
