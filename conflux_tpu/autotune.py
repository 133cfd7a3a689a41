"""Measured variant dispatch — pick factorization knobs from recorded
measurements instead of asking the user.

Role of the reference's hand-measured variant switch
(`src/conflux/cholesky/Cholesky.cpp:857-921`: overlapping vs
non-overlapping Cholesky chosen per (P, N) from benchmark-derived case
arms). The TPU-native recast makes the rules DATA rather than code:

- a built-in table holds every configuration measured to date, each rule
  carrying its provenance (which benchmark log it came from);
- a JSON table can extend/override it (`CONFLUX_TPU_TUNE_TABLE` env var,
  or :func:`load_table`), so a chip tuning session updates dispatch
  decisions by committing a data file, not editing code;
- lookup is most-specific-wins (device > P > dtype > bounded N-range),
  later-loaded rules beating built-ins on ties, so an override table
  needs only the rows it changes.

Honesty contract: rules exist only where measurements exist. Unmeasured
configurations fall through to broader rules (ultimately the library
defaults) and the returned provenance says so — `recommended()` never
fabricates a tuning claim. The pre-decided default-flip criteria
(docs/ROUND3.md) apply: hardware A/B results land here as new rules, not
as silent default changes.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Mapping

_VALID_ALGOS = ("lu", "cholesky", "qr")


@dataclasses.dataclass(frozen=True)
class Rule:
    """One measured dispatch rule. `None` fields match anything; `device`
    is a substring of jax's lowercased `device_kind` (e.g. 'v5e', 'cpu').
    `n_lo`/`n_hi` bound the (unpadded) matrix dimension inclusively."""

    algo: str
    knobs: Mapping[str, Any]
    device: str | tuple | None = None  # substring(s); any-of for tuples
    P: int | None = None
    n_lo: int = 0
    n_hi: int | None = None
    dtype: str | None = None
    provenance: str = ""

    def matches(self, algo: str, N: int, P: int, dtype: str,
                device_kind: str) -> bool:
        dev = ((self.device,) if isinstance(self.device, str)
               else self.device)
        return (self.algo == algo
                and (dev is None or any(d in device_kind for d in dev))
                and (self.P is None or self.P == P)
                and self.n_lo <= N <= (self.n_hi if self.n_hi is not None
                                       else N)
                and (self.dtype is None or self.dtype == dtype))

    def specificity(self) -> int:
        return ((8 if self.device is not None else 0)
                + (4 if self.P is not None else 0)
                + (2 if self.dtype is not None else 0)
                + (1 if (self.n_lo > 0 or self.n_hi is not None) else 0))


@dataclasses.dataclass(frozen=True)
class Recommendation:
    knobs: dict
    provenance: str


# Library defaults double as the weakest rule per algo: what the entry
# points do when no knob is passed. Keeping them IN the table means
# recommended() always resolves, and the provenance string says honestly
# that nothing was measured for the query. `v` is deliberately None here:
# the un-passed tile default is ADAPTIVE (Cholesky's memory heuristic,
# each miniapp's own default), so an unmeasured rule must not override it
# — None knobs never overwrite (apply_auto contract); only measured rules
# pin a tile.
_DEFAULTS = {
    "lu": dict(precision="highest", v=None, panel_chunk=None,
               segs=(16, 16), tree="pairwise", update="segments",
               lookahead=False, election="gather"),
    "cholesky": dict(precision="highest", v=None, segs=(8, 8),
                     lookahead=False),
    "qr": dict(precision="highest", v=None, csegs=8, lookahead=False,
               tree="gather"),
}

_BUILTIN_RULES: list[Rule] = [
    # ----- catch-alls: library defaults, explicitly unmeasured -----
    *[Rule(algo=a, knobs=_DEFAULTS[a],
           provenance="library defaults — no measurement matches this "
           "configuration")
      for a in _VALID_ALGOS],
    # ----- single-chip v5e LU: the only hardware-measured core -----
    Rule(algo="lu", device=("v5e", "v5 lite"), P=1,
         n_lo=8192, n_hi=32768,
         dtype="float32",
         knobs=dict(_DEFAULTS["lu"], v=1024, panel_chunk=8192),
         provenance="BENCH_r01 10,446 GFLOP/s + round-2 tune 10,749 "
         "(data/benchmarks/ tpu logs): precision=highest chunk=8192 "
         "v=1024 best of the measured matrix; tree=flat/update=block "
         "flips pending the hardware A/B (docs/ROUND3.md criteria "
         "1-2; call-count evidence in "
         "data/benchmarks/election_callcount_r4.json)"),
    # bf16 storage rides the same structure; panel math is f32 either way
    Rule(algo="lu", device=("v5e", "v5 lite"), P=1, n_lo=8192,
         dtype="bfloat16",
         knobs=dict(_DEFAULTS["lu"], v=1024, panel_chunk=8192),
         provenance="structure from the f32 v5e measurements (BENCH_r01); "
         "bf16 trailing GEMMs share the chunking — no separate bf16 "
         "tune exists yet"),
    # ----- CPU-mesh rules from the committed sweep matrix -----
    # (data/benchmarks/summary.csv, README table: best rates at tile 256
    # for LU/Cholesky, 128 for QR; lookahead measured a net LOSS with no
    # overlap-capable runtime — LU +15% / QR +87%, DESIGN §8b)
    Rule(algo="lu", device="cpu",
         knobs=dict(_DEFAULTS["lu"], v=256, lookahead=False),
         provenance="CPU-mesh sweep (data/benchmarks/, README table): "
         "tile 256 best across grids; lookahead measured +15% on the "
         "no-overlap CPU backend"),
    Rule(algo="cholesky", device="cpu",
         knobs=dict(_DEFAULTS["cholesky"], v=256, lookahead=False),
         provenance="CPU-mesh sweep (data/benchmarks/): tile 256 best "
         "across grids"),
    Rule(algo="qr", device="cpu",
         knobs=dict(_DEFAULTS["qr"], v=128, lookahead=False),
         provenance="CPU-mesh sweep (data/benchmarks/): tile 128 best; "
         "lookahead measured +87% on the no-overlap CPU backend"),
    # ----- explicitly unmeasured hardware legs (honest fall-through) ---
    Rule(algo="cholesky", device=("v5e", "v5 lite"),
         knobs=_DEFAULTS["cholesky"],
         provenance="NO hardware measurement yet for Cholesky on TPU "
         "(VERDICT r3 item 4): library defaults"),
    Rule(algo="qr", device=("v5e", "v5 lite"),
         knobs=_DEFAULTS["qr"],
         provenance="NO hardware measurement yet for QR on TPU "
         "(VERDICT r3 item 4): library defaults"),
]

_loaded_rules: list[Rule] = []
_env_table_loaded = False


def _rules() -> list[Rule]:
    global _env_table_loaded
    if not _env_table_loaded:
        _env_table_loaded = True
        path = os.environ.get("CONFLUX_TPU_TUNE_TABLE")
        if path:
            load_table(path)
    return _BUILTIN_RULES + _loaded_rules


def load_table(path: str) -> int:
    """Append rules from a JSON file (a list of Rule-shaped objects; only
    `algo` and `knobs` are required). Later rules beat built-ins on
    specificity ties, so a tuning session's table needs only the rows it
    changes. Returns the number of rules added."""
    with open(path) as f:
        raw = json.load(f)
    if not isinstance(raw, list):
        raise ValueError(f"{path}: tune table must be a JSON list of rules")
    added = []
    for i, r in enumerate(raw):
        if not isinstance(r, dict) or "algo" not in r or "knobs" not in r:
            raise ValueError(
                f"{path}[{i}]: each rule needs at least algo + knobs")
        if r["algo"] not in _VALID_ALGOS:
            raise ValueError(
                f"{path}[{i}]: unknown algo {r['algo']!r} "
                f"(want one of {_VALID_ALGOS})")
        allowed = {f.name for f in dataclasses.fields(Rule)}
        unknown = set(r) - allowed
        if unknown:
            raise ValueError(
                f"{path}[{i}]: unknown rule fields {sorted(unknown)}")
        knobs = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in r["knobs"].items()}
        added.append(Rule(**{**r, "knobs": knobs}))
    _loaded_rules.extend(added)
    return len(added)


def reset_loaded_table() -> None:
    """Drop JSON-loaded rules (test hook; built-ins are immutable)."""
    global _env_table_loaded
    _loaded_rules.clear()
    _env_table_loaded = False


def detect_device_kind() -> str:
    """Lowercased device kind of device 0 ('cpu', 'tpu v5 lite', ...).
    NOTE: initializes a jax backend (and so claims the chip); callers
    that must not pass device_kind explicitly."""
    import jax

    return jax.devices()[0].device_kind.lower()


def recommended(algo: str, N: int, P: int = 1, dtype: str = "float32",
                device_kind: str | None = None) -> Recommendation:
    """The measured-best knob set for (algo, N, P, dtype, device).

    `P` is the total device count (grid volume). `device_kind=None`
    detects the current backend's device 0 (see `detect_device_kind`'s
    wedge caveat). The winning rule is the most specific match; its
    provenance names the measurement (or states that none exists)."""
    if algo not in _VALID_ALGOS:
        raise ValueError(f"unknown algo {algo!r} (want {_VALID_ALGOS})")
    if N < 1 or P < 1:
        raise ValueError(f"need positive N and P, got N={N} P={P}")
    dtype = str(dtype)
    if device_kind is None:
        device_kind = detect_device_kind()
    device_kind = device_kind.lower()
    best: Rule | None = None
    best_key = (-1, -1)
    for i, rule in enumerate(_rules()):
        if rule.matches(algo, N, P, dtype, device_kind):
            key = (rule.specificity(), i)  # ties: later-loaded wins
            if key > best_key:
                best, best_key = rule, key
    assert best is not None  # the catch-all rules always match
    return Recommendation(knobs=dict(best.knobs),
                          provenance=best.provenance)
