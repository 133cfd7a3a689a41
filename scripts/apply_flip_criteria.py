"""Apply the pre-decided default-flip criteria (docs/ROUND3.md) to
measured A/B logs — so the one-shot chip session ends in DECISIONS, not
in logs waiting for a human.

Parses `scripts/tpu_tune.py` result lines (the format the recovery
watcher's queue produces in data/benchmarks/round*-recovery.txt):

    algo=lu precision=highest chunk=8192 v=1024 segs=lib tree=flat \
        swap=xla update=segments: 11234.0 GFLOP/s
        residual=2.9e-05

and evaluates each criterion against its matched-pair baseline (same
config except the flipped knob):

  1. tree='flat' becomes the default if it gains >= 2% with a clean
     full-scale residual (<= 3.2e-5, the f32-HIGHEST level — DESIGN §14:
     a hot-loop rewrite is adopted ONLY with an at-scale residual gate).
  2. update='block' likewise.
  3. (historical) swap='dma' was decided only by its staged probe; the
     kernel was deleted unadopted in round 4 when the chip never
     recovered — any dma rows in old logs are ignored.
  4. panel_chunk=12288 as a bench-local override if it survives + wins.

Output: a decision per criterion (ADOPT / KEEP / NO-DATA, with the
numbers), and with --emit-rules a JSON autotune table
(conflux_tpu.autotune.load_table format) encoding the winners with
their measurement provenance.

Usage:
    python scripts/apply_flip_criteria.py data/benchmarks/round4-recovery.txt \
        [--emit-rules data/tune_table_r4.json]
"""

from __future__ import annotations

import argparse
import json
import re
import sys

RESIDUAL_GATE = 3.2e-5  # f32-HIGHEST level at N=32768 (DESIGN §14)
GAIN_BAR = 0.02

# The all-defaults baseline config every flip criterion pairs against
# (the watcher queue's plain highest:8192:1024 row). A decisive pair
# must match this on every knob except the flipped one — a flip may
# not be adopted off a pairing that varies some OTHER knob (e.g.
# tree=flat winning only under segs=32x16), per ADVICE r4 #2.
BASELINE_CONFIG = {"algo": "lu", "precision": "highest", "chunk": "8192",
                   "v": "1024", "segs": "lib", "tree": "pairwise",
                   "update": "segments", "swap": "xla",
                   "lookahead": "off"}


def _on_baseline(rec: dict, knob: str) -> bool:
    return all(rec.get(k) == v for k, v in BASELINE_CONFIG.items()
               if k != knob)

_LINE = re.compile(
    r"algo=(?P<algo>\w+) precision=(?P<precision>\w+) "
    r"chunk=(?P<chunk>\w+) v=(?P<v>\d+) segs=(?P<segs>[\w|x]+) "
    r"tree=(?P<tree>\w+) (?:swap=(?P<swap>\w+) )?"
    r"(?:lookahead=(?P<lookahead>\w+) )?update=(?P<update>\w+): "
    r"(?P<gflops>[\d.]+) GFLOP/s")
_RES = re.compile(r"residual=(?P<res>[\d.eE+-]+)")


def parse_log(text: str) -> list[dict]:
    """All tune records in `text`, each with its following residual line
    (residual None when the line is missing or FAILED)."""
    records = []
    for line in text.splitlines():
        m = _LINE.search(line)
        if m:
            d = m.groupdict()
            # pre-round-4 logs carry a swap field; post-removal lines
            # don't. Normalize so cross-era records still pair (the
            # only swap value a surviving record can mean is 'xla').
            d["swap"] = d["swap"] or "xla"
            # pre-round-5 logs predate the lookahead token; the only
            # value those lines can mean is the library default (off)
            d["lookahead"] = d["lookahead"] or "off"
            d["gflops"] = float(d["gflops"])
            d["residual"] = None
            records.append(d)
            continue
        r = _RES.search(line)
        if r and records and records[-1]["residual"] is None \
                and "FAILED" not in line:
            records[-1]["residual"] = float(r.group("res"))
    return records


def _clean(r: dict) -> bool:
    return r["residual"] is not None and r["residual"] <= RESIDUAL_GATE


def _best(records: list[dict], algo: str = "lu") -> dict | None:
    ok = [r for r in records if r["algo"] == algo and _clean(r)]
    return max(ok, key=lambda r: r["gflops"]) if ok else None


def evaluate_flip(records: list[dict], knob: str, flipped: str,
                  baseline: str) -> dict:
    """Criterion outcome for one knob: best matched pair (same config
    modulo `knob`), gain, and the ADOPT/KEEP/NO-DATA decision.

    The decisive pair is restricted to the ALL-DEFAULTS baseline
    config (BASELINE_CONFIG modulo `knob`): a flip that wins only in
    combination with some other non-default knob must not flip the
    global default (ADVICE r4 #2). Off-baseline flip rows never decide;
    they are surfaced in the detail line as context (a NO-DATA mention,
    or a re-measure hint when one out-gains the decisive pair).

    BOTH sides of the pair prefer residual-CLEAN records: a timing
    whose residual check failed can never be adopted (DESIGN §14), and
    a dirty baseline timing is equally untrustworthy (the §14 forensics
    saw corrupted runs time fast) — so a clean flip is judged against
    the best CLEAN baseline, and dirty records on either side are
    considered only when no clean one exists."""
    flips = [r for r in records if r[knob] == flipped and r["algo"] == "lu"
             and _on_baseline(r, knob)]
    bases = [r for r in records if r[knob] == baseline and r["algo"] == "lu"
             and _on_baseline(r, knob)]
    off = [r for r in records if r[knob] == flipped and r["algo"] == "lu"
           and not _on_baseline(r, knob)]
    if not flips or not bases:
        extra = (f"; {len(off)} off-baseline {flipped} row(s) observed "
                 "(informational only — cannot decide a default)"
                 if off else "")
        return {"knob": knob, "decision": "NO-DATA",
                "detail": f"no all-defaults {flipped}-vs-{baseline} pair "
                f"in the logs (queue item not yet run?){extra}"}
    clean_flips = [f for f in flips if _clean(f)]
    f = max(clean_flips or flips, key=lambda r: r["gflops"])
    b = max([r for r in bases if _clean(r)] or bases,
            key=lambda r: r["gflops"])
    gain = f["gflops"] / b["gflops"] - 1.0
    res_ok = _clean(f)
    adopt = gain >= GAIN_BAR and res_ok
    detail = (f"{flipped} {f['gflops']:.0f} vs {baseline} "
              f"{b['gflops']:.0f} GFLOP/s ({gain:+.1%}); residual "
              f"{f['residual'] if f['residual'] is not None else 'MISSING'}"
              f" (gate {RESIDUAL_GATE})")
    best_off = max((r for r in off if _clean(r)),
                   key=lambda r: r["gflops"], default=None)
    if best_off is not None and best_off["gflops"] > f["gflops"]:
        diffs = " ".join(f"{k}={best_off[k]}" for k, v in
                         BASELINE_CONFIG.items()
                         if k != knob and best_off.get(k) != v)
        detail += (f"; off-baseline context: {flipped} reached "
                   f"{best_off['gflops']:.0f} GFLOP/s under {diffs} "
                   "(cannot decide a default — consider a re-measure "
                   "with that config as the new baseline)")
    if adopt:
        decision = "ADOPT"
    elif not res_ok:
        decision = "KEEP (residual gate failed — DESIGN §14)"
    else:
        decision = f"KEEP (gain below the {GAIN_BAR:.0%} bar)"
    return {"knob": knob, "decision": decision, "detail": detail,
            "flip": f, "base": b, "gain": gain}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("logs", nargs="+", help="watcher/tune log files")
    ap.add_argument("--emit-rules", default=None, metavar="JSON",
                    help="write the winning configs as an autotune rules "
                    "table (conflux_tpu.autotune.load_table format)")
    args = ap.parse_args(argv)

    text = ""
    for p in args.logs:
        with open(p) as f:
            text += f.read() + "\n"
    records = parse_log(text)
    print(f"parsed {len(records)} tune records from {len(args.logs)} logs")

    # headline check: the bench.py JSON line, vs the best prior measured
    # rate (round-2 tune logs) — the BENCH_r04 'done' bar of VERDICT r3
    HEADLINE_BAR = 10749.0
    for line in text.splitlines():
        if '"metric"' in line and "GFLOP/s" in line:
            try:
                d = json.loads(line)
            except ValueError:
                continue
            if "LU" in d.get("metric", ""):
                ok = (d["value"] >= HEADLINE_BAR
                      and d.get("residual", 1) <= RESIDUAL_GATE)
                print(f"headline: {d['value']:.0f} GFLOP/s residual "
                      f"{d.get('residual')} -> "
                      f"{'MEETS' if ok else 'BELOW'} the "
                      f"{HEADLINE_BAR:.0f} prior-best bar")
    if not records:
        print("no records: the measurement queue has not produced tune "
              "lines yet (criteria cannot be applied)")
        return 1

    outcomes = [
        evaluate_flip(records, "tree", "flat", "pairwise"),
        evaluate_flip(records, "update", "block", "segments"),
        evaluate_flip(records, "chunk", "12288", "8192"),
        # round-5 criterion (VERDICT r4 item 8): lookahead stays off
        # unless a single-chip A/B shows a real gain with a clean
        # residual (the CPU mesh measured it +15% SLOWER on LU)
        evaluate_flip(records, "lookahead", "on", "off"),
    ]
    for o in outcomes:
        print(f"criterion {o['knob']}: {o['decision']}")
        if "detail" in o:
            print(f"    {o['detail']}")
    dma = [r for r in records if r.get("swap") == "dma"]
    if dma:
        print(f"note: {len(dma)} swap=dma rows in the logs are historical "
              "— the kernel was deleted unadopted in round 4")

    best = _best(records)  # LU only: the emitted rule is an LU rule
    if best:
        print(f"best residual-clean LU record: {best['gflops']:.0f} "
              f"GFLOP/s ({best['precision']}:{best['chunk']}:{best['v']} "
              f"tree={best['tree']} update={best['update']})")

    if args.emit_rules:
        if best is None:
            # never silently skip the file a downstream
            # CONFLUX_TPU_TUNE_TABLE consumer expects
            print(f"NOT writing {args.emit_rules}: no residual-clean LU "
                  "record exists (every timing's residual check failed "
                  "or is missing) — criteria cannot adopt anything")
            return 2
        # the rule encodes the printed DECISIONS, not the raw best
        # record: a KEEP'd flip (or a 12288 row that merely timed well)
        # must not become a table default through the back door.
        # precision/v come from the best clean LU record (the measured
        # headline family); tree/update follow their criterion;
        # chunk=12288 is bench-local only (criterion 4) so the rule
        # keeps 8192, with the outcome recorded in the provenance.
        tree_o, update_o, chunk_o, la_o = outcomes
        knobs = {"precision": best["precision"], "v": int(best["v"]),
                 "panel_chunk": 8192,
                 "tree": "flat" if tree_o["decision"] == "ADOPT"
                 else "pairwise",
                 "update": "block" if update_o["decision"] == "ADOPT"
                 else "segments",
                 "lookahead": la_o["decision"] == "ADOPT"}
        rules = [{
            "algo": "lu", "device": ["v5e", "v5 lite"], "P": 1,
            "n_lo": 8192, "n_hi": 32768, "dtype": "float32",
            "knobs": knobs,
            "provenance": (f"chip-session A/B ({', '.join(args.logs)}): "
                           f"best clean {best['gflops']:.0f} GFLOP/s "
                           f"residual {best['residual']:.2e}; criteria: "
                           + "; ".join(f"{o['knob']}={o['decision']}"
                                       for o in outcomes)
                           + "; chunk=12288 bench-local only (ROUND3.md)"),
        }]
        with open(args.emit_rules, "w") as f:
            json.dump(rules, f, indent=1)
        print(f"wrote {args.emit_rules}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
