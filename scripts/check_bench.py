#!/usr/bin/env python
"""Validate committed BENCH_*.json artifacts against the shared envelope.

Every gated benchmark (benchmarks/bench_router.py, bench_router_faults.py,
bench_dsg_serving.py, bench_decode_loop.py, bench_prefix_sharing.py) wraps its payload in the envelope from
benchmarks/common.py:

  {"name":       str,
   "gates":      [{"description": str, "threshold": num, "value": num,
                   "passed": bool}, ...],      # non-empty
   "ratio":      num,                          # the headline ratio
   "timestamps": {"start": iso8601, "end": iso8601},  # end >= start
   "results":    dict}                         # benchmark-specific

This script checks every committed BENCH_*.json parses, carries exactly
that shape, and has every gate passed — a committed artifact from a red
run (the benches write before raising, so failures leave diagnosable
files) must never land.  Extra top-level keys are rejected: they belong
under "results", where dashboards expect benchmark-specific payloads.

  python scripts/check_bench.py              # repo root artifacts
  python scripts/check_bench.py --root DIR   # testing

No dependencies beyond the standard library.
"""
from __future__ import annotations

import argparse
import datetime
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

TOP_KEYS = {"name", "gates", "ratio", "timestamps", "results"}
GATE_KEYS = {"description", "threshold", "value", "passed"}


def _num(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def _iso(ts) -> datetime.datetime | None:
    try:
        t = datetime.datetime.fromisoformat(ts)
    except (TypeError, ValueError):
        return None
    if t.tzinfo is None:           # naive timestamps compare as UTC
        t = t.replace(tzinfo=datetime.timezone.utc)
    return t


def check_file(path: Path) -> list:
    """All envelope violations in one artifact (empty list = clean)."""
    try:
        data = json.loads(path.read_text(encoding="utf-8"))
    except (ValueError, UnicodeDecodeError) as e:
        return [f"not valid JSON: {e}"]
    if not isinstance(data, dict):
        return ["top level must be an object"]

    bad = []
    missing, extra = TOP_KEYS - set(data), set(data) - TOP_KEYS
    if missing:
        bad.append(f"missing keys: {sorted(missing)}")
    if extra:
        bad.append(f"unexpected top-level keys {sorted(extra)} "
                   f"(benchmark payloads belong under 'results')")

    if "name" in data and not (isinstance(data["name"], str)
                               and data["name"]):
        bad.append("'name' must be a non-empty string")
    if "ratio" in data and not _num(data["ratio"]):
        bad.append("'ratio' must be a number")
    if "results" in data and not isinstance(data["results"], dict):
        bad.append("'results' must be an object")

    gates = data.get("gates")
    if gates is not None:
        if not isinstance(gates, list) or not gates:
            bad.append("'gates' must be a non-empty list")
        else:
            for i, g in enumerate(gates):
                if not isinstance(g, dict) or set(g) != GATE_KEYS:
                    bad.append(f"gates[{i}] must have exactly "
                               f"{sorted(GATE_KEYS)}")
                    continue
                if not (isinstance(g["description"], str)
                        and g["description"]):
                    bad.append(f"gates[{i}].description must be a "
                               f"non-empty string")
                if not (_num(g["threshold"]) and _num(g["value"])):
                    bad.append(f"gates[{i}] threshold/value must be "
                               f"numbers")
                if not isinstance(g["passed"], bool):
                    bad.append(f"gates[{i}].passed must be a bool")
                elif not g["passed"]:
                    bad.append(f"gates[{i}] FAILED: "
                               f"{g.get('description')} "
                               f"(value {g.get('value')} vs threshold "
                               f"{g.get('threshold')}) — a red-run "
                               f"artifact must not be committed")

    ts = data.get("timestamps")
    if ts is not None:
        if not isinstance(ts, dict) or set(ts) != {"start", "end"}:
            bad.append("'timestamps' must be {'start', 'end'}")
        else:
            start, end = _iso(ts["start"]), _iso(ts["end"])
            if start is None or end is None:
                bad.append("timestamps must be ISO-8601 strings")
            elif end < start:
                bad.append(f"timestamps end < start "
                           f"({ts['end']} < {ts['start']})")
    return bad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=str(REPO),
                    help="directory whose BENCH_*.json files to check "
                         "(default: repo root)")
    args = ap.parse_args()

    root = Path(args.root)
    files = sorted(root.glob("BENCH_*.json"))
    if not files:
        print(f"FAIL no BENCH_*.json found under {root} — gated "
              f"benchmarks commit their artifacts")
        sys.exit(1)

    failures = 0
    for path in files:
        problems = check_file(path)
        for p in problems:
            print(f"FAIL {path.name}: {p}")
        failures += len(problems)
    if failures:
        sys.exit(1)
    print(f"ok: {len(files)} BENCH_*.json artifacts match the shared "
          f"envelope, all gates green")


if __name__ == "__main__":
    main()
