"""Bench-baseline drift gate (ISSUE 15 satellite).

Every bench in this repo prints ONE JSON line; until now those lines
lived in ad-hoc per-round JSON artifacts and prose in docs/BENCHMARKS.md
— nothing machine-readable tracked the trajectory, so a silent 2×
regression between PRs would only surface if a human re-read the docs.
This tool normalizes a bench's JSON line into `docs/baselines/
<bench>.<platform>.json` and flags relative drift beyond tolerance on
the next run.

Normalization (`normalize`): the record is flattened to dot-keyed
leaves and split into
- `values`  — plain numerics, compared with RELATIVE tolerance
  (default 25% — bench noise on shared hosts is real; the point is
  catching step changes, not basis points);
- `gates`   — strings, bools, and any numeric whose key smells like a
  correctness artifact (digest/checksum/crc/parity/pass...): these
  must match EXACTLY. Drift in a gate is a correctness failure, never
  noise, so gates stay hard even under `--smoke`.

Usage:
    python benchmarks/compare_baselines.py --update receive_leg < one.json
    python bench.py | python benchmarks/compare_baselines.py --check bench
    ... --check bench --smoke        # CI: drift is advisory (exit 0),
                                     # gate mismatches still exit 1

Exit codes: 0 ok/advisory, 1 gate mismatch (always) or drift
(non-smoke), 2 usage/missing-input errors. A missing baseline for this
(bench, platform) pair is advisory: it prints the `--update` command
and exits 0 — first runs on a new platform must not break CI.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, Tuple

BASELINE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "docs", "baselines",
)

# Numeric leaves whose names mark correctness artifacts — exact-match
# gates, never tolerance-compared.
GATE_KEY_RE = re.compile(
    r"(digest|checksum|crc|parity|golden|byte_identical|pass)", re.I
)

# Key SEGMENTS that identify the run but should neither gate nor
# drift (free-text method notes, timestamps, artifact paths). Exact
# segment match — a substring test would eat e.g. "detail.*" ("tail")
# or "dispatch_overhead_ms" ("path").
IGNORE_SEGMENTS = frozenset(
    {"method", "written_at", "timestamp", "path", "cmd", "tail", "note"}
)


def _ignored(key: str) -> bool:
    return any(seg.lower() in IGNORE_SEGMENTS for seg in key.split("."))

DEFAULT_TOLERANCE = 0.25


def _flatten(obj, prefix="") -> Dict[str, object]:
    out: Dict[str, object] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(_flatten(v, f"{prefix}{k}."))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            out.update(_flatten(v, f"{prefix}{i}."))
    else:
        out[prefix[:-1]] = obj
    return out


def normalize(record: dict, bench: str) -> dict:
    """One bench JSON line → the stored baseline shape: numeric
    `values` (tolerance-compared), exact-match `gates`, and the
    platform key the baseline file is selected by."""
    flat = _flatten(record)
    values: Dict[str, float] = {}
    gates: Dict[str, object] = {}
    platform = "unknown"
    for key, v in flat.items():
        leaf = key.rsplit(".", 1)[-1]
        if leaf == "platform":
            platform = str(v)
            continue
        if _ignored(key):
            continue
        if isinstance(v, bool) or isinstance(v, str) or v is None:
            gates[key] = v
        elif isinstance(v, (int, float)):
            if GATE_KEY_RE.search(key):
                gates[key] = v
            else:
                values[key] = float(v)
    return {"bench": bench, "platform": platform,
            "values": values, "gates": gates}


def baseline_path(bench: str, platform: str) -> str:
    return os.path.join(BASELINE_DIR, f"{bench}.{platform}.json")


def compare(baseline: dict, current: dict,
            tolerance: float = DEFAULT_TOLERANCE
            ) -> Tuple[list, list]:
    """→ (gate_failures, drifts). Gate failures: [(key, base, cur)].
    Drifts: [(key, base, cur, rel)] where rel = |cur-base|/max(|base|,
    tiny). Keys present on only one side are DRIFT (shape changed —
    worth a look, not a hard failure) unless they are gates (a vanished
    checksum field IS a failure)."""
    gate_failures, drifts = [], []
    b_gates, c_gates = baseline.get("gates", {}), current.get("gates", {})
    for key in sorted(set(b_gates) | set(c_gates)):
        b, c = b_gates.get(key, "<absent>"), c_gates.get(key, "<absent>")
        if b != c:
            gate_failures.append((key, b, c))
    b_vals, c_vals = baseline.get("values", {}), current.get("values", {})
    for key in sorted(set(b_vals) | set(c_vals)):
        if key not in b_vals or key not in c_vals:
            drifts.append((key, b_vals.get(key), c_vals.get(key), None))
            continue
        b, c = b_vals[key], c_vals[key]
        rel = abs(c - b) / max(abs(b), 1e-12)
        if rel > tolerance:
            drifts.append((key, b, c, rel))
    return gate_failures, drifts


def _read_record(args) -> dict:
    raw = (open(args.file).read() if args.file else sys.stdin.read())
    # A whole-file JSON document first (a driver artifact that wraps
    # the line under "parsed"); else benches may emit warnings before
    # their JSON line — take the LAST line that parses as a JSON object.
    try:
        rec = json.loads(raw)
        if isinstance(rec, dict):
            return rec.get("parsed", rec) if "parsed" in rec else rec
    except ValueError:
        pass
    last_err = None
    for line in reversed([l for l in raw.splitlines() if l.strip()]):
        try:
            rec = json.loads(line)
        except ValueError as e:
            last_err = e
            continue
        if isinstance(rec, dict):
            # Driver artifacts wrap the line under "parsed".
            return rec.get("parsed", rec) if "parsed" in rec else rec
    raise SystemExit(f"no JSON object line found in input ({last_err})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--update", metavar="BENCH",
                    help="normalize stdin/--file into the baseline store")
    ap.add_argument("--check", metavar="BENCH",
                    help="compare stdin/--file against the stored baseline")
    ap.add_argument("--file", help="read the bench JSON from a file "
                                   "instead of stdin")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help=f"relative drift tolerance (default "
                         f"{DEFAULT_TOLERANCE})")
    ap.add_argument("--smoke", action="store_true",
                    help="advisory mode: drift prints warnings but exits 0 "
                         "(gates stay hard)")
    ap.add_argument("--baseline-dir", default=BASELINE_DIR,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if bool(args.update) == bool(args.check):
        ap.error("exactly one of --update / --check is required")
    bench = args.update or args.check
    current = normalize(_read_record(args), bench)
    path = os.path.join(args.baseline_dir,
                        f"{bench}.{current['platform']}.json")
    if args.update:
        os.makedirs(args.baseline_dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(current, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"baseline written: {path} "
              f"({len(current['values'])} values, "
              f"{len(current['gates'])} gates)")
        return 0
    if not os.path.exists(path):
        print(f"no baseline for ({bench}, {current['platform']}) — "
              f"advisory pass; record one with:\n"
              f"  ... | python benchmarks/compare_baselines.py "
              f"--update {bench}")
        return 0
    with open(path) as f:
        baseline = json.load(f)
    gate_failures, drifts = compare(baseline, current, args.tolerance)
    for key, b, c in gate_failures:
        print(f"GATE MISMATCH {key}: baseline={b!r} current={c!r}")
    for key, b, c, rel in drifts:
        if rel is None:
            print(f"DRIFT (shape) {key}: baseline={b} current={c}")
        else:
            print(f"DRIFT {key}: baseline={b:g} current={c:g} "
                  f"({100 * rel:.1f}% > {100 * args.tolerance:.0f}%)")
    if gate_failures:
        return 1
    if drifts and not args.smoke:
        return 1
    if drifts:
        print(f"(smoke: {len(drifts)} drift(s) advisory-only)")
    if not gate_failures and not drifts:
        print(f"ok: within {100 * args.tolerance:.0f}% of {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
