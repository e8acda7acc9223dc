#!/usr/bin/env python
"""Benchmark regression guard: compare a fresh BENCH_*.json artifact against
the prior checked-in baseline, row by row, failing loudly on big slowdowns.

    python scripts/bench_guard.py BENCH_PR3.json --baseline BENCH_PR2.json

Rows are matched by ``name``; only rows present in both artifacts are
compared.  A row regresses when ``us_per_call`` grew by more than
``--tolerance`` (default 2.0x, override with env ``BENCH_GUARD_TOL``).
Rows below the ``--min-us`` noise floor in the *baseline* are skipped —
sub-100 us wall numbers on a shared CPU container are scheduler noise —
as are derived-only rows (``us_per_call == 0``).  Improvements are
reported but never fail.

``--require PATTERN`` (repeatable, fnmatch) asserts the fresh artifact
*contains* at least one row matching each pattern — a presence guard for
rows whose absence would silently drop coverage (e.g. an artifact written
with ``--only`` that skipped a module).

Exit status 1 on any regression or missing required row, so
``scripts/ci.sh`` fails the build.
"""
from __future__ import annotations

import argparse
import fnmatch
import json
import os
import sys


def load_rows(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    return {r["name"]: float(r["us_per_call"]) for r in doc["rows"]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("artifact", help="fresh benchmark JSON to check")
    p.add_argument("--baseline", required=True,
                   help="prior checked-in benchmark JSON")
    p.add_argument("--tolerance", type=float,
                   default=float(os.environ.get("BENCH_GUARD_TOL", "2.0")),
                   help="max allowed new/old us_per_call ratio (default 2.0;"
                        " env BENCH_GUARD_TOL overrides)")
    # Sub-150 us rows on the shared CPU container swing >3x between
    # identical runs (measured on fig1_insert/none/threads1); anything
    # below that floor is scheduler noise, not signal.
    p.add_argument("--min-us", type=float, default=150.0,
                   help="skip rows whose baseline is below this noise floor")
    p.add_argument("--require", action="append", default=[],
                   metavar="PATTERN",
                   help="fail unless the fresh artifact has >=1 row matching "
                        "this fnmatch pattern (repeatable)")
    args = p.parse_args(argv)

    new = load_rows(args.artifact)
    old = load_rows(args.baseline)

    missing = [pat for pat in args.require
               if not any(fnmatch.fnmatch(name, pat) for name in new)]
    if missing:
        print(f"bench_guard: {args.artifact} is missing required rows:")
        for pat in missing:
            print(f"  no row matches {pat!r}")
        return 1

    shared = sorted(set(new) & set(old))
    if not shared:
        print(f"bench_guard: no shared rows between {args.artifact} and "
              f"{args.baseline}; nothing to compare")
        return 0

    regressions, compared = [], 0
    print(f"bench_guard: {args.artifact} vs {args.baseline} "
          f"(tolerance {args.tolerance:.2f}x, noise floor {args.min_us:.0f} us)")
    for name in shared:
        o, n = old[name], new[name]
        if o <= 0 or n <= 0 or o < args.min_us:
            continue
        compared += 1
        ratio = n / o
        flag = ""
        if ratio > args.tolerance:
            flag = "  << REGRESSION"
            regressions.append((name, o, n, ratio))
        elif ratio < 1 / args.tolerance:
            flag = "  (improved)"
        print(f"  {name}: {o:.0f} -> {n:.0f} us  ({ratio:.2f}x){flag}")

    if regressions:
        print(f"\nbench_guard: {len(regressions)}/{compared} rows regressed "
              f"past {args.tolerance:.2f}x:")
        for name, o, n, ratio in regressions:
            print(f"  {name}: {o:.0f} -> {n:.0f} us ({ratio:.2f}x)")
        print("If intentional (e.g. a semantics trade), rerun with "
              "BENCH_GUARD_TOL=<higher> and justify in the PR.")
        return 1
    print(f"bench_guard: OK ({compared} rows within tolerance)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
