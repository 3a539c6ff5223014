#!/usr/bin/env python
"""Gate the pinned packed-container byte ratios (``BENCH_pack.json``).

    python tools/check_bench.py BENCH_pack.json bench-pack-ci.json

Compares a freshly-measured ``benchmarks.pack_bench --bench-json``
artifact against the committed baseline:

  * each record's ``speedup`` field — fixed-L bytes / variable-width
    bytes on the same parameters — may not regress by more than 20%;
  * every baseline record must still be present (same kind + name).

Exit status 1 on any failure, with a per-record report either way.
"""
from __future__ import annotations

import json
import sys

TOLERANCE = 0.20  # max allowed relative ratio regression


def _key(rec):
    return (rec["kind"], rec["name"])


def check(base_doc: dict, new_doc: dict) -> list:
    failures = []
    if base_doc.get("schema") != new_doc.get("schema"):
        failures.append(f"schema mismatch: {base_doc.get('schema')} vs "
                        f"{new_doc.get('schema')}")
        return failures
    if base_doc.get("mode") != new_doc.get("mode"):
        failures.append(
            f"mode mismatch ({base_doc.get('mode')} baseline vs "
            f"{new_doc.get('mode')} candidate): smoke-mode ratios are not "
            f"comparable to full-mode ones")
        return failures
    new_by_key = {_key(r): r for r in new_doc.get("records", [])}
    for b in base_doc.get("records", []):
        k = _key(b)
        n = new_by_key.get(k)
        tag = f"{k[0]}/{k[1]}"
        if n is None:
            failures.append(f"{tag}: record missing from candidate")
            continue
        got = n.get("speedup")
        if got is None:
            failures.append(f"{tag}: speedup missing from candidate")
            continue
        floor = b["speedup"] * (1 - TOLERANCE)
        status = "ok" if got >= floor else "FAIL"
        print(f"{status:4s} {tag:32s} speedup {b['speedup']:6.2f}x -> "
              f"{got:6.2f}x (floor {floor:.2f}x)")
        if status == "FAIL":
            failures.append(
                f"{tag}: speedup regressed {b['speedup']:.2f}x -> "
                f"{got:.2f}x (> {TOLERANCE:.0%} drop)")
    return failures


def main(argv) -> int:
    if len(argv) != 3:
        print(__doc__)
        return 2
    with open(argv[1]) as f:
        base = json.load(f)
    with open(argv[2]) as f:
        new = json.load(f)
    failures = check(base, new)
    if failures:
        print("\ncheck_bench FAILURES:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print(f"\ncheck_bench: all {len(base.get('records', []))} records "
          f"within {TOLERANCE:.0%} of the pinned trajectory")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
