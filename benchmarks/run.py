"""Benchmark harness — one module per paper table/figure + system extras.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).

  table1_storage       paper Table 1 (scheme storage costs) + MEASURED
                       packed-container / checkpoint bytes (ISSUE 5)
  table2_scheme        paper Table 2 (eq.2 vs eq.4 accuracy, no retrain)
  table3_sweep         paper Table 3 (L_W x L_I accuracy-drop grid) + E5
  table4_nsr           paper Table 4 (per-layer SNR: measured vs model)
  blocksize_ablation   E10 TPU K-tile block-size ablation (beyond paper)
  faults_bench         E15 fault endurance: NSR / top-1 agreement vs
                       bit-error rate x L x target (ISSUE 7 acceptance)
  cnn_train            E16 BFP train-to-accuracy: quantized backward
                       GEMMs + compressed gradient exchange at L=4..12
                       vs float baseline (ISSUE 8 acceptance)
  pack_bench           E18 packed-container bytes: fixed-L vs
                       variable-width (ISSUE 10 acceptance).  Pinned
                       trajectory in BENCH_pack.json, written by
                       ``python -m benchmarks.pack_bench --bench-json``
                       (own schema ``pack-1``, own CI gate)

These suites reproduce the paper's accuracy and error numbers and count
bytes; a time they print is a host CPU time, not speed.  Speed is
measured on the chip by
``bench/run.py`` (cells in ``BENCHMARK.json``; findings in PERF.md).

Flags:
  --smoke       tiny shapes, 1 rep — CI rot-check mode (the numbers are
                meaningless; the scripts running end-to-end is the point)
  --csv PATH    tee every emitted row to PATH (CI uploads it)
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (blocksize_ablation, cnn_train, common, faults_bench,
                        pack_bench, table1_storage, table2_scheme,
                        table3_sweep, table4_nsr)

_ALL = {
    "table1": table1_storage.run,
    "table2": table2_scheme.run,
    "table3": table3_sweep.run,
    "table4": table4_nsr.run,
    "blocksize": blocksize_ablation.run,
    "faults": faults_bench.run,
    "cnn_train": cnn_train.run,
    "pack": pack_bench.run,
}


def main() -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.run")
    ap.add_argument("names", nargs="*", metavar="suite",
                    help=f"suites to run (default: all of {list(_ALL)})")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / 1 rep (CI rot check)")
    ap.add_argument("--csv", metavar="PATH",
                    help="also write CSV rows to PATH")
    args = ap.parse_args()
    names = args.names or list(_ALL)
    unknown = [n for n in names if n not in _ALL]
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; available: {list(_ALL)}")
    common.set_smoke(args.smoke)
    fh = open(args.csv, "w") if args.csv else None
    common.set_csv(fh)

    print("name,us_per_call,derived")
    if fh:
        fh.write("name,us_per_call,derived\n")
    failures = 0
    for n in names:
        t0 = time.time()
        try:
            _ALL[n]()
        except Exception:
            failures += 1
            traceback.print_exc()
        print(f"# {n} done in {time.time() - t0:.1f}s", flush=True)
    if fh:
        fh.close()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
