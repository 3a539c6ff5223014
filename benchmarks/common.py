"""Shared benchmark utilities: CSV emission, smoke mode, JSON records.

``benchmarks.run --smoke`` flips :data:`SMOKE` before any suite runs;
each suite consults it to shrink shapes/grids/reps so the whole harness
finishes in CI seconds — the point is that benchmark SCRIPTS cannot rot,
not that smoke numbers mean anything.  ``--csv PATH`` tees every
``emit`` row to a file (uploaded as a CI artifact).

``pack_bench --bench-json PATH`` additionally collects its structured
records (:func:`add_record`) into a JSON artifact — the pinned
``BENCH_pack.json`` byte ratios that ``tools/check_bench.py`` gates.
"""
from __future__ import annotations

from typing import Optional, TextIO

#: True under ``benchmarks.run --smoke``: tiny shapes, 1 warmup / 1 rep.
SMOKE = False

_CSV: Optional[TextIO] = None

#: collected structured records when ``--bench-json`` is active
_JSON: Optional[list] = None


def set_smoke(on: bool) -> None:
    global SMOKE
    SMOKE = on


def set_csv(fh: Optional[TextIO]) -> None:
    global _CSV
    _CSV = fh


def set_json(records: Optional[list]) -> None:
    global _JSON
    _JSON = records


def add_record(rec: dict) -> None:
    """Append one structured record to the ``--bench-json`` collection
    (no-op when JSON collection is off)."""
    if _JSON is not None:
        _JSON.append(rec)


def bench_reps(warmup: int = 2, iters: int = 5) -> dict:
    """Requested reps, collapsed to (1, 1) in smoke mode."""
    return ({"warmup": 1, "iters": 1} if SMOKE
            else {"warmup": warmup, "iters": iters})


def emit(name: str, us_per_call: float, derived: str = ""):
    row = f"{name},{us_per_call:.1f},{derived}"
    print(row, flush=True)
    if _CSV is not None:
        _CSV.write(row + "\n")
        _CSV.flush()
