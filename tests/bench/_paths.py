"""Puts the checkout's root and ``src/`` on ``sys.path`` for the tests of
the benchmark (``bench/`` is run as a script, not installed)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
