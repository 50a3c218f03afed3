"""Puts ``bench/`` (the harness) and ``src/`` (the program) on the path
for the benchmark's own tests."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
