"""Tests of the benchmark harness, on the CPU at a small size.

They import the harness's modules from ``bench/`` and the system under
test from ``src/``, and drive ``run.execute`` without its look for a chip.
"""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("REPRO_PLAN_CACHE", "off")
