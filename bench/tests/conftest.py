"""Tests of the benchmark itself, run by path:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They put bench/ and src/ on the import path the way bench/run.py does."""
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH_DIR, os.path.join(os.path.dirname(BENCH_DIR), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
