"""Tests of the benchmark itself. They live beside it and leave ``tests/`` and
its count alone: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
