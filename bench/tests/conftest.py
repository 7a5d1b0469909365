"""Tests of the benchmark itself, on the CPU at sizes a test run holds:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

The harness's modules and the program are imported from the checkout."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
