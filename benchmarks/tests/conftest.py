"""The benchmark's own tests run on the CPU: ``python -m pytest
benchmarks/tests``.  They never measure anything."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (BENCH, os.path.dirname(BENCH))
                if p not in sys.path]
