"""These tests run on the CPU: ``python -m pytest benchmark/tests -q``.
They never print a result line under a device's name."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(BENCH, "reference"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
