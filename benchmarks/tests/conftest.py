"""Tests of the benchmark's own code.  Run them on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They import the harness the way ``run.py`` does, by putting the benchmark's
directory and the repo's root on the path.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
