"""The benchmark's own tests: `python -m pytest benchmark/tests -q` with
JAX_PLATFORMS=cpu. The modules under benchmark/ import one another by bare
name, as `run.py` makes them when it is the command."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)
