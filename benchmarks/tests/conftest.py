"""CPU tests of the benchmark's own code: `python -m pytest benchmarks/tests -q`
(not part of tier-1). JAX is held to the CPU before anything imports it."""
import os
import sys

os.environ['JAX_PLATFORMS'] = 'cpu'
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
