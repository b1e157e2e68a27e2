"""The benchmark's own tests run on the CPU backend at rehearsal size:
``python3 -m pytest benchmark/tests -q`` from the root of the checkout
(not part of the repo's tier-1 command, which collects ``tests/`` only)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH, os.path.join(BENCH, "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)
