"""benchmarks/tests: run by hand and by benchmarks/rehearse.sh, outside
the repo's tier-1 (``python -m pytest benchmarks/tests -q``). Pins JAX
to the CPU before anything imports it."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
