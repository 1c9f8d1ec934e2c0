"""chipbench's own tests: the yardstick's arithmetic, on the CPU.

    python -m pytest chipbench/tests -q

Not part of the repo's tier-1 suite (which runs `tests/`)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
