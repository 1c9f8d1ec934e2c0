"""Builder's tool: `tools/read_limits_window.py` (sound runs over many
seeds, then the TWO controls on some of them: the plain reference with
every matmul operand rounded to a precision below the configuration's, and
the plain reference with `sliding_window` None) for a cell whose runner is
`engine_laguna`. The same `main`, with that runner's `Runner` and
`control_numbers` in `engine_window`'s place.

    python3 chipbench/tools/read_limits_laguna.py \\
        --workload laguna-xs2-agentturns --seeds 11,12,... \\
        --control-seeds 11,12,... --precisions int8

Lines go to standard output and, one JSON object a reading, to
chiprun_out/limits_<cell>.jsonl.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.runners import engine_laguna  # noqa: E402
from chipbench.tools import read_limits_window as tool  # noqa: E402

if __name__ == "__main__":
    tool.engine_window = engine_laguna
    sys.exit(tool.main())
