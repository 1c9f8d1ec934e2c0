"""Builder's tool, not the benchmark's command: one run of a cell with its
traffic changed in memory. The sweep that fixes an open-loop mix's rate
(`--rate`), the spread a tail would have if every seed drew its own order
of the cycle (`--order seeded`), and a kept trace to read by hand
(`--keep-trace DIR`). Its result line is not a benchmark result.

    python3 chipbench/tools/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        [--trace 0|1] [--rate R] [--order fixed|seeded] [--keep-trace DIR]
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import cell as cell_mod  # noqa: E402
from chipbench import generator, run  # noqa: E402


def main(argv=None) -> int:
    t_start = run.process_start_time()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--order", choices=("fixed", "seeded"), default="fixed")
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    cell = cell_mod.load_cell(args.workload)
    run.log(f"TOOL RUN, not a benchmark run: rate {args.rate}, order "
            f"{args.order}")
    if args.rate is not None:
        cell.traffic["arrivals"]["rate_per_s"] = args.rate
    if args.order == "seeded":
        fixed = generator._base_cycle

        def seeded(mix, n, span_s):
            """The same lengths and gaps, in an order drawn from --seed."""
            prompt, output, gaps = fixed(mix, n, span_s)
            rng = np.random.default_rng([args.seed & 0xFFFFFFFF, 0x0DE4])
            order = rng.permutation(n)
            return prompt[order], output[order], rng.permutation(gaps)

        generator._base_cycle = seeded
    return run.run_cell(cell, args.seed, args.seconds, args.trace, t_start,
                        keep_trace=args.keep_trace)


if __name__ == "__main__":
    try:
        code = main()
    except cell_mod.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        code = 1
    sys.stdout.flush()
    sys.exit(code)
