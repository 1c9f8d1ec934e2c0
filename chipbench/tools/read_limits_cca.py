"""Builder's tool: `tools/read_limits_gdn.py` for a cell whose runner is
`engine_cca`. In one process and at the cell's own size: the output check's
numbers in sound runs over many seeds, what the control gives on some of
them (the plain reference in a precision below the configuration's,
streamed as the check is: `runners/engine_cca.py: control_numbers`; the
router, the norms, the rotation and the depthwise taps stay float32), and
what the check's logits read with the slot's tail ZEROED at every pass
boundary (`zero_tail_numbers`): both have to fail a limit.

    python3 chipbench/tools/read_limits_cca.py \\
        --workload zaya1-8b-reasoning --seeds 11,12,... \\
        --control-seeds 11,12,... --precisions int8,fp8

Lines go to standard output and, one JSON object a reading, to
chiprun_out/limits_<cell>.jsonl.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import cell as cell_mod  # noqa: E402
from chipbench import run  # noqa: E402
from chipbench.runners import engine_cca  # noqa: E402


def _ints(text: str):
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--precisions", default="int8")
    args = ap.parse_args(argv)
    cell = cell_mod.load_cell(args.workload)

    import jax

    from ray_tpu.util.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    enable_compile_cache()
    run.log(f"device {run.device_facts()}")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"limits_{cell.name}.jsonl"), "a")

    def emit(kind: str, seed: int, result: dict, t0: float) -> None:
        row = {"cell": cell.name, "kind": kind, "seed": seed,
               "numbers": {r["name"]: r["value"] for r in result["numbers"]},
               "notes": result.get("notes"),
               "seconds": round(time.monotonic() - t0, 1)}
        print("READ " + json.dumps(row), flush=True)
        out.write(json.dumps(row) + "\n")
        out.flush()

    limits = cell.config["limits"]
    for seed in args.seeds:
        t0 = time.monotonic()
        runner = engine_cca.Runner(cell, seed, 1.0, run.log)
        emit("sound", seed, runner.setup(warm=False), t0)
        if seed in args.control_seeds:
            t0 = time.monotonic()
            # the engine is idle: its pool makes room for the float32
            # reference (no name is kept for the stage: it holds the params)
            runner.engine.compute.kv_pages = None
            gc.collect()
            emit("zero-tail", seed,
                 engine_cca.zero_tail_numbers(runner, limits), t0)
            ref, cfg = runner.reference, dict(runner.published)
            weights = ref.weights_from_program_tree(runner.engine.params)
            sample = runner.check_sample
            runner.engine = None
            gc.collect()
            for precision in filter(None, args.precisions.split(",")):
                t0 = time.monotonic()
                emit(f"control-{precision}", seed,
                     engine_cca.control_numbers(ref, weights, cfg,
                                                precision, sample, limits),
                     t0)
            del weights
        del runner
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
