"""Builder's tool, not the benchmark's command: read, in one process and at
the cell's own size, what the output check's numbers are in sound runs over
many seeds and what the control (the plain reference in the precision below
the configuration's, chipbench/control.py) gives on a few of them. A limit
is set from these two readings and never from a guess (PERF.md section 2).

    python3 chipbench/tools/read_limits.py --workload <cell> \\
        --seeds 11,12,... --control-seeds 11,12,13 --precisions int8,fp8

Every seed makes its own weights, system under test and check, as a run of
the benchmark does; only the window is left out. Lines go to standard
output and, one JSON object a reading, to chiprun_out/limits_<cell>.jsonl.
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
from chipbench import control, run  # noqa: E402


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

    def emit(kind: str, seed: int, result: dict, secs: float) -> None:
        row = {"cell": cell.name, "kind": kind, "seed": seed,
               "numbers": {r["name"]: r["value"] for r in result["numbers"]},
               "notes": result.get("notes"), "seconds": round(secs, 1)}
        print("READ " + json.dumps(row), flush=True)
        out.write(json.dumps(row) + "\n")
        out.flush()

    limits = cell.config["limits"]
    Runner = cell_mod.load_module("runners", cell.runner).Runner
    for seed in args.seeds:
        t0 = time.monotonic()
        runner = Runner(cell, seed, 1.0, run.log)
        engine = cell.runner == "engine"
        emit("sound", seed, runner.setup(warm=False) if engine
             else runner.setup(), time.monotonic() - t0)
        if seed in args.control_seeds:
            ref, cfg = runner.reference, dict(runner.published)
            if engine:
                weights = ref.weights_from_program_tree(runner.engine.params)
                sample = runner.check_sample
                runner.engine = None
            else:
                weights = ref.weights_from_program_tree(runner.state.params)
                ids = jax.numpy.asarray(runner.batches[0])
                runner.state = runner.trainer = None
            gc.collect()
            for precision in args.precisions.split(","):
                t0 = time.monotonic()
                res = (control.serve_numbers(ref, weights, cfg, precision,
                                             sample, limits) if engine else
                       control.train_numbers(ref, weights, cfg, precision,
                                             ids, limits))
                emit(f"control-{precision}", seed, res,
                     time.monotonic() - t0)
            del weights
        del runner
        gc.collect()
        stats = jax.devices()[0].memory_stats() or {}
        run.log(f"seed {seed} done; device bytes in use "
                f"{stats.get('bytes_in_use')} peak "
                f"{stats.get('peak_bytes_in_use')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
