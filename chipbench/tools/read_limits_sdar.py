"""Builder's tool: `tools/read_limits_moe.py` for a cell whose runner is
`engine_diffusion`. In one process and at the cell's own size: the output
check's numbers in sound runs over many seeds, and what the control gives
on some of them (the plain reference in a precision below the
configuration's, put in the program's place along the SAME block states
and judged as the program is: runners/engine_diffusion.py
`control_states`).

    python3 chipbench/tools/read_limits_sdar.py --workload sdar-30b-a3b-chat \\
        --seeds 11,12,... --control-seeds 11,12,... --precisions int8,fp8

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
from chipbench.runners import engine_diffusion as ed  # noqa: E402
from chipbench.runners import engine_moe  # noqa: E402


def _ints(text: str):
    return [int(x) for x in text.split(",") if x]


def control_numbers(runner, precision: str, limits) -> dict:
    """What the check would read if the program computed as the reference
    does at `precision`."""
    ref, cfg = runner.reference, dict(runner.published)
    weights = ref.weights_from_program_tree(runner.engine.params)
    sample = runner.check_sample
    logits, tokens = ed.control_states(
        ed.block_rows(ref, weights, cfg, precision),
        sample["logit_states"], sample["token_states"], cfg["block_length"])
    out, notes = ed.judge(ed.block_rows(ref, weights, cfg), ref, logits,
                          tokens, cfg)
    res = out.result(limits)
    res["notes"].update(notes)
    row, more = engine_moe.expert_choice(
        engine_moe.reference_forward(ref, weights, cfg, precision),
        engine_moe.reference_forward(ref, weights, cfg, "float32"),
        sample["logit_seqs"], limits)
    res["numbers"].append(row)
    res["notes"].update(more)
    return res


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
        runner = ed.Runner(cell, seed, 1.0, run.log)
        emit("sound", seed, runner.setup(warm=False), t0)
        if seed in args.control_seeds:
            for precision in args.precisions.split(","):
                t0 = time.monotonic()
                emit(f"control-{precision}", seed,
                     control_numbers(runner, precision, limits), t0)
        runner.engine.close()
        del runner
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
