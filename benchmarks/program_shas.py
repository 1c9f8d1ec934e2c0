"""One untraced run of a benchmark cell (needs a chip) that also writes the
sha256 of the lowered text of every warm-up program of its engine
(`LLMEngine.program_text`) or of its trainer's step
(`ShardedTrainer.program_text`), then what two such runs are compared by:
how a PR that claims to change no program shows it where the Pallas paths
are chosen, which tests/test_program_pins.py on the CPU does not see (PR
61, PERF.md section 6). Its result line is the cell's own (`correct`,
`programs_built_in_window`).

    python3 benchmarks/program_shas.py run <tree root> <cell> <seed> <out.json>
    python3 benchmarks/program_shas.py texts <tree root> <cell> <seed> <out.json>
    python3 benchmarks/program_shas.py compare <parent.json> <change.json> ...

`tree root` is a checkout of this repo (`.` or a parent unpacked under
`.scratch/`). Run both sides from the SAME path, one after the other. A
kernel's serialized body holds the Python call stack it was traced under,
file names AND LINE NUMBERS (so an edit that moves a line of models/*.py
changes the text of every kernel-holding program and nothing it computes):
what is hashed is the text with every kernel's body printed without its
locations (`without_kernel_locations`). `texts` is the side
that needs no result: a serving cell's weights and engine, no warm-up, no
check and no window (a tenth of a cold run). `compare` needs no
chip: a line a pair with the programs compared and those equal, the names of
the others, exit 1 if any differ; the texts are in `<out>.texts.json.gz`.
`SHAS_SECONDS=3 JAX_PLATFORMS=cpu ... run . tiny-chat 1 x.json` rehearses.
"""
import base64
import gzip
import hashlib
import json
import os
import re
import sys


class _Built(Exception):
    pass


def run(root: str, cell_name: str, seed: str, out_path: str,
        texts_only: bool = False) -> int:
    root = os.path.abspath(root)
    out_path = os.path.abspath(out_path)
    os.chdir(root)
    sys.path.insert(0, root)
    from chipbench import cell as cell_mod
    from chipbench import run as bench

    cell = cell_mod.load_cell(cell_name)
    seconds = float(os.environ.get("SHAS_SECONDS", "50"))
    held = {}
    if texts_only and "engine" in cell.runner:
        runner = held["runner"] = cell_mod.load_module(
            "runners", cell.runner).Runner(cell, int(seed), seconds, bench.log)

        def built():
            raise _Built

        runner._check_outputs = built
        code = None
        try:
            runner.setup(False)
        except _Built:
            pass
    else:
        lines = bench._summary_lines
        bench._summary_lines = lambda runner: (held.update(runner=runner),
                                               lines(runner))[1]
        code = bench.run_cell(cell, int(seed), seconds, 0,
                              bench.process_start_time())
    texts = program_texts(held["runner"])
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({"cell": cell_name, "exit": code, "shas": {
            k: hashlib.sha256(without_kernel_locations(t).encode())
            .hexdigest() for k, t in texts.items()}}, f, indent=1)
    with gzip.open(out_path + ".texts.json.gz", "wt") as f:
        json.dump(texts, f)
    return code


def without_kernel_locations(text: str) -> str:
    """`text` with the body of every `tpu_custom_call` (Mosaic bytecode in
    base64) replaced by its assembly without debug info."""
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body(match):
        ctx = ir.Context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True   # `stable_mosaic`'s version
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return module.operation.get_asm(enable_debug_info=False)

    return re.sub(r'(?<=\\22body\\22: \\22)([A-Za-z0-9+/=]+)(?=\\22)',
                  body, text)


def program_texts(runner) -> dict:
    """name -> lowered text of every program the cell's set-up built."""
    if hasattr(runner, "trainer"):
        # the shapes and shardings of the steps it took (the state itself
        # was given away step by step)
        return {"train_step": runner.trainer.program_text(
            *runner.trainer._step_avals)}
    engine = runner.engine
    return {f"{kind}:{key}": engine.program_text(kind, key)
            for kind, key in engine._warmup_programs(None, True)}


def compare(paths) -> int:
    differ = 0
    for a, b in zip(paths[::2], paths[1::2]):
        with open(a) as f:
            one = json.load(f)
        with open(b) as f:
            two = json.load(f)
        names = sorted(set(one["shas"]) | set(two["shas"]))
        other = [n for n in names
                 if one["shas"].get(n) != two["shas"].get(n)]
        differ += len(other)
        print(f"{one['cell']}: exit {one['exit']} / {two['exit']}, "
              f"{len(names)} programs compared, {len(names) - len(other)} "
              f"equal" + (f", NOT {other}" if other else ""))
    return int(differ > 0)


if __name__ == "__main__":
    if len(sys.argv) == 6 and sys.argv[1] in ("run", "texts"):
        sys.exit(run(*sys.argv[2:], texts_only=sys.argv[1] == "texts") or 0)
    if len(sys.argv) >= 4 and sys.argv[1] == "compare" \
            and len(sys.argv) % 2 == 0:
        sys.exit(compare(sys.argv[2:]))
    sys.exit(__doc__)
