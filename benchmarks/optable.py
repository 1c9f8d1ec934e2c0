"""One traced run of a benchmark cell (needs a chip), then the op table of
the median decode, prefill and block program of its span: self time by op
name, count, in order of first start. What PERF.md's per-program op splits
are made with (PR 51). Its result line is not a benchmark result.

    python3 benchmarks/optable.py <tree root> <cell> <seed> <out file>

`tree root` is a checkout of this repo (`.` or a parent unpacked under
`.scratch/`): the run and the reduction are that tree's own `chipbench/`.
"""
import collections
import os
import shutil
import sys
import tempfile

root, cell_name, seed, out_path = sys.argv[1:5]
root = os.path.abspath(root)
os.chdir(root)
sys.path.insert(0, root)

from chipbench import cell as cell_mod  # noqa: E402
from chipbench import run, tracered  # noqa: E402

t_start = run.process_start_time()
keep = tempfile.mkdtemp()
code = run.run_cell(cell_mod.load_cell(cell_name), int(seed), 50.0, 1,
                    t_start, keep_trace=keep)
trace = tracered.Trace.load(os.path.join(keep, f"{cell_name}.trace.json.gz"))
shutil.rmtree(keep, ignore_errors=True)
os.makedirs(os.path.dirname(out_path), exist_ok=True)
with open(out_path, "w") as f:
    for kind in ("jit_run_decode(", "jit_run_prefill(", "jit_run_block("):
        mods = sorted((e for e in tracered.clip(trace.modules.get(0, []),
                                                trace.window)
                       if e[0].startswith(kind)), key=lambda e: e[2])
        if not mods:
            continue
        name, start, dur = mods[len(mods) // 2]
        inside = [e for e in trace.ops.get(0, [])
                  if e[1] >= start and e[1] + e[2] <= start + dur]
        own = tracered.self_times(inside)
        by = collections.OrderedDict()
        for op, s, ns in sorted(own, key=lambda e: e[1]):
            c = by.setdefault(op, [0, 0, s - start])
            c[0] += 1
            c[1] += ns
        f.write(f"## {name}: median of {len(mods)} programs, {dur / 1e6:.3f} "
                f"ms; ops inside {len(inside)}, self time "
                f"{sum(c[1] for c in by.values()) / 1e6:.3f} ms\n")
        for op, (n, ns, first) in by.items():
            f.write(f"{ns / 1e6:9.4f} ms  x{n:<5d} first at "
                    f"{first / 1e6:8.3f} ms  {op}\n")
sys.exit(code)
