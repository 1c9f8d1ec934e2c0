"""One traced run of a benchmark cell (needs a chip), then the op table of
the median decode, prefill and block program of its span (of the pretrain
cell's: the median train step): self time by op
name, count, in order of first start, each op with the scope the program
gives its instruction (`LLMEngine.program_scopes`, through
`chipbench/scoped.py`), and the program's roll-up by scope above it. What
PERF.md's per-program op splits are made with (PR 51; by scope since PR
52). Its result line is not a benchmark result.

    python3 benchmarks/optable.py <tree root> <cell> <seed> <out file>

`tree root` is a checkout of this repo (`.` or a parent unpacked under
`.scratch/`): the run and the reduction are that tree's own `chipbench/`
(`OPTABLE_SECONDS=4 JAX_PLATFORMS=cpu ... . tiny-chat 1 x.txt` rehearses).
A tree without `chipbench/scoped.py`, or whose program cannot say, gets
the table it always got: the median program by its module event, no scope.
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

try:
    from chipbench import scoped
except ImportError:
    scoped = None

# the kinds of program a span can hold (`chipbench/scoped.py`); the
# trainer's step has no `jit_run_train(` module event: by scope only
KINDS = ("decode", "prefill", "block", "train")

# the runner, for its engine's tables (as benchmarks/flightrecords.py
# reaches it)
held = {}
lines = run._summary_lines
run._summary_lines = lambda runner: (held.update(runner=runner),
                                     lines(runner))[1]
t_start = run.process_start_time()
keep = tempfile.mkdtemp()
code = run.run_cell(cell_mod.load_cell(cell_name), int(seed),
                    float(os.environ.get("OPTABLE_SECONDS", "50")), 1,
                    t_start, keep_trace=keep)
trace = tracered.Trace.load(os.path.join(keep, f"{cell_name}.trace.json.gz"))
shutil.rmtree(keep, ignore_errors=True)
ctx = {"trace": tracered.reduce_trace(trace), "log": run.log,
       "runner": held.get("runner")}


def median_by_module(kind):
    """(name, start, dur, table or None, programs): the median program of
    `kind` by its module events, clipped to the window."""
    mods = sorted((e for e in tracered.clip(trace.modules.get(0, []),
                                            trace.window)
                   if e[0].startswith(f"jit_run_{kind}(")),
                  key=lambda e: e[2])
    if not mods:
        return None
    return (*mods[len(mods) // 2], None, len(mods))


def median_by_scope(kind):
    """The same among the WHOLE programs of the span, with the table of the
    bucket its dispatch record names; None where the program cannot say."""
    if scoped is None:
        return None
    spans = scoped.spans_of(ctx, kind, f"the op table's {kind} scopes")
    if not spans:
        return None
    start, end, table = sorted(spans, key=lambda s: s[1] - s[0])[
        len(spans) // 2]
    return f"jit_run_{kind}(", start, end - start, table, len(spans)


os.makedirs(os.path.dirname(out_path), exist_ok=True)
with open(out_path, "w") as f:
    for kind in KINDS:
        got = median_by_scope(kind) or median_by_module(kind)
        if got is None:
            continue
        name, start, dur, table, n = got
        inside = [e for e in trace.ops.get(0, [])
                  if e[1] >= start and e[1] + e[2] <= start + dur]
        own = tracered.self_times(inside)
        by = collections.OrderedDict()
        by_scope = {}
        for op, s, ns in sorted(own, key=lambda e: e[1]):
            c = by.setdefault(op, [0, 0, s - start])
            c[0] += 1
            c[1] += ns
            if table is not None:
                inst, opcode = scoped.instruction(op)
                key = (table.get(inst, "(unknown instruction)"), opcode)
                by_scope[key] = by_scope.get(key, 0) + ns
        total = sum(c[1] for c in by.values())
        f.write(f"## {name}: median of {n} programs, {dur / 1e6:.3f} "
                f"ms; ops inside {len(inside)}, self time "
                f"{total / 1e6:.3f} ms\n")
        if table is not None:
            f.write("# by scope (ms, share of the program's device time):\n")
            for path, ns in scoped.rollup(by_scope):
                f.write(f"# {ns / 1e6:9.4f} ms {100 * ns / dur:6.2f}%  "
                        f"{path}\n")
        for op, (count, ns, first) in by.items():
            where = ""
            if table is not None:
                where = "  <- " + (scoped.cut(table.get(
                    scoped.instruction(op)[0], "(unknown instruction)"))
                    or "(no scope)")
            f.write(f"{ns / 1e6:9.4f} ms  x{count:<5d} first at "
                    f"{first / 1e6:8.3f} ms  {op}{where}\n")
    if scoped is not None:
        # every whole program of a kind, as the per-layer readers sum them
        for kind in KINDS:
            t = scoped.table(ctx, [kind])
            if t is not None:
                f.write("\n".join(scoped.rollup_lines(t, kind)) + "\n")
sys.exit(code)
