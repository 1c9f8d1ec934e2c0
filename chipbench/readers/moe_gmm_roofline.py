"""The expert layer's grouped matmuls' share of their roofline in the
traced seconds: the least time the chip could take for the REAL
assignments of the dispatches that ran there (the larger of operations
over peak FLOP/s and bytes over peak bytes/s; `moe_assignments` and
`moe_experts_touched` of the engine's `engine.dispatch` flight records,
through chipbench/moe_work.py) over the kernel's self time inside those
programs. Says which peak bounds it on an earlier line.

Built like `ring_kernel_roofline.py` (whose pairing it repeats: that file
has no function to call): the records are placed on the trace by
`chipbench/clockfit.py`, the i-th program of the trace is the i-th record
of one run of consecutive dispatches, and only programs of `kind` that lie
wholly inside the traced window count, with their kernel events. A program
whose records carry no `moe_*` fields (a dense model, a commit before the
dropless layer) gives `None`. Needed work counts real tokens only, so a
reading over 100% is a bug in the count."""

import re

from chipbench import clockfit, flops, moe_work, ring, tracered

STEP_SPAN = "chipbench.engine.step"
PROGRAMS = {"decode": re.compile(r"^jit_run_decode\("),
            "prefill": re.compile(r"^jit_run_prefill\(")}


def read(ctx, kind: str, op_pattern: str):
    red, log = ctx["trace"], ctx["log"]
    if red is None or not ctx["peaks"]:
        return None
    steps = ring.records("engine.step", log)
    dispatches = ring.records("engine.dispatch", log)
    if steps is None or dispatches is None:
        return None
    if not any("moe_assignments" in r for r in dispatches):
        log("ring engine.dispatch: no record carries moe_assignments")
        return None
    trace = red.trace
    spans = [s for name, s, _ in trace.host if name == STEP_SPAN]
    fit, why = clockfit.fit(spans, [r["start_ns"] for r in steps])
    if fit is None:
        log(f"clock fit: {why}: moe_gmm {kind} roofline left out")
        return None
    lo, hi = trace.window
    if not ring.complete_since("engine.dispatch", dispatches, "dispatch_ns",
                               lo + fit.offset_ns, log):
        return None
    chip = min(trace.modules)
    programs = sorted(
        ((k, s, d) for name, s, d in trace.modules[chip]
         for k, rx in PROGRAMS.items()
         if rx.search(name) and s < hi and s + d > lo),
        key=lambda e: e[1])
    paired, why = clockfit.pair(
        programs, sorted(dispatches, key=lambda r: r["seq"]),
        fit.offset_ns)
    if paired is None:
        log(f"pairing: {why}: moe_gmm {kind} roofline left out")
        return None
    whole = [(e, r) for e, r in zip(programs, paired)
             if e[0] == kind and e[1] >= lo and e[1] + e[2] <= hi
             and "moe_assignments" in r]
    n_kind = sum(e[0] == kind for e in programs)
    if not whole or n_kind - len(whole) > 2:
        log(f"pairing: {len(whole)} of {n_kind} {kind} programs lie wholly "
            f"in the window with routing counts: left out")
        return None
    inside = [(e[1], e[1] + e[2]) for e, _ in whole]
    rx = re.compile(op_pattern)
    kernel_ns, at = 0, 0
    for name, start, own in sorted(tracered.self_times(trace.ops[chip]),
                                   key=lambda e: e[1]):
        while at < len(inside) and inside[at][1] <= start:
            at += 1
        if at < len(inside) and inside[at][0] <= start and rx.search(name):
            kernel_ns += own
    if kernel_ns <= 0:
        return None
    pub = ctx["cell"].config
    h, f = pub["hidden_size"], pub["intermediate_size"]
    assignments = sum(r["moe_assignments"] for _, r in whole)
    touched = sum(r["moe_experts_touched"] for _, r in whole)
    roof = flops.roofline_seconds(
        {"ops": moe_work.gmm_ops(assignments, h, f),
         "bytes": moe_work.gmm_bytes(assignments, touched, h, f)},
        ctx["peaks"])
    log(f"moe_gmm: {len(whole)} {kind} programs paired with records (of "
        f"{n_kind} in the window), {assignments} real assignments, "
        f"{touched} experts touched (layers and steps summed); kernel "
        f"{kernel_ns / 1e6:.3f} ms, least {roof['seconds'] * 1e3:.3f} ms, "
        f"{roof['bound']}-bound (ops {roof['t_ops'] * 1e3:.3f} ms, bytes "
        f"{roof['t_bytes'] * 1e3:.3f} ms)")
    return 100.0 * roof["seconds"] / (kernel_ns / 1e9)
