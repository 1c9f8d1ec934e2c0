"""The executed programs of a traced window, each with the `engine.dispatch`
record of the dispatch that ran it: what `readers/ring_kernel_roofline.py`
and `readers/moe_gmm_roofline.py` each do in line, here once for the
readers that came after them. The records are placed on the trace by
`chipbench/clockfit.py`; only programs of `kind` that lie wholly inside the
traced window are returned. Nothing raises: a trace or a recorder that is
missing, a fit or a pairing that fails, gives None and a line."""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

from chipbench import clockfit, ring, tracered

STEP_SPAN = "chipbench.engine.step"
PROGRAMS = {"decode": re.compile(r"^jit_run_decode\("),
            "prefill": re.compile(r"^jit_run_prefill\(")}

Paired = Tuple[Tuple[str, int, int], Dict[str, Any]]


def whole_programs(ctx, kind: str, what: str) -> Optional[List[Paired]]:
    """[((kind, start_ns, duration_ns), record)] of the `kind` programs
    wholly in the traced window, in the order the device ran them."""
    red, log = ctx["trace"], ctx["log"]
    if red is None:
        return None
    steps = ring.records("engine.step", log)
    dispatches = ring.records("engine.dispatch", log)
    if steps is None or dispatches is None:
        return None
    trace = red.trace
    spans = [s for name, s, _ in trace.host if name == STEP_SPAN]
    fit, why = clockfit.fit(spans, [r["start_ns"] for r in steps])
    if fit is None:
        log(f"clock fit: {why}: {what} left out")
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
        programs, sorted(dispatches, key=lambda r: r["seq"]), fit.offset_ns)
    if paired is None:
        log(f"pairing: {why}: {what} left out")
        return None
    whole = [(e, r) for e, r in zip(programs, paired)
             if e[0] == kind and e[1] >= lo and e[1] + e[2] <= hi]
    n_kind = sum(e[0] == kind for e in programs)
    if not whole or n_kind - len(whole) > 2:
        log(f"pairing: {len(whole)} of {n_kind} {kind} programs lie wholly "
            f"in the window: {what} left out")
        return None
    return whole


def op_self_ns(ctx, whole: List[Paired], op_pattern: str) -> int:
    """Self time of the device ops named by `op_pattern` that started
    inside one of the `whole` programs."""
    trace = ctx["trace"].trace
    inside = [(e[1], e[1] + e[2]) for e, _ in whole]
    rx = re.compile(op_pattern)
    total, at = 0, 0
    for name, start, own in sorted(
            tracered.self_times(trace.ops[min(trace.modules)]),
            key=lambda e: e[1]):
        while at < len(inside) and inside[at][1] <= start:
            at += 1
        if at < len(inside) and inside[at][0] <= start and rx.search(name):
            total += own
    return total
