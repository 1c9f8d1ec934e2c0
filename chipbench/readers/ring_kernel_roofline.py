"""A serving kernel's share of its roofline in the traced seconds: the
least time the chip could take for the real rows of the dispatches that
ran there (the larger of operations over peak FLOP/s and bytes over peak
bytes/s; from each row's `q_tokens` and `ctx_tokens` in the engine's
`engine.dispatch` flight records) over the kernel's self time inside those
programs. Says which peak bounds it on an earlier line.

The records are placed on the trace by `chipbench/clockfit.py`: one offset
for the session from the `chipbench.engine.step` spans, then the i-th
program of the trace is the i-th record of one run of consecutive
dispatches. Only programs that lie wholly inside the traced window count,
with their kernel events; needed work counts real tokens only (`work`:
`paged_decode` by chipbench/kernel_work.py, `flash_fwd` by
chipbench/flops.py over the tokens each row prefills), so a reading over
100% is a bug in the count."""

import re

from chipbench import clockfit, flops, kernel_work, ring, tracered

STEP_SPAN = "chipbench.engine.step"
PROGRAMS = {"decode": re.compile(r"^jit_run_decode\("),
            "prefill": re.compile(r"^jit_run_prefill\(")}


def read(ctx, kind: str, op_pattern: str, work: str):
    red, log = ctx["trace"], ctx["log"]
    if red is None or not ctx["peaks"]:
        return None
    steps = ring.records("engine.step", log)
    dispatches = ring.records("engine.dispatch", log)
    if steps is None or dispatches is None:
        return None
    trace = red.trace
    spans = [s for name, s, _ in trace.host if name == STEP_SPAN]
    fit, why = clockfit.fit(spans, [r["start_ns"] for r in steps])
    if fit is None:
        log(f"clock fit: {why}: {kind} roofline left out")
        return None
    log(f"clock fit: record time - trace time = {fit.offset_ns} ns from "
        f"{fit.spans} step spans, largest residual {fit.residual_ns} ns")
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
        log(f"pairing: {why}: {kind} roofline left out")
        return None
    log(f"pairing: {len(programs)} programs of the traced window, "
        + ", ".join(f"{sum(e[0] == k for e in programs)} {k}"
                    for k in PROGRAMS) + f"; {why}")
    whole = [(e, r) for e, r in zip(programs, paired)
             if e[0] == kind and e[1] >= lo and e[1] + e[2] <= hi]
    n_kind = sum(e[0] == kind for e in programs)
    if not whole or n_kind - len(whole) > 2:
        log(f"pairing: {len(whole)} of {n_kind} {kind} programs lie wholly "
            f"in the window: left out")
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
    hq, hkv = pub["num_attention_heads"], pub["num_key_value_heads"]
    d = pub.get("head_dim") or pub["hidden_size"] // hq
    need = {"ops": 0.0, "bytes": 0.0}
    rows = tokens = 0
    for _, rec in whole:
        for _, q, ctx_tokens in rec["rows"]:
            if work == "paged_decode":
                w = kernel_work.paged_decode_chunk(ctx_tokens, rec["k"], hq,
                                                   hkv, d)
            else:
                # the flash kernel covers the tokens the row prefills; a
                # cached prefix is merged in outside it
                w = flops.flash_fwd(b=1, sq=q, sk=q, hq=hq, hkv=hkv, d=d)
            need["ops"] += w["ops"]
            need["bytes"] += w["bytes"]
            rows += 1
            tokens += ctx_tokens
    layers = pub["num_hidden_layers"]
    roof = flops.roofline_seconds(
        {k: layers * v for k, v in need.items()}, ctx["peaks"])
    log(f"{work}: {len(whole)} {kind} programs paired with records (of "
        f"{n_kind} in the window), {rows} real rows, {tokens} context "
        f"tokens; kernel {kernel_ns / 1e6:.3f} ms, least "
        f"{roof['seconds'] * 1e3:.3f} ms, {roof['bound']}-bound (ops "
        f"{roof['t_ops'] * 1e3:.3f} ms, bytes {roof['t_bytes'] * 1e3:.3f} "
        f"ms)")
    return 100.0 * roof["seconds"] / (kernel_ns / 1e9)
