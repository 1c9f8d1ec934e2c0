"""The device's timeline from the engine's own stamps, as the per-layer
readers see it.

Since PR 37 every `engine.dispatch` flight record carries `enqueued_ns`,
`device_start_ns`, `device_end_ns` and `end_exact`, stamped by the host with
no profiler (`ray_tpu/serve/llm/engine.py:_device_stamps`): a program that had
not finished when the host came to fetch it ended as the fetch returned, and
started when it was enqueued or when the program before it ended. The
`engine.request` and `engine.step` records carry what follows from them.

Two things live here. (a) The part of the timed window the readers of those
fields read: `[t0, t0 + trace.start_share x seconds)`, BEFORE the profiler's
session begins. `run.py:Tracer.poll` starts the session from inside the
runner's loop, the call stalls the loop for seconds, and what follows is
the backlog of that stall; the stamps need no session, so they are read where
the cell still runs at its own rate. (b) `check`: in the traced seconds, where
the device trace has the programs' own events, the stamps against the module
events. In a traced run the readers read the stamps only if the check ran and
passed; where the stamps are missing (a parent commit, a pp engine) or mostly
upper bounds, `usable` gives None with a line too. Nothing raises.

What the check can judge, and what it cannot. An exact end is stamped when
the fetch returns, so it lies after the module event's end by the fetch's own
lag (the completion's way to the host and the copy of the tokens) AND by
whatever the trace misplaces its device plane against its host plane: in
nineteen sessions of PR 37 the sum read 1.28-1.53 ms in fourteen and 2.30-2.46
in five (twelve sessions of one cell since PR 29: 1.28-2.86), the same for
every program of a session. A DURATION between two stamped ends carries neither,
so what `prefill_device_us_per_token.ttft` and the program part of a wait
inherit is the scatter of the errors about their median, and the issue's
limits (0.5 ms at the median, 2 ms at p95) judge that. The sum itself is
held between 0 (a blocked fetch cannot return before its program ended: below
it the clock fit or the stamp is wrong) and `MAX_END_LAG_MS`. Where a program
of the traced seconds found the device idle (the trace says so: nothing ran
for `IDLE_GAP_NS` before it), its module event's start against the host's
enqueue takes the plane's misplacement alone, and the line says what is then
left of the sum: the fetch's lag, which a wait that ends at a stamped end
(`ttft_device_wait_ms_p50.ttft`) holds once.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Tuple

from chipbench import clockfit, paired, ring, stats

MIN_EXACT_SHARE = 0.8
MAX_END_ERR_MEDIAN_MS = 0.5
MAX_END_ERR_P95_MS = 2.0
# 1.4 x the largest sum of lag and misplacement read so far (above)
MAX_END_LAG_MS = 4.0
# `clockfit.pair` asks that a record was dispatched before its program's
# event starts and fetched after it ends, within a tolerance; its own 1 ms
# is under the plane's misplacement, and a program that found the device
# idle then refuses the whole pairing (PERF.md section 7)
PAIR_TOLERANCE_NS = int(MAX_END_LAG_MS * 1e6)
IDLE_GAP_NS = 1_000_000

Paired = Tuple[Tuple[str, int, int], Dict[str, Any], bool]


def before_profiler_ns(ctx) -> Tuple[int, int]:
    """[lo, hi) on the recorder's clock: the timed window up to the second
    at which `run.py:Tracer` starts the profiler (computed as it does)."""
    lo, _ = ring.window_ns(ctx)
    spec = ctx["cell"].traffic.get("trace", {})
    seconds = float(ctx["seconds"])
    span = float(spec.get("seconds", 4.0))
    start_s = min(float(spec.get("start_share", 0.5)) * seconds,
                  max(0.0, seconds - span))
    return lo, lo + int(start_s * 1e9)


def _programs(ctx) -> Optional[Tuple[List[Paired], int]]:
    """([((kind, start_ns, duration_ns), record, the device was idle for
    IDLE_GAP_NS before it)] of the decode and prefill programs wholly in
    the traced seconds, in the order the device ran them; record time less
    trace time). As `paired.whole_programs`, with both kinds in one
    pairing and this file's tolerance."""
    log, red = ctx["log"], ctx.get("trace")
    steps = ring.records("engine.step", log)
    dispatches = ring.records("engine.dispatch", log)
    if red is None or not red.trace.modules or steps is None \
            or dispatches is None:
        return None
    trace = red.trace
    spans = [s for name, s, _ in trace.host if name == paired.STEP_SPAN]
    fit, why = clockfit.fit(spans, [r["start_ns"] for r in steps])
    if fit is None:
        log(f"stamped.check: clock fit: {why}")
        return None
    lo, hi = trace.window
    if not ring.complete_since("engine.dispatch", dispatches, "dispatch_ns",
                               lo + fit.offset_ns, log):
        return None
    events = sorted(
        ((k, s, d) for name, s, d in trace.modules[min(trace.modules)]
         for k, rx in paired.PROGRAMS.items()
         if rx.search(name) and s < hi and s + d > lo),
        key=lambda e: e[1])
    records, why = clockfit.pair(
        events, sorted(dispatches, key=lambda r: r["seq"]), fit.offset_ns,
        PAIR_TOLERANCE_NS)
    if records is None:
        log(f"stamped.check: pairing: {why}")
        return None
    ends = [None] + [s + d for _, s, d in events]
    return [(e, r, end is not None and e[1] - end >= IDLE_GAP_NS)
            for e, r, end in zip(events, records, ends)
            if e[1] >= lo and e[1] + e[2] <= hi], fit.offset_ns


def _about(errs_ns: List[int]) -> Dict[str, float]:
    """The errors' signed median, and their scatter about it."""
    mid = statistics.median(errs_ns)
    mags = [abs(e - mid) / 1e6 for e in errs_ns]
    return {"n": len(mags), "signed_median": mid / 1e6,
            "median": stats.percentile(mags, 50),
            "p95": stats.percentile(mags, 95), "max": max(mags)}


def _line(what: str, errs_ns: List[int]) -> str:
    if not errs_ns:
        return f"{what}: none"
    s = _about(errs_ns)
    return (f"{what}: n={s['n']} stamp - event ms median "
            f"{s['signed_median']:+.3f}; about it |error| median "
            f"{s['median']:.3f} p95 {s['p95']:.3f} max {s['max']:.3f}")


def check(ctx) -> Optional[Dict[str, Any]]:
    """The stamps against the device trace, over the decode and prefill
    programs wholly in the traced seconds: `device_end_ns` on the trace's
    clock less the module event's end, `device_start_ns` less its start.
    Logged apart: exact ends (the verdict is theirs, as the module's text
    says), ends that are upper bounds, starts of programs that followed an
    exactly-ended one without a gap, and the programs that found the device
    idle BY THE TRACE: their enqueue against the event's start (the plane's
    misplacement, and with it what is left of the exact ends' median), how
    many of them the stamps call idle-found too, and the idle time both
    give between the same programs. None, with a line, where nothing can be
    paired or no paired record has stamps."""
    log = ctx["log"]
    got = _programs(ctx)
    if not got or not got[0]:
        log("stamped.check: no program of the traced seconds is paired "
            "with its record: the stamps are not checked against the trace")
        return None
    programs, offset = got
    by_seq = {r["seq"]: r for _, r, _ in programs}
    exact_end, bound_end, start_after, idle_start = [], [], [], []
    stamped = agreed = idle_traced = idle_stamped = 0
    before = None
    for (kind, start, dur), rec, idle in programs:
        if rec.get("device_end_ns") is None:
            before = None
            continue
        stamped += 1
        end_err = rec["device_end_ns"] - offset - (start + dur)
        (exact_end if rec["end_exact"] else bound_end).append(end_err)
        found_idle = rec["device_start_ns"] == rec["enqueued_ns"]
        if idle:
            idle_start.append(rec["enqueued_ns"] - offset - start)
            agreed += found_idle
        elif not found_idle and (by_seq.get(rec["seq"] - 1)
                                 or {}).get("end_exact"):
            start_after.append(rec["device_start_ns"] - offset - start)
        if before is not None and before[1]["end_exact"]:
            idle_traced += max(0, start - sum(before[0][1:]))
            idle_stamped += rec["device_start_ns"] - before[1]["device_end_ns"]
        before = ((kind, start, dur), rec)
    if not stamped:
        log(f"stamped.check: none of {len(programs)} paired programs' "
            f"records has device stamps")
        return None
    log(f"stamped.check: {stamped} programs of the traced seconds "
        f"({sum(e[0] == 'prefill' for e, _, _ in programs)} prefill) "
        f"against their module events; end_exact {len(exact_end)} of "
        f"{stamped} = {100 * len(exact_end) / stamped:.1f}%")
    for what, errs in (
            ("exact ends", exact_end),
            ("ends that are upper bounds", bound_end),
            ("starts behind an exactly-ended program", start_after),
            ("enqueues of the programs the trace shows starting on an idle "
             "device", idle_start)):
        log("stamped.check " + _line(what, errs))
    log(f"stamped.check: the stamps call {agreed} of those "
        f"{len(idle_start)} idle-found; between the same programs the "
        f"device idled {idle_traced / 1e6:.3f} ms by the trace, had "
        f"nothing enqueued {idle_stamped / 1e6:.3f} ms by the stamps")
    out = {"programs": stamped, "exact_share": len(exact_end) / stamped,
           "ok": False}
    if exact_end:
        s = _about(exact_end)
        out.update(end_lag_ms=s["signed_median"], end_median_ms=s["median"],
                   end_p95_ms=s["p95"])
        out["ok"] = (0 <= s["signed_median"] <= MAX_END_LAG_MS
                     and s["median"] <= MAX_END_ERR_MEDIAN_MS
                     and s["p95"] <= MAX_END_ERR_P95_MS)
        if idle_start:
            plane = statistics.median(idle_start) / 1e6
            out.update(plane_ms=plane)
            log(f"stamped.check: the trace places its device plane "
                f"{plane:+.3f} ms early against the host's enqueue of an "
                f"idle-found program (a launch later, at most); the exact "
                f"ends' {s['signed_median']:+.3f} less that is the fetch's "
                f"lag behind a program's end: "
                f"{s['signed_median'] - plane:.3f} ms")
    return out


def _judge(ctx) -> Optional[Tuple[int, int]]:
    log = ctx["log"]
    recs = ring.records("engine.dispatch", log)
    if recs is None:
        return None
    lo, hi = before_profiler_ns(ctx)
    if not ring.complete_since("engine.dispatch", recs, "dispatch_ns", lo,
                               log):
        return None
    part = [r for r in recs if lo <= r["dispatch_ns"] < hi]
    stamped = [r for r in part if r.get("device_end_ns") is not None]
    if not stamped:
        log(f"stamped: none of the {len(part)} dispatch records of the "
            f"{(hi - lo) / 1e9:.1f} s before the profiler has device "
            f"stamps (a program from before them, or a pp engine): nothing "
            f"is read")
        return None
    exact = sum(bool(r["end_exact"]) for r in stamped)
    prefills = [r for r in stamped if r["kind"] == "prefill"]
    # the host behind the device: a pass that had finished before its
    # fetch began took at most its stamped time
    late = [round((r["device_end_ns"] - r["device_start_ns"]) / 1e6, 1)
            for r in prefills if not r["end_exact"]]
    log(f"stamped: {(hi - lo) / 1e9:.1f} s of the window before the "
        f"profiler's session are read: {len(part)} dispatches, end_exact "
        f"{exact} = {100 * exact / len(part):.1f}%; the host came late to "
        f"{len(late)} of {len(prefills)} prefill programs"
        + (f", which took at most ms {late[:8]}" if late else ""))
    if exact < MIN_EXACT_SHARE * len(part):
        log(f"stamped: under {100 * MIN_EXACT_SHARE:.0f}% of the ends are "
            f"exact (the host came to most programs after they had "
            f"finished): the timeline is bounds, nothing is read")
        return None
    if ctx.get("trace") is not None:
        verdict = check(ctx)
        if verdict is None:
            log("stamped: a traced run whose stamps could not be checked "
                "against the trace: nothing is read")
            return None
        if not verdict["ok"]:
            log(f"stamped: exact ends lie "
                f"{verdict.get('end_lag_ms', float('nan')):+.3f} ms after "
                f"their module events' at the median (limits 0 to "
                f"{MAX_END_LAG_MS}) and about that "
                f"{verdict.get('end_median_ms', float('nan')):.3f} ms at "
                f"the median, {verdict.get('end_p95_ms', float('nan')):.3f} "
                f"at p95 (limits {MAX_END_ERR_MEDIAN_MS} / "
                f"{MAX_END_ERR_P95_MS}): nothing is read")
            return None
    return lo, hi


def usable(ctx) -> Optional[Tuple[int, int]]:
    """The part [lo, hi) of the window before the profiler if the stamps
    may be read there, else None (the reason is logged once)."""
    if "stamped" not in ctx:
        ctx["stamped"] = _judge(ctx)
    return ctx["stamped"]


def exact_programs(ctx, kind: str) -> Optional[List[Dict[str, Any]]]:
    """The `kind` dispatch records of the usable part whose start and end
    are both exact: the host waited for the program, and for the one
    before it unless the device was idle when it was enqueued."""
    part = usable(ctx)
    if part is None:
        return None
    lo, hi = part
    recs = ring.records("engine.dispatch", ctx["log"])
    by_seq = {r["seq"]: r for r in recs}
    return [r for r in recs
            if r["kind"] == kind and lo <= r["dispatch_ns"] < hi
            and r.get("end_exact")
            and (r["device_start_ns"] == r["enqueued_ns"]
                 or (by_seq.get(r["seq"] - 1) or {}).get("end_exact"))]


def steps_before_profiler(ctx) -> Optional[List[Dict[str, Any]]]:
    """The `engine.step` records that started in the part before the
    profiler; None where there are none."""
    recs = ring.records("engine.step", ctx["log"])
    if recs is None:
        return None
    lo, hi = before_profiler_ns(ctx)
    if not ring.complete_since("engine.step", recs, "start_ns", lo,
                               ctx["log"]):
        return None
    return [r for r in recs if lo <= r["start_ns"] < hi] or None
