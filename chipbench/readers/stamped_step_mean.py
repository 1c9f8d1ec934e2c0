"""The mean `engine.step` duration (milliseconds) over the steps that
started in the window's part before the profiler's session: what a decoding
row pays a token, the decode program and on top of it the passes of other
prompts it waited for and any stall. A mean over some 2000 steps, because
the durations are a mixture (a step either waited for a pass of some bucket
or did not) and a high percentile sits on the edge between two of its parts
in one cell or another. The reader logs the percentiles all the same, and
the LONGEST step, for a stall inside one step is 13% of this mean at 3 s
and is named there: its six phases, how many of its harvests waited for the
device (`fetch_blocked`, PR 37: a long fetch with it is the device or the
runtime, a long other phase is the host), the rows it left running and
waiting, and how many steps took over `STALL` times the median (the longest
pass of a chat cell is 12 times its median step). It needs no exact stamp,
so `stamped.usable` does not gate it, and a program from before PR 37 has
everything but the flag."""

import statistics

from chipbench import stamped, stats

PHASES = ("intake_ns", "admit_ns", "dispatch_prefill_ns",
          "dispatch_decode_ns", "fetch_ns", "harvest_ns")
STALL = 20


def read(ctx):
    steps = stamped.steps_before_profiler(ctx)
    if not steps:
        return None
    took = [(r["end_ns"] - r["start_ns"]) / 1e6 for r in steps]
    at = max(range(len(steps)), key=took.__getitem__)
    r, median = steps[at], statistics.median(took)
    ctx["log"](
        f"engine.step: {len(steps)} before the profiler, ms mean "
        f"{statistics.fmean(took):.3f} {stats.summarize(took)} p99 "
        f"{stats.percentile(took, 99):.3f}; the longest is seq {r['seq']}, "
        f"{took[at]:.3f} ms: " + " ".join(
            f"{p[:-3]} {r[p] / 1e6:.3f}" for p in PHASES)
        + f"; fetch_blocked {r.get('fetch_blocked')} device_idle_ns "
        f"{r.get('device_idle_ns')}; running {r['running']} waiting "
        f"{r['waiting']}; {sum(t > STALL * median for t in took)} steps "
        f"over {STALL} x the median; the device had nothing enqueued "
        f"{sum(s.get('device_idle_ns') or 0 for s in steps) / 1e9:.3f} s")
    return statistics.fmean(took)
