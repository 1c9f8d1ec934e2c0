"""A percentile, over the window's counted requests, of the milliseconds
between two timestamps of their `engine.request` flight records
(`request_id` is the runner's `rid`): queue wait is `dispatched_ns -
arrival_ns`, prefill `first_token_ns - dispatched_ns`. With `partition`
the reader also logs how the runner's own TTFT splits at the median."""

import statistics

from chipbench import ring, stats


def read(ctx, start: str, end: str, q: float, partition: bool = False):
    recs = ring.records("engine.request", ctx["log"])
    if recs is None:
        return None
    lo, _ = ring.window_ns(ctx)
    if not ring.complete_since("engine.request", recs, "arrival_ns", lo,
                               ctx["log"]):
        return None
    by_id = {r["request_id"]: r for r in recs}
    counted = [(t, by_id[t.rid]) for t in ctx["records"]
               if t.counted and t.rid in by_id]
    values = [(r[end] - r[start]) / 1e6 for _, r in counted
              if r[start] is not None and r[end] is not None
              and r[end] >= r[start]]
    ctx["log"](f"engine.request {end} - {start}: {len(values)} of "
               f"{sum(1 for t in ctx['records'] if t.counted)} counted "
               f"requests; {stats.summarize(values)}")
    if partition:
        _log_partition(ctx, counted)
    return stats.percentile(values, q)


def _log_partition(ctx, counted) -> None:
    """ttft = late + queue wait + prefill + (the runner's stamp - the
    engine's first-token stamp), per request; medians of each."""
    parts = {"ttft": [], "late": [], "queue_wait": [], "prefill": [],
             "rest": []}
    for t, r in counted:
        if t.ttft_ms is None or r["dispatched_ns"] is None \
                or r["first_token_ns"] is None:
            continue
        wait = (r["dispatched_ns"] - r["arrival_ns"]) / 1e6
        prefill = (r["first_token_ns"] - r["dispatched_ns"]) / 1e6
        parts["ttft"].append(t.ttft_ms)
        parts["late"].append(t.late_ms)
        parts["queue_wait"].append(wait)
        parts["prefill"].append(prefill)
        parts["rest"].append(t.ttft_ms - t.late_ms - wait - prefill)
    if not parts["ttft"]:
        return
    med = {k: statistics.median(v) for k, v in parts.items()}
    ctx["log"](
        f"ttft partition at the median over {len(parts['ttft'])} requests "
        f"(ms): ttft {med['ttft']:.1f} ; late {med['late']:.1f} + "
        f"queue_wait {med['queue_wait']:.3f} + prefill "
        f"{med['prefill']:.1f} = "
        f"{med['late'] + med['queue_wait'] + med['prefill']:.1f} ; per "
        f"request ttft - (late + queue_wait + prefill): median "
        f"{med['rest']:.3f} max {max(parts['rest']):.3f}")
