"""Device microseconds a REAL prompt token of the prefill programs the
engine ran before the profiler's session: the sum of their stamped device
time (`device_end_ns - device_start_ns` of `engine.dispatch`, PR 37) over
the sum of their rows' `q_tokens`. Over the programs whose start and end
are both exact (`chipbench/stamped.py:exact_programs`). A ratio of sums
over every pass of the part, where a median over its requests sits on a
step between two buckets; padding counts against it, as it costs."""

from chipbench import stamped, stats


def read(ctx):
    passes = stamped.exact_programs(ctx, "prefill")
    if not passes:
        return None
    took = [r["device_end_ns"] - r["device_start_ns"] for r in passes]
    tokens = [sum(q for _, q, _ in r["rows"]) for r in passes]
    if not sum(tokens):
        return None
    ctx["log"](
        f"stamped prefill: {len(passes)} passes with exact stamps before "
        f"the profiler, {sum(took) / 1e6:.1f} ms on the device for "
        f"{sum(tokens)} real tokens ({sum(r['tokens_padded'] for r in passes)}"
        f" computed); ms a pass "
        f"{stats.summarize([t / 1e6 for t in took])}")
    return sum(took) / 1e3 / sum(tokens)
