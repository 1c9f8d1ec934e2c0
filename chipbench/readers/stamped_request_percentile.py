"""A percentile of one of the two device parts of a first token's wait that
the engine stamps on its `engine.request` records (PR 37): `device_wait_ns`
(first dispatch to the last pass's stamped end, less the prompt's own
programs: other programs ahead of it on the device, and the fetch's lag
behind that end) or `prefill_device_ns` (the prompt's own passes; each row
of a wave carries the whole wave). Over the counted requests whose first
token came BEFORE the profiler's session (`chipbench/stamped.py`) and whose
parts are exact; where the host came late to one of a request's programs
(`parts_exact` False) its parts are bounds, and it is counted in the line
and not read, as a preempted request is (it keeps its first token and gets
a later dispatch). With `partition` the whole of the runner's TTFT is logged
a request: late + queue wait + the two device parts + the host's code up to
the token + the runner's stamp after `step()` less the engine's."""

from chipbench import ring, stamped, stats

PARTS = ("device_wait_ns", "prefill_device_ns", "harvest_host_ns")


def read(ctx, field: str, q: float = 50, partition: bool = False):
    part = stamped.usable(ctx)
    if part is None:
        return None
    lo, hi = part
    recs = ring.records("engine.request", ctx["log"])
    if recs is None or not ring.complete_since(
            "engine.request", recs, "arrival_ns", lo, ctx["log"]):
        return None
    by_id = {r["request_id"]: r for r in recs}
    counted = [(t, by_id[t.rid]) for t in ctx["records"]
               if t.counted and t.rid in by_id]
    early = [(t, r) for t, r in counted
             if r.get(field) is not None and not r["preemptions"]
             and r["first_token_ns"] < hi]
    read_ = [(t, r) for t, r in early if r["parts_exact"]]
    if not read_:
        ctx["log"](f"engine.request {field}: none of {len(counted)} counted "
                   f"requests has it exact with a first token before the "
                   f"profiler ({len(early)} have it as a bound)")
        return None
    whole = sum(sum(r[p] for p in PARTS)
                == r["first_token_ns"] - r["dispatched_ns"]
                for _, r in read_)
    values = [r[field] / 1e6 for _, r in read_]
    ctx["log"](
        f"engine.request {field}: {len(early)} of {len(counted)} counted "
        f"requests got their first token before the profiler; the host "
        f"came late to a program of {len(early) - len(read_)} of them "
        f"(parts_exact False: not read); ms {stats.summarize(values)}; the "
        f"three parts sum to first_token_ns - dispatched_ns in {whole} of "
        f"{len(read_)}")
    if partition:
        _log_partition(ctx, read_)
    return stats.percentile(values, q)


def _log_partition(ctx, read_) -> None:
    """ttft = late + (dispatched - arrival) + device_wait + prefill_device
    + harvest_host + rest, a request; p50 and p95 of each, and `rest` (the
    runner's stamp after step() returns less `first_token_ns`)."""
    names = ("ttft", "late", "queue_wait", "device_wait", "prefill_device",
             "harvest_host", "rest")
    cols = {k: [] for k in names}
    for t, r in read_:
        if t.ttft_ms is None:
            continue
        row = [t.ttft_ms, t.late_ms,
               (r["dispatched_ns"] - r["arrival_ns"]) / 1e6,
               *(r[p] / 1e6 for p in PARTS)]
        for k, v in zip(names, row + [row[0] - sum(row[1:])]):
            cols[k].append(v)
    if not cols["ttft"]:
        return
    for q in (50, 95):
        ctx["log"](
            f"stamped ttft partition at p{q} over {len(cols['ttft'])} "
            f"requests (ms): " + " ; ".join(
                f"{k} {stats.percentile(cols[k], q):.3f}" for k in names))
    ctx["log"](f"stamped ttft partition: rest a request, median "
               f"{stats.percentile(cols['rest'], 50):.3f} max "
               f"{max(cols['rest']):.3f}")
