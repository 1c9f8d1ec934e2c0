"""Median milliseconds of the window's flight records of one kind:
`end_ns - start_ns`, less the fields named in `minus` (an `engine.step`
less its `fetch_ns` is the host's own time in the step; a `train.step` is
the host's time to dispatch one training step)."""

import statistics

from chipbench import ring


def read(ctx, kind: str, minus=()):
    recs = ring.in_window(ctx, kind, "start_ns")
    if not recs:
        return None
    took = [(r["end_ns"] - r["start_ns"] - sum(r[f] for f in minus)) / 1e6
            for r in recs]
    ctx["log"](f"{kind}: {len(took)} records in the window; ms median "
               f"{statistics.median(took):.4f} max {max(took):.4f}")
    return statistics.median(took)
