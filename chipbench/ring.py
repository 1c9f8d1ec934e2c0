"""The program's flight recorder as the per-layer readers see it.

`ray_tpu.util.tracing` keeps a bounded ring of plain tuples per record kind
(`engine.request`, `engine.dispatch`, `engine.step`, `train.step`), filled
in every run; the readers reach them through `tracing.records(kind)`, which
is process-global. A program without the recorder (a parent commit), or a
ring that has dropped records of the window, gives `None`: the metric is
left out of the line, and nothing raises.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple


def records(kind: str, log) -> Optional[List[Dict[str, Any]]]:
    """Every held record of `kind` as a dict, oldest first."""
    from ray_tpu.util import tracing

    read = getattr(tracing, "records", None)
    if read is None or kind not in getattr(tracing, "FIELDS", {}):
        log(f"ring {kind}: this program has no flight recorder")
        return None
    fields = tracing.FIELDS[kind]
    return [dict(zip(fields, rec)) for rec in read(kind)]


def window_ns(ctx) -> Tuple[int, int]:
    """The timed window on the recorder's clock (Unix-epoch nanoseconds).
    The runner's `t0` is monotonic; the two clocks are read once, here."""
    shift = time.time_ns() - time.monotonic_ns()
    lo = int(ctx["runner"].t0 * 1e9) + shift
    return lo, lo + int(ctx["seconds"] * 1e9)


def complete_since(kind: str, recs: List[Dict[str, Any]], at: str,
                   since_ns: int, log) -> bool:
    """False, with a line, if the ring dropped records it wrote after
    `since_ns` (the ring drops its oldest)."""
    from ray_tpu.util import tracing

    lost = tracing.dropped(kind)
    if lost and (not recs or recs[0][at] > since_ns):
        log(f"ring {kind}: {lost} records dropped, some of them inside "
            f"the window: nothing is read")
        return False
    return True


def in_window(ctx, kind: str, at: str) -> Optional[List[Dict[str, Any]]]:
    """The records of `kind` whose timestamp `at` lies in the timed
    window."""
    recs = records(kind, ctx["log"])
    if recs is None:
        return None
    lo, hi = window_ns(ctx)
    if not complete_since(kind, recs, at, lo, ctx["log"]):
        return None
    return [r for r in recs if lo <= r[at] < hi]
