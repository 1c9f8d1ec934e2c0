"""Place the program's flight records on a profiler trace.

The recorder stamps Unix-epoch nanoseconds; `jax.profiler.ProfileData`
reports the same clock counted from the start of its session. So one
constant per session moves a record onto a trace. `fit` finds it from two
views of the same events: the `chipbench.engine.step` host spans the runner
wraps around every `engine.step()` of the traced seconds, and the
`engine.step` records the engine wrote for every step of the run. `pair`
then gives each executed program of the trace (a module event of the
device plane) the `engine.dispatch` record of the dispatch that ran it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

TOLERANCE_NS = 1_000_000


@dataclasses.dataclass
class Fit:
    offset_ns: int        # record time - trace time
    residual_ns: int      # largest |record - span - offset|
    first: int            # index of the record that is the first span
    spans: int


def fit(span_starts: Sequence[int], record_starts: Sequence[int],
        tol_ns: int = TOLERANCE_NS) -> Tuple[Optional[Fit], str]:
    """The one alignment of the span sequence inside the record sequence
    at which every (record start - span start) agrees within `tol_ns`.
    Returns (fit, why): no fit if no alignment is consistent, if several
    are, or if there are too few spans to tell."""
    spans = np.asarray(span_starts, np.int64)
    recs = np.asarray(record_starts, np.int64)
    m, n = len(spans), len(recs)
    if m < 3:
        return None, f"{m} spans: too few to align"
    if n < m:
        return None, f"{n} records for {m} spans"
    diffs = np.lib.stride_tricks.sliding_window_view(recs, m) - spans
    good = np.flatnonzero(np.ptp(diffs, axis=1) <= tol_ns)
    if len(good) != 1:
        return None, (f"{len(good)} alignments of {m} spans in {n} records "
                      f"agree within {tol_ns} ns")
    row = diffs[good[0]]
    offset = int(np.sort(row)[m // 2])    # integers: 1.8e18 ns overflow a float's 53 bits
    return Fit(offset_ns=offset,
               residual_ns=int(np.abs(row - offset).max()),
               first=int(good[0]), spans=m), "ok"


Event = Tuple[str, int, int]      # (name, start_ns, duration_ns)


def pair(events: Sequence[Tuple[str, int, int]],
         dispatches: Sequence[Dict[str, Any]], offset_ns: int,
         tol_ns: int = TOLERANCE_NS
         ) -> Tuple[Optional[List[Dict[str, Any]]], str]:
    """The dispatch record of each executed program. `events` are (kind,
    start_ns, duration_ns) on the trace's clock in the order the device ran
    them, `dispatches` the `engine.dispatch` records in dispatch order
    (`seq`). The device runs programs in dispatch order, so the events are
    one run of consecutive records; it is the run in which every kind
    agrees, every record was dispatched before its program started and
    fetched after it ended. Where several runs qualify (a device that
    idles), the one whose fetches follow their programs most closely."""
    m, n = len(events), len(dispatches)
    if m == 0 or n < m:
        return None, f"{m} programs, {n} dispatch records"
    kinds = [d["kind"] for d in dispatches]
    sent = np.asarray([d["dispatch_ns"] for d in dispatches],
                      np.int64) - offset_ns
    got = np.asarray([d["fetch_end_ns"] for d in dispatches],
                     np.int64) - offset_ns
    start = np.asarray([e[1] for e in events], np.int64)
    end = start + np.asarray([e[2] for e in events], np.int64)
    want = [e[0] for e in events]
    best, lag_of_best, qualified = None, None, 0
    for j in range(n - m + 1):
        if kinds[j:j + m] != want:
            continue
        if (sent[j:j + m] > start + tol_ns).any() \
                or (got[j:j + m] < end - tol_ns).any():
            continue
        qualified += 1
        lag = int(np.median(got[j:j + m] - end))
        if best is None or lag < lag_of_best:
            best, lag_of_best = j, lag
    if best is None:
        return None, (f"no run of {m} records has the programs' kinds and "
                      f"was dispatched before and fetched after them")
    return (list(dispatches[best:best + m]),
            f"record seq {dispatches[best]['seq']} is the first program; "
            f"{qualified} runs qualified; median fetch end - program end "
            f"{lag_of_best / 1e6:.3f} ms")
