"""Arithmetic on a run's timeline: percentiles, TTFT, TPOT, rates.
Pure Python + numpy; tested on hand-made timelines (tests/test_stats.py)."""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default); None for no samples."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class RequestRecord:
    """One request's timeline, seconds relative to the window's start."""
    rid: str
    due_s: float
    counted: bool
    prompt_tokens: int
    max_tokens: int
    sent_s: Optional[float] = None       # handed to the system
    first_s: Optional[float] = None      # first token delta returned
    finish_s: Optional[float] = None     # finished delta returned
    out_tokens: int = 0
    finish_reason: Optional[str] = None
    token_ids: Optional[List[int]] = None

    @property
    def ok(self) -> bool:
        return (self.finish_s is not None
                and self.finish_reason in ("length", "stop"))

    @property
    def late_ms(self) -> Optional[float]:
        return None if self.sent_s is None else 1e3 * (self.sent_s
                                                       - self.due_s)

    @property
    def ttft_ms(self) -> Optional[float]:
        """First token minus the time the request was DUE, so a stall
        counts against the requests behind it. Only for requests that
        finished: an unfinished one is a failure with no sample."""
        if not self.ok or self.first_s is None:
            return None
        return 1e3 * (self.first_s - self.due_s)

    @property
    def tpot_ms(self) -> Optional[float]:
        """(finish - first token) / (output tokens - 1): per request,
        because tokens may be delivered in chunks."""
        if not self.ok or self.first_s is None or self.out_tokens < 2:
            return None
        return 1e3 * (self.finish_s - self.first_s) / (self.out_tokens - 1)


def field_values(records: Sequence[RequestRecord], field: str,
                 counted_only: bool = True) -> List[float]:
    vals = [getattr(r, field) for r in records
            if r.counted or not counted_only]
    return [v for v in vals if v is not None]


def processed_tokens(records: Sequence[RequestRecord],
                     token_events: Sequence[Tuple[float, int]],
                     lo: float, hi: float) -> int:
    """All the work done in (lo, hi], finished request or not: the prompt
    tokens of every request whose prefill ended in it (its first token is
    the prefill's) plus every token generated in it. `token_events` are
    (time, tokens delivered) of every output delta."""
    prompts = sum(r.prompt_tokens for r in records
                  if r.first_s is not None and lo < r.first_s <= hi)
    return prompts + sum(n for t, n in token_events if lo < t <= hi)


def summarize(values: Sequence[float]) -> Dict[str, Optional[float]]:
    return {"n": len(values), "p50": percentile(values, 50),
            "p95": percentile(values, 95),
            "max": max(values) if values else None}
