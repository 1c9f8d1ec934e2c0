"""Median device duration of the executions of one program (events of the
`XLA Modules` line whose name matches `pattern`), over all chips."""

import statistics

from chipbench import tracered


def read(ctx, pattern: str):
    red = ctx["trace"]
    if red is None:
        return None
    durs = []
    for chip, events in red.trace.modules.items():
        durs += tracered.durations_matching(
            tracered.clip(events, red.trace.window), pattern)
    if not durs:
        return None
    ctx["log"](f"program /{pattern}/: {len(durs)} executions in the trace")
    return statistics.median(durs) / 1e6
