"""A kernel's device time (self time of the ops whose name matches
`pattern`) as a share of the device's busy time in the traced window."""

import re


def read(ctx, pattern: str):
    red = ctx["trace"]
    if red is None or red.busy_s <= 0:
        return None
    rx = re.compile(pattern)
    chips = max(1, len(red.trace.ops))
    secs = sum(s for name, s in red.ops_by_name_s.items() if rx.search(name))
    if secs <= 0:
        return None
    return 100.0 * (secs / chips) / red.busy_s
