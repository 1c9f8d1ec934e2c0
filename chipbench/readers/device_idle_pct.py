"""1 - (union of device op intervals) / traced window, mean over chips."""


def read(ctx):
    red = ctx["trace"]
    return None if red is None else red.idle_pct
