"""Peak share of the KV page pool in use: 1 - min(free_pages) / num_pages,
from `stats()["free_pages"]` sampled after every step."""


def read(ctx):
    free = ctx["samples"].get("free_pages") or []
    pages = ctx["counters"].get("num_pages")
    if not free or not pages:
        return None
    return 100.0 * (1.0 - min(free) / pages)
