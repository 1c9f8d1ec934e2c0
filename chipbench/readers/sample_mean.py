"""Mean of a counter the benchmark's loop sampled after every step of the
window (`LLMEngine.stats()` keys)."""


def read(ctx, key: str):
    vals = ctx["samples"].get(key) or []
    return sum(vals) / len(vals) if vals else None
