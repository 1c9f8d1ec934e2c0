"""Share of the token positions the window's prefill programs computed
that no request asked for: 100 x (1 - real q_tokens / tokens_padded) over
the `engine.dispatch` records of kind `prefill` dispatched in the window
(`tokens_padded` is rows x bucket of the compiled program)."""

from chipbench import ring


def read(ctx):
    recs = ring.in_window(ctx, "engine.dispatch", "dispatch_ns")
    if recs is None:
        return None
    waves = [r for r in recs if r["kind"] == "prefill"]
    padded = sum(r["tokens_padded"] for r in waves)
    if padded <= 0:
        return None
    real = sum(q for r in waves for _, q, _ in r["rows"])
    rows = sum(len(r["rows"]) for r in waves)
    ctx["log"](f"prefill waves in the window: {len(waves)}, real rows "
               f"{rows} of {sum(r['rows_padded'] for r in waves)}, real "
               f"tokens {real} of {padded}")
    return 100.0 * (1.0 - real / padded)
