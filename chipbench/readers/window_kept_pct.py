"""What the sliding-window layers of the live sequences HOLD, over what the
same layers would hold had they kept every token: the sum of
`window_tokens_held` over the sum of `full_tokens_held` (a row's context)
of the window's decode dispatches, x 100. 100 = nothing was released; at a
window of 1024 and contexts of 7k about 15. Records without the fields (a
program before the two-kind cache) give None."""

from chipbench import ring


def read(ctx):
    recs = ring.in_window(ctx, "engine.dispatch", "dispatch_ns")
    if not recs:
        return None
    rows = [r for r in recs if r["kind"] == "decode"
            and r.get("window_tokens_held") is not None
            and r.get("full_tokens_held")]
    if not rows:
        ctx["log"]("ring engine.dispatch: no decode record in the window "
                   "carries window_tokens_held")
        return None
    held = sum(r["window_tokens_held"] for r in rows)
    every = sum(r["full_tokens_held"] for r in rows)
    ctx["log"](f"decode records in the window: {len(rows)}; a sliding layer "
               f"holds {held / len(rows):.0f} tokens of the live rows' "
               f"{every / len(rows):.0f} a step")
    return 100.0 * held / every
