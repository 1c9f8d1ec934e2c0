"""A ratio of sums over the window's `engine.dispatch` records of kind
`block` (a model that generates by diffusion over blocks): `num` over
`den`, each a field of the record or, with `den_passes_x_experts`, the
record's forward passes x the configuration's layers x experts (what
`moe_experts_touched` is a share of), times `scale`.

    block_passes x rows / block_tokens_fixed   forward passes a row
                                        computes for a token it emits
    moe_experts_touched / (passes x L x E) x 100   experts a pass reads

Records without the fields (a program before the block step) give None."""

from chipbench import ring


def read(ctx, num: str, den: str = None, den_passes_x_experts: bool = False,
         num_x_rows: bool = False, scale: float = 1.0):
    recs = ring.in_window(ctx, "engine.dispatch", "dispatch_ns")
    if not recs:
        return None
    blocks = [r for r in recs if r["kind"] == "block"
              and r.get("block_passes") and r.get(num) is not None]
    if not blocks:
        ctx["log"]("ring engine.dispatch: no block record in the window "
                   f"carries block_passes and {num}")
        return None
    top = sum(r[num] * (len(r["rows"]) if num_x_rows else 1)
              for r in blocks)
    if den_passes_x_experts:
        pub = ctx["cell"].config
        bottom = sum(r["block_passes"] for r in blocks) * (
            pub["num_hidden_layers"] * pub["num_experts"])
    else:
        bottom = sum(r[den] for r in blocks)
    if not bottom:
        return None
    ctx["log"](f"block records in the window: {len(blocks)}; {num} {top} "
               f"over {'passes x layers x experts' if den_passes_x_experts else den} "
               f"{bottom}; rows a program "
               f"{sum(len(r['rows']) for r in blocks) / len(blocks):.1f}")
    return scale * top / bottom
