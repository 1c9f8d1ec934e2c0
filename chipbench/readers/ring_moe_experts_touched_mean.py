"""Experts a decode step touches in a layer, mean over the window's decode
dispatches: `moe_experts_touched / (layers x k)` of each `engine.dispatch`
record of kind decode (`k` = the record's fused steps). With
`decode_batch_mean.tpot` it says how many weight bytes a step must read:
every touched expert's three matrices. Records without the field (a dense
model, a commit before the dropless layer) give `None`."""

import statistics

from chipbench import ring


def read(ctx):
    recs = ring.in_window(ctx, "engine.dispatch", "dispatch_ns")
    if not recs:
        return None
    layers = ctx["cell"].config["num_hidden_layers"]
    per_step = [r["moe_experts_touched"] / (layers * r["k"]) for r in recs
                if r["kind"] == "decode" and "moe_experts_touched" in r]
    if not per_step:
        return None
    ctx["log"](f"moe experts touched a layer and decode step: "
               f"{len(per_step)} decode records in the window; mean "
               f"{statistics.fmean(per_step):.3f} median "
               f"{statistics.median(per_step):.3f} max {max(per_step):.3f}")
    return statistics.fmean(per_step)
