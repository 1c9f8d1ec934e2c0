"""The share of the router's assignments that fell on the experts this
chip HOLDS, over the window's `engine.dispatch` records:
`moe_assignments` (real assignments to held experts) over
`moe_assignments_routed` (real tokens x experts per token x expert
layers), in percent: 100 x held / routed experts under even routing
(3.125 for 12 of 384). With `moe_experts_touched` in the log it says how
many bytes of experts a step reads. Records without the field (a program
that holds all its experts, or one before the share) give None."""

from chipbench import ring


def read(ctx):
    recs = ring.in_window(ctx, "engine.dispatch", "dispatch_ns")
    if not recs:
        return None
    recs = [r for r in recs if r.get("moe_assignments_routed")]
    if not recs:
        ctx["log"]("ring engine.dispatch: no record in the window carries "
                   "moe_assignments_routed")
        return None
    held = sum(r["moe_assignments"] for r in recs)
    routed = sum(r["moe_assignments_routed"] for r in recs)
    decode = [r for r in recs if r["kind"] == "decode"]
    if decode:
        ctx["log"](
            f"expert share: decode steps touched "
            f"{sum(r['moe_experts_touched'] for r in decode) / sum(r['k'] for r in decode):.2f}"
            f" held experts a step (all expert layers), "
            f"{sum(len(r['rows']) for r in decode) / len(decode):.1f} live "
            f"rows a program")
    ctx["log"](f"expert share: {held} of {routed} routed assignments on "
               f"held experts over {len(recs)} records")
    return 100.0 * held / routed
