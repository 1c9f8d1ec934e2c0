"""A share of a roofline, of a program's time or of the experts, inside the
programs of one kind that ran wholly in the traced seconds, for a model
whose sliding-window and full attention layers have their own query-head
counts (models/laguna.py; chipbench/laguna_work.py counts the work from the
paired `engine.dispatch` records and the published keys; chipbench/
paired.py pairs records and programs):

    what="window_decode" / "full_decode"  the kind's decode kernel: the
                    larger of the least time by HBM bytes (the live rows'
                    keys and values, a window or a context a step and
                    layer, once a kv head; queries in and out) and by the
                    MXU, over the kernel's self time in the decode programs
    what="window_flash" / "full_flash"  the kind's flash calls: the REAL
                    (query, key) pairs inside its mask x ITS heads x 4 x
                    128 at the bf16 peak over their self time in the
                    prefill programs
    what="pass"     a prefill program: the larger of its real operations
                    at the bf16 peak and its least bytes (the weights once
                    a program, the experts touched, its rows' keys and
                    values) at the HBM peak, over the prefill programs' own
                    device time
    what="decode_bytes"  the least time to read, a step, the weights every
                    step reads + the experts TOUCHED + the live rows' keys
                    and values, over the decode programs' own device time
    what="gmm"      the grouped matmuls' least time for the real
                    assignments on the touched experts over the kernel's
                    self time in the programs of `kind`
    what="attn_time"  the self time of the ops `op_pattern` names as a
                    share of the device time of the programs of `kind`
    what="experts_touched"  the experts a decode step's live rows touched
                    as a share of the sparse layers' experts

Records without the family's fields (a program before it) give None and
nothing raises; needed work counts real tokens, live rows, pairs inside a
mask and touched experts only, so a roofline reading over 100% is a bug in
the count."""

from chipbench import flops, laguna_work, paired
from chipbench.window_work import FULL, SLIDING

KINDS = {"window_decode": "decode", "full_decode": "decode",
         "decode_bytes": "decode", "experts_touched": "decode",
         "window_flash": "prefill", "full_flash": "prefill",
         "pass": "prefill"}
LAYER_KIND = {"window_decode": SLIDING, "full_decode": FULL,
              "window_flash": SLIDING, "full_flash": FULL}


def _whole(ctx, kind: str, what: str):
    whole = paired.whole_programs(ctx, kind, f"laguna {what}")
    if whole is None:
        return None
    whole = [(e, r) for e, r in whole if r.get("window_heads")]
    if not whole:
        ctx["log"](f"ring engine.dispatch: no {kind} record carries "
                   f"window_heads")
        return None
    return whole


def _need(what: str, r, pub):
    """(operations, bytes) the record's program needs at the least."""
    rows, k = r["rows"], r["k"]
    touched = r.get("moe_experts_touched") or 0
    assigned = r.get("moe_assignments") or 0
    ops = nbytes = 0.0
    if what in ("window_decode", "full_decode"):
        for _, _, c in rows:
            w = laguna_work.decode_kernel(LAYER_KIND[what], c, k, pub)
            ops, nbytes = ops + w["ops"], nbytes + w["bytes"]
    elif what in ("window_flash", "full_flash"):
        ops = laguna_work.flash_ops(LAYER_KIND[what], rows, pub)
    elif what == "decode_bytes":
        # `moe_experts_touched` is summed over layers and fused steps:
        # spread evenly over the steps
        nbytes = sum(laguna_work.decode_step_bytes(
            pub, [c + j for _, _, c in rows], touched / k) for j in range(k))
    elif what == "pass":
        tokens = max(1, sum(q for _, q, _ in rows))
        ops = sum(laguna_work.pass_ops(q, end, assigned * q / tokens, pub)
                  for _, q, end in rows)
        nbytes = laguna_work.program_weight_bytes(pub, touched, False) + sum(
            laguna_work.pass_kv_bytes(q, end, pub) for _, q, end in rows)
    else:
        w = laguna_work.gmm_work(pub, assigned, touched)
        ops, nbytes = w["ops"], w["bytes"]
    return ops, nbytes


def read(ctx, what: str, op_pattern: str = None, kind: str = None):
    if not ctx["peaks"]:
        return None         # no chip: no device plane to pair records with
    pub, log = ctx["cell"].config, ctx["log"]
    kind = kind or KINDS[what]
    whole = _whole(ctx, kind, what)
    if whole is None:
        return None
    if what == "experts_touched":
        steps = sum(r["k"] for _, r in whole)
        touched = sum(r.get("moe_experts_touched") or 0 for _, r in whole)
        log(f"laguna experts_touched: {touched} over {steps} decode steps x "
            f"{laguna_work.sparse_layers(pub)} layers x "
            f"{pub['num_experts']} experts")
        return 100.0 * touched / (steps * laguna_work.sparse_layers(pub)
                                  * pub["num_experts"])
    device_ns = sum(e[2] for e, _ in whole)
    took_ns = (device_ns if op_pattern is None
               else paired.op_self_ns(ctx, whole, op_pattern))
    if took_ns <= 0 or device_ns <= 0:
        return None
    if what == "attn_time":
        return 100.0 * took_ns / device_ns
    need = {"ops": 0.0, "bytes": 0.0}
    for _, r in whole:
        ops, nbytes = _need(what, r, pub)
        need["ops"] += ops
        need["bytes"] += nbytes
    roof = flops.roofline_seconds(need, ctx["peaks"])
    log(f"laguna {what} ({kind}): {len(whole)} programs paired with records; "
        f"took {took_ns / 1e6:.3f} ms, least {roof['seconds'] * 1e3:.3f} ms,"
        f" {roof['bound']}-bound (ops {roof['t_ops'] * 1e3:.3f} ms, bytes "
        f"{roof['t_bytes'] * 1e3:.3f} ms)")
    return 100.0 * roof["seconds"] / (took_ns / 1e9)
