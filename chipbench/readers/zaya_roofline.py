"""A share of a roofline, of a program's time or of the experts, inside the
programs of one kind that ran wholly in the traced seconds, for a model
whose attention runs inside a compressed latent beside a tail a decode slot
(models/zaya.py; chipbench/zaya_work.py counts the work from the paired
`engine.dispatch` records and the published keys; chipbench/paired.py pairs
records and programs):

    what="cca_decode"  the paged-decode kernel at 8 / 2 heads of 128: the
                    larger of the least time by HBM bytes (the live rows'
                    keys and values, 1 KB a token and layer, queries in
                    and out) and by the MXU, over the kernel's self time in
                    the decode programs
    what="cca_flash"  the flash calls: the REAL (query, key) pairs x 8
                    heads x 4 x 128 at the bf16 peak over their self time
                    in the prefill programs
    what="pass"     a prefill program: the larger of its real operations
                    at the bf16 peak and its least bytes (the weights once
                    a program, the experts touched, its rows' keys and
                    values) at the HBM peak, over the prefill programs' own
                    device time
    what="decode_bytes"  the least time to read, a step, the weights every
                    step reads + the experts TOUCHED + the live rows' keys,
                    values and tails + the head, over the decode programs'
                    own device time
    what="gmm"      the grouped matmuls' least time for the real
                    assignments on the touched experts over the kernel's
                    self time in the programs of `kind`
    what="time"     the self time of the ops `op_pattern` names as a share
                    of the device time of the programs of `kind`
    what="experts_touched"  the experts a decode step's live rows touched
                    as a share of the layers' experts

Records without the family's `cca_layers` (a program before it) give None
and nothing raises; needed work counts real tokens, live rows, pairs under
the mask and touched experts only, so a roofline reading over 100% is a bug
in the count."""

from chipbench import flops, paired, zaya_work

KINDS = {"cca_decode": "decode", "decode_bytes": "decode",
         "experts_touched": "decode", "cca_flash": "prefill",
         "pass": "prefill"}


def _whole(ctx, kind: str, what: str):
    whole = paired.whole_programs(ctx, kind, f"zaya {what}")
    if whole is None:
        return None
    whole = [(e, r) for e, r in whole if r.get("cca_layers")]
    if not whole:
        ctx["log"](f"ring engine.dispatch: no {kind} record carries "
                   f"cca_layers")
        return None
    return whole


def _need(what: str, r, pub):
    """(operations, bytes) the record's program needs at the least."""
    rows, k = r["rows"], r["k"]
    touched = r.get("moe_experts_touched") or 0
    assigned = r.get("moe_assignments") or 0
    ops = nbytes = 0.0
    if what == "cca_decode":
        for _, _, c in rows:
            w = zaya_work.decode_kernel(c, k, pub)
            ops, nbytes = ops + w["ops"], nbytes + w["bytes"]
    elif what == "cca_flash":
        ops = zaya_work.flash_ops(rows, pub)
    elif what == "decode_bytes":
        # `moe_experts_touched` is summed over layers and fused steps:
        # spread evenly over the steps
        nbytes = sum(zaya_work.decode_step_bytes(
            pub, [c + j for _, _, c in rows], touched / k) for j in range(k))
    elif what == "pass":
        tokens = max(1, sum(q for _, q, _ in rows))
        ops = sum(zaya_work.pass_ops(q, end, assigned * q / tokens, pub)
                  for _, q, end in rows)
        nbytes = zaya_work.program_weight_bytes(pub, touched, False) + sum(
            zaya_work.pass_kv_bytes(q, end, pub) for _, q, end in rows)
    else:
        w = zaya_work.gmm_work(pub, assigned, touched)
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
        slots = steps * pub["num_hidden_layers"] * pub["num_experts"]
        log(f"zaya experts_touched: {touched} over {steps} decode steps x "
            f"{pub['num_hidden_layers']} layers x {pub['num_experts']} "
            f"experts; {sum(len(r['rows']) for _, r in whole) / len(whole):.1f}"
            f" live rows a program")
        return 100.0 * touched / slots
    device_ns = sum(e[2] for e, _ in whole)
    took_ns = (device_ns if op_pattern is None
               else paired.op_self_ns(ctx, whole, op_pattern))
    if took_ns <= 0 or device_ns <= 0:
        return None
    if what == "time":
        return 100.0 * took_ns / device_ns
    need = {"ops": 0.0, "bytes": 0.0}
    for _, r in whole:
        ops, nbytes = _need(what, r, pub)
        need["ops"] += ops
        need["bytes"] += nbytes
    roof = flops.roofline_seconds(need, ctx["peaks"])
    log(f"zaya {what} ({kind}): {len(whole)} programs paired with records; "
        f"took {took_ns / 1e6:.3f} ms ({took_ns / 1e6 / len(whole):.3f} a "
        f"program), least {roof['seconds'] * 1e3:.3f} ms, {roof['bound']}"
        f"-bound (ops {roof['t_ops'] * 1e3:.3f} ms, bytes "
        f"{roof['t_bytes'] * 1e3:.3f} ms)")
    return 100.0 * roof["seconds"] / (took_ns / 1e9)
