"""A share of a roofline inside the programs of one kind that ran wholly in
the traced seconds, for a model of gated-delta-net and latent-attention
layers with one chip's share of routed experts (chipbench/gdn_work.py
counts the work from the paired `engine.dispatch` records and the published
keys; chipbench/paired.py pairs records and programs):

    what="update_kernel"  the delta-rule update kernel: the least time by
                    HBM bytes (a live row's state read and written a GDN
                    layer and fused step, its q, k, v, o and gates) against
                    the kernel's self time in the decode programs
    what="decode_bytes"  the least time to read, a step, the weights every
                    step reads + the experts TOUCHED + the live rows'
                    states and tails (read and written) + their latents,
                    over the decode programs' own device time: the share of
                    the whole step
    what="pass"     a prefill pass's real operations (its real tokens'
                    matmuls with the held experts' real assignments, the
                    delta rule's chunk products, W_kvb over the context its
                    chunks materialised, the real pairs) at the bf16 peak
                    over the prefill programs' own device time: it carries
                    the conv, the solve and the materialising einsums,
                    which are plain XLA and have no name in a trace

Records without the family's `gdn_layers` (a program before it) give None
and nothing raises; needed work counts real tokens, live rows and touched
experts only, so a reading over 100% is a bug in the count."""

from chipbench import flops, gdn_work, paired


def _whole(ctx, kind: str, what: str):
    whole = paired.whole_programs(ctx, kind, f"gdn {what} roofline")
    if whole is None:
        return None
    whole = [(e, r) for e, r in whole if r.get("gdn_layers")]
    if not whole:
        ctx["log"](f"ring engine.dispatch: no {kind} record carries "
                   f"gdn_layers")
        return None
    return whole


def read(ctx, what: str, op_pattern: str = None):
    if not ctx["peaks"]:
        return None
    pub, log = ctx["cell"].config, ctx["log"]
    kind = {"update_kernel": "decode", "decode_bytes": "decode",
            "pass": "prefill"}[what]
    whole = _whole(ctx, kind, what)
    if whole is None:
        return None
    need = {"ops": 0.0, "bytes": 0.0}
    rows = 0
    for _, r in whole:
        rows += len(r["rows"])
        if what == "update_kernel":
            w = gdn_work.update_kernel(len(r["rows"]), r["k"], pub)
            need["ops"] += w["ops"]
            need["bytes"] += w["bytes"]
        elif what == "decode_bytes":
            # `moe_experts_touched` is summed over layers and fused steps:
            # spread evenly over the steps
            for j in range(r["k"]):
                need["bytes"] += gdn_work.decode_step_bytes(
                    pub, [c + j for _, _, c in r["rows"]],
                    (r.get("moe_experts_touched") or 0) / r["k"])
        else:
            chunks = r.get("mla_ctx_chunks") or (0,) * len(r["rows"])
            tokens = max(1, sum(q for _, q, _ in r["rows"]))
            for (_, q, end), n in zip(r["rows"], chunks):
                need["ops"] += gdn_work.pass_ops(
                    q, end, n, (r.get("moe_assignments") or 0) * q / tokens,
                    pub)
    if op_pattern is None:
        took_ns = sum(e[2] for e, _ in whole)
    else:
        took_ns = paired.op_self_ns(ctx, whole, op_pattern)
    if took_ns <= 0:
        return None
    roof = flops.roofline_seconds(need, ctx["peaks"])
    log(f"gdn {what}: {len(whole)} programs paired with records, "
        f"{rows / len(whole):.1f} real rows a program; took "
        f"{took_ns / 1e6:.3f} ms, least {roof['seconds'] * 1e3:.3f} ms, "
        f"{roof['bound']}-bound (ops {roof['t_ops'] * 1e3:.3f} ms, bytes "
        f"{roof['t_bytes'] * 1e3:.3f} ms)")
    return 100.0 * roof["seconds"] / (took_ns / 1e9)
