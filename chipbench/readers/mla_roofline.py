"""A share of a roofline inside the programs of one kind that ran wholly
in the traced seconds, for a model with latent attention (MLA) and one
chip's share of routed experts (chipbench/mla_work.py counts the work from
the paired `engine.dispatch` records and the published keys;
chipbench/paired.py pairs records and programs):

    what="decode_kernel"  the absorbed decode kernel: the larger of the
                    least time by HBM bytes (the live rows' latents at 576
                    values a token and layer, queries in and out) and by
                    the MXU (64 heads x 2 x (576 + 512) a latent) over the
                    kernel's self time in the decode programs
    what="flash"    the materialised prefill: the REAL (query, key) pairs
                    (own tokens causal, context by `ctx_tokens`) x 64 heads
                    x 2 x (192 + 128) at the bf16 peak over the flash
                    forward's self time in the prefill programs
    what="pass"     a prefill pass's real operations (its real tokens'
                    matmuls with the held experts' real assignments, W_kvb
                    over the context its chunks materialised, the real
                    pairs) at the bf16 peak over the prefill programs' own
                    device time: it carries the materialising einsums,
                    which are plain XLA and have no name in a trace
    what="decode_bytes"  the least time to read, a step, the weights every
                    step reads + the experts TOUCHED + the live rows'
                    latents, over the decode programs' own device time
    what="gmm"      the grouped matmuls' least time for the HELD experts'
                    real assignments on the touched experts, at the
                    expert's width, over the kernel's self time in the
                    prefill and decode programs

Records without the latent family's fields (a program before it) give
None and nothing raises; needed work counts real tokens, live rows and
touched experts only, so a reading over 100% is a bug in the count."""

from chipbench import flops, mla_work, paired


def _whole(ctx, kind: str, what: str):
    whole = paired.whole_programs(ctx, kind, f"mla {what} roofline")
    if whole is None:
        return None
    whole = [(e, r) for e, r in whole if r.get("mla_layers")]
    if not whole:
        ctx["log"](f"ring engine.dispatch: no {kind} record carries "
                   f"mla_layers")
        return None
    return whole


def read(ctx, what: str, op_pattern: str = None):
    if not ctx["peaks"]:
        return None
    pub, log = ctx["cell"].config, ctx["log"]
    kinds = {"decode_kernel": ("decode",), "decode_bytes": ("decode",),
             "flash": ("prefill",), "pass": ("prefill",),
             "gmm": ("prefill", "decode")}[what]
    need = {"ops": 0.0, "bytes": 0.0}
    took_ns = programs = 0
    for kind in kinds:
        whole = _whole(ctx, kind, what)
        if whole is None:
            if what == "gmm":
                continue       # a span may hold programs of one kind only
            return None
        programs += len(whole)
        for _, r in whole:
            if what == "decode_kernel":
                for _, _, c in r["rows"]:
                    w = mla_work.decode_kernel(c, r["k"], pub)
                    need["ops"] += w["ops"]
                    need["bytes"] += w["bytes"]
            elif what == "decode_bytes":
                # `moe_experts_touched` is summed over layers and fused
                # steps: spread evenly over the steps
                for j in range(r["k"]):
                    need["bytes"] += mla_work.decode_step_bytes(
                        pub, [c + j for _, _, c in r["rows"]],
                        (r.get("moe_experts_touched") or 0) / r["k"])
            elif what == "flash":
                need["ops"] += mla_work.flash_ops(sum(
                    mla_work.real_pairs(q, end) for _, q, end in r["rows"]),
                    pub)
            elif what == "pass":
                chunks = r.get("mla_ctx_chunks") or (0,) * len(r["rows"])
                tokens = max(1, sum(q for _, q, _ in r["rows"]))
                for (_, q, end), n in zip(r["rows"], chunks):
                    need["ops"] += mla_work.pass_ops(
                        q, end, n, (r.get("moe_assignments") or 0)
                        * q / tokens, pub)
            else:
                w = mla_work.gmm_work(pub, r.get("moe_assignments") or 0,
                                      r.get("moe_experts_touched") or 0)
                need["ops"] += w["ops"]
                need["bytes"] += w["bytes"]
        if op_pattern is None:
            took_ns += sum(e[2] for e, _ in whole)
        else:
            took_ns += paired.op_self_ns(ctx, whole, op_pattern)
    if took_ns <= 0 or not programs:
        return None
    roof = flops.roofline_seconds(need, ctx["peaks"])
    log(f"mla {what}: {programs} programs paired with records; took "
        f"{took_ns / 1e6:.3f} ms, least {roof['seconds'] * 1e3:.3f} ms, "
        f"{roof['bound']}-bound (ops {roof['t_ops'] * 1e3:.3f} ms, bytes "
        f"{roof['t_bytes'] * 1e3:.3f} ms)")
    return 100.0 * roof["seconds"] / (took_ns / 1e9)
