"""A share of a roofline inside the programs of one kind that ran wholly
in the traced seconds, for a model whose attention layers are of two kinds,
sliding-window and full, with a sparse expert FFN in every layer
(chipbench/window_work.py counts the work from the paired `engine.dispatch`
records and the published keys; chipbench/paired.py pairs records and
programs):

    what="decode_kernel"  the sliding layers' decode kernel: the larger of
                    the least time by HBM bytes (the live rows' windows:
                    min(context, window) keys and values a step and layer,
                    queries in and out) and by the MXU over the kernel's
                    self time in the decode programs
    what="flash"    the sliding layers' flash calls: the REAL (query, key)
                    pairs INSIDE the band x 32 heads x 4 x 128 at the bf16
                    peak over their self time in the prefill programs
    what="full_decode_kernel"  the full layers' decode kernel, as
                    "decode_kernel": the live rows' whole contexts
    what="full_flash"  the full layers' flash calls (a pass's own tokens,
                    then its context in one call or in chunks), as "flash":
                    the REAL pairs under the causal mask
    what="pass"     a prefill pass's real operations (its real tokens'
                    matmuls with their real assignments, each layer kind's
                    real pairs) at the bf16 peak over the prefill programs'
                    own device time
    what="decode_bytes"  the least time to read, a step, the weights every
                    step reads + the experts TOUCHED + the live rows' keys
                    and values (a context a full layer, a window a sliding
                    one), over the decode programs' own device time
    what="gmm"      the grouped matmuls' least time for the real
                    assignments on the touched experts, at the expert's
                    width, over the kernel's self time in the prefill and
                    decode programs

Records without the family's fields (a program before it) give None and
nothing raises; needed work counts real tokens, live rows, pairs inside a
mask and touched experts only, so a reading over 100% is a bug in the
count."""

from chipbench import flops, paired, window_work


def _whole(ctx, kind: str, what: str):
    whole = paired.whole_programs(ctx, kind, f"window {what} roofline")
    if whole is None:
        return None
    whole = [(e, r) for e, r in whole if r.get("window_layers")]
    if not whole:
        ctx["log"](f"ring engine.dispatch: no {kind} record carries "
                   f"window_layers")
        return None
    return whole


def read(ctx, what: str, op_pattern: str = None):
    if not ctx["peaks"]:
        return None
    pub, log = ctx["cell"].config, ctx["log"]
    kinds = {"decode_kernel": ("decode",), "full_decode_kernel": ("decode",),
             "decode_bytes": ("decode",), "flash": ("prefill",),
             "full_flash": ("prefill",), "pass": ("prefill",),
             "gmm": ("prefill", "decode")}[what]
    decode_kernel = {"decode_kernel": window_work.window_decode_kernel,
                     "full_decode_kernel": window_work.full_decode_kernel}
    flash = {"flash": window_work.window_flash_ops,
             "full_flash": window_work.full_flash_ops}
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
            if what in decode_kernel:
                for _, _, c in r["rows"]:
                    w = decode_kernel[what](c, r["k"], pub)
                    need["ops"] += w["ops"]
                    need["bytes"] += w["bytes"]
            elif what == "decode_bytes":
                # `moe_experts_touched` is summed over layers and fused
                # steps: spread evenly over the steps
                for j in range(r["k"]):
                    need["bytes"] += window_work.decode_step_bytes(
                        pub, [c + j for _, _, c in r["rows"]],
                        (r.get("moe_experts_touched") or 0) / r["k"])
            elif what in flash:
                need["ops"] += flash[what](r["rows"], pub)
            elif what == "pass":
                tokens = max(1, sum(q for _, q, _ in r["rows"]))
                for _, q, end in r["rows"]:
                    need["ops"] += window_work.pass_ops(
                        q, end, (r.get("moe_assignments") or 0) * q / tokens,
                        pub)
            else:
                w = window_work.gmm_work(pub, r.get("moe_assignments") or 0,
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
    log(f"window {what}: {programs} programs paired with records; took "
        f"{took_ns / 1e6:.3f} ms, least {roof['seconds'] * 1e3:.3f} ms, "
        f"{roof['bound']}-bound (ops {roof['t_ops'] * 1e3:.3f} ms, bytes "
        f"{roof['t_bytes'] * 1e3:.3f} ms)")
    return 100.0 * roof["seconds"] / (took_ns / 1e9)
