"""The selective-scan kernel's share of its roofline in the traced
seconds: the least time the chip could take for the REAL prompt tokens of
the prefill programs that ran wholly there, times the layers that scan
them (`q_tokens` of each real row and `ssm_layers` of the paired
`engine.dispatch` records; bytes by chipbench/ssm_work.py), over the
kernel's self time inside those programs.

The bound is the HBM's: `peaks.json` has no vector-unit peak (the scan has
no matmul, so the bf16 FLOP/s peak is not its ceiling), and the line says
what the operations would take at that peak only for the reader. A
program whose records carry no `ssm_layers` (any other model, a commit
before the state-space layers) gives None. Needed work counts real tokens
only, so a reading over 100% is a bug in the count."""

from chipbench import paired, ssm_work


def read(ctx, op_pattern: str):
    if not ctx["peaks"]:
        return None
    whole = paired.whole_programs(ctx, "prefill", "ssm_scan roofline")
    if whole is None:
        return None
    whole = [(e, r) for e, r in whole if r.get("ssm_layers")]
    if not whole:
        ctx["log"]("ring engine.dispatch: no prefill record carries "
                   "ssm_layers")
        return None
    kernel_ns = paired.op_self_ns(ctx, whole, op_pattern)
    if kernel_ns <= 0:
        return None
    pub = ctx["cell"].config
    d, n = pub["mamba_expand"] * pub["hidden_size"], pub["mamba_d_state"]
    token_layers = sum(r["ssm_layers"] * sum(q for _, q, _ in r["rows"])
                       for _, r in whole)
    t_bytes = ssm_work.scan_bytes(token_layers, d, n) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    t_ops = ssm_work.scan_ops(token_layers, d, n) \
        / ctx["peaks"]["bf16_flops_per_s"]
    ctx["log"](
        f"ssm_scan: {len(whole)} prefill programs paired with records, "
        f"{token_layers} real (token, layer)s; kernel "
        f"{kernel_ns / 1e6:.3f} ms, least {t_bytes * 1e3:.3f} ms by HBM "
        f"bytes (the bound: no vector-unit peak in peaks.json; the "
        f"operations at the bf16 matmul peak would be {t_ops * 1e3:.3f} ms)")
    return 100.0 * t_bytes / (kernel_ns / 1e9)
