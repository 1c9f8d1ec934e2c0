"""The sparse layers' decode attention as a share of its HBM roofline in
the traced seconds: the least time to read the SELECTED keys and values
of the live rows of the decode programs that ran wholly there (the rule's
count at each row's position, all sparse layers and kv heads, fused steps
a position apart; chipbench/sala_work.py) over the paged-decode kernel's
self time inside those programs.

What it leaves out, and says so: the selection itself (scoring the
compressed keys, `top_k`, building the table) is plain XLA inside
`_sparse_select`, which the trace cannot name, so its time is in no
denominator here and its bytes (the compressed keys scored) in no
numerator; `decode_bytes_roofline.serve_tok_s` carries both. A program
whose records carry no `sparse_layers` gives None. Selected keys of live
rows only, so a reading over 100% is a bug in the count."""

from chipbench import paired, sala_work


def read(ctx, op_pattern: str):
    if not ctx["peaks"]:
        return None
    whole = paired.whole_programs(ctx, "decode", "sparse decode roofline")
    if whole is None:
        return None
    whole = [(e, r) for e, r in whole if r.get("sparse_layers")]
    if not whole:
        ctx["log"]("ring engine.dispatch: no decode record carries "
                   "sparse_layers")
        return None
    kernel_ns = paired.op_self_ns(ctx, whole, op_pattern)
    if kernel_ns <= 0:
        return None
    pub = ctx["cell"].config
    need = sum(sala_work.sparse_decode_bytes(
        pub, [c - 1 + j for _, _, c in r["rows"] for j in range(r["k"])])
        for _, r in whole)
    least = need / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["log"](
        f"sparse decode: {len(whole)} decode programs paired with records; "
        f"selected K and V {need / 1e6:.1f} MB, least {least * 1e3:.3f} ms, "
        f"kernel {kernel_ns / 1e6:.3f} ms (the selection's own time is not "
        f"in it)")
    return 100.0 * least / (kernel_ns / 1e9)
