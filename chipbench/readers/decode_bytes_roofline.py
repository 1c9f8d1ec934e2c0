"""A hybrid model's decode step as a share of the bytes it must move: the
least time the chip could take for the decode programs that ran wholly in
the traced seconds (every weight once a fused step, each LIVE row's
recurrent state read and written once, each live row's KV tokens read
once; chipbench/ssm_work.py, from the real rows and `ssm_state_bytes_row`
of the paired `engine.dispatch` records and the published keys) over the
device duration of those programs (`jit_run_decode(` module events).

It is where a state update that touches dead slots, or copies a pool,
shows: neither is in the least. A program whose records carry no
`ssm_state_bytes_row` gives None. Live rows only, so a reading over 100%
is a bug in the count."""

from chipbench import paired, ssm_work


def read(ctx):
    if not ctx["peaks"]:
        return None
    whole = paired.whole_programs(ctx, "decode", "decode bytes roofline")
    if whole is None:
        return None
    whole = [(e, r) for e, r in whole if r.get("ssm_state_bytes_row")]
    if not whole:
        ctx["log"]("ring engine.dispatch: no decode record carries "
                   "ssm_state_bytes_row")
        return None
    pub = ctx["cell"].config
    nq, nkv = pub["num_attention_heads"], pub["num_key_value_heads"]
    hd = pub.get("head_dim") or pub["hidden_size"] // nq
    n_attn = pub["num_hidden_layers"] // pub["attn_layer_period"]
    kv_token = n_attn * nkv * 2 * hd * 2            # K and V, bf16
    weights = ssm_work.hybrid_weight_bytes(pub)
    need = rows = 0
    for _, r in whole:
        for j in range(r["k"]):                     # a fused step at a time
            need += ssm_work.decode_step_bytes(
                weights, len(r["rows"]), r["ssm_state_bytes_row"],
                sum(c + j for _, _, c in r["rows"]), kv_token)
        rows += len(r["rows"])
    device_ns = sum(e[2] for e, _ in whole)
    least = need / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["log"](
        f"decode bytes: {len(whole)} decode programs paired with records, "
        f"{rows / len(whole):.1f} live rows a program; weights "
        f"{weights / 1e9:.3f} GB a step, least {least * 1e3:.3f} ms, "
        f"device {device_ns / 1e6:.3f} ms")
    return 100.0 * least / (device_ns / 1e9)
