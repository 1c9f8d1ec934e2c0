"""A MiniCPM-SALA-family model's decode step as a share of the bytes it
must move: the least time the chip could take for the decode programs that
ran wholly in the traced seconds (every weight once a fused step, each
LIVE row's lightning state read and written once, the SELECTED keys and
values and the compressed keys scored of each live row in each sparse
layer; chipbench/sala_work.py, from the real rows of the paired
`engine.dispatch` records and the published keys) over the device duration
of those programs (`jit_run_decode(` module events).

It is where a state update that touches dead slots, a sparse layer that
reads its whole context, a copied pool or a slow selection shows: none is
in the least. A program whose records carry no `lin_state_bytes_row` gives
None. Live rows only, so a reading over 100% is a bug in the count."""

from chipbench import paired, sala_work


def read(ctx):
    if not ctx["peaks"]:
        return None
    whole = paired.whole_programs(ctx, "decode", "decode bytes roofline")
    if whole is None:
        return None
    whole = [(e, r) for e, r in whole if r.get("lin_state_bytes_row")]
    if not whole:
        ctx["log"]("ring engine.dispatch: no decode record carries "
                   "lin_state_bytes_row")
        return None
    pub = ctx["cell"].config
    need = rows = 0
    for _, r in whole:
        for j in range(r["k"]):                     # a fused step at a time
            need += sala_work.decode_step_bytes(
                pub, [c - 1 + j for _, _, c in r["rows"]])
        rows += len(r["rows"])
    device_ns = sum(e[2] for e, _ in whole)
    least = need / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["log"](
        f"decode bytes: {len(whole)} decode programs paired with records, "
        f"{rows / len(whole):.1f} live rows a program; weights "
        f"{sala_work.decode_weight_bytes(pub) / 1e9:.3f} GB a step, least "
        f"{least * 1e3:.3f} ms, device {device_ns / 1e6:.3f} ms")
    return 100.0 * least / (device_ns / 1e9)
