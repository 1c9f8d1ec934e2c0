"""A MiniCPM-SALA-family model's prefill passes as a share of their
compute roofline in the traced seconds: the least time at the chip's bf16
matmul peak for the REAL tokens of the prefill programs that ran wholly
there (2 operations a matrix parameter and token through the kept layers,
the head where a pass is a prompt's last, the lightning layers' state
products, the sparse layers' REAL query-key pairs by the selection rule;
chipbench/sala_work.py, from the rows, `pass_index` and `final` of the
paired `engine.dispatch` records) over the device duration of those
programs (`jit_run_prefill(` module events).

The program-level metric that carries the mixers the trace cannot name:
the chunked lightning mixer and the sparse prefill are plain XLA inside
their jitted wrappers (no kernel, so no event of their own). A masked
dense pass over unselected blocks, a length bucket's padding and a head
computed at every position all read low here, which is the truth. A
program whose records carry no `sparse_layers` gives None."""

import numpy as np

from chipbench import paired, sala_work


def read(ctx):
    if not ctx["peaks"]:
        return None
    whole = paired.whole_programs(ctx, "prefill", "prefill pass roofline")
    if whole is None:
        return None
    whole = [(e, r) for e, r in whole if r.get("sparse_layers")]
    if not whole:
        ctx["log"]("ring engine.dispatch: no prefill record carries "
                   "sparse_layers")
        return None
    pub = ctx["cell"].config
    ops = tokens = resumed = 0
    for _, r in whole:
        for (_, q, end), index, final in zip(r["rows"], r["pass_index"],
                                             r["final"]):
            ops += sala_work.pass_ops(np.arange(end - q, end), final, pub)
            tokens += q
            resumed += index > 0
    device_ns = sum(e[2] for e, _ in whole)
    least = ops / ctx["peaks"]["bf16_flops_per_s"]
    ctx["log"](
        f"prefill passes: {len(whole)} prefill programs paired with "
        f"records, {tokens} real tokens, {resumed} resumed rows; least "
        f"{least * 1e3:.3f} ms at the matmul peak, device "
        f"{device_ns / 1e6:.3f} ms")
    return 100.0 * least / (device_ns / 1e9)
