"""The flash kernels' share of their roofline in a training step: the least
time the chip could take for one step's forward + backward attention (the
larger of operations over peak FLOP/s and bytes over peak bytes/s, from the
cell's static shapes by chipbench/flops.py) over the kernels' device time
per step in the trace. Says which bound it is on an earlier line."""

import re

from chipbench import flops, tracered


def read(ctx, pattern: str, step_pattern: str):
    red, work = ctx["trace"], ctx["work"]
    if red is None or work.get("kind") != "train" or not ctx["peaks"]:
        return None
    rx = re.compile(pattern)
    secs = sum(s for name, s in red.ops_by_name_s.items() if rx.search(name))
    steps = sum(len(tracered.durations_matching(
        tracered.clip(ev, red.trace.window), step_pattern))
        for ev in red.trace.modules.values())
    if secs <= 0 or steps <= 0:
        return None
    pub = work["published"]
    hq, hkv = pub["num_attention_heads"], pub["num_key_value_heads"]
    d = pub.get("head_dim") or pub["hidden_size"] // hq
    shape = dict(b=work["batch"], sq=work["seq"], sk=work["seq"], hq=hq,
                 hkv=hkv, d=d, causal=True)
    fwd, bwd = flops.flash_fwd(**shape), flops.flash_bwd(**shape)
    layers = pub["num_hidden_layers"]
    need = {k: layers * (fwd[k] + bwd[k]) for k in ("ops", "bytes")}
    roof = flops.roofline_seconds(need, ctx["peaks"])
    per_step = secs / steps
    ctx["log"](f"flash fwd+bwd: {per_step*1e3:.3f} ms a step over {steps} "
               f"steps; least {roof['seconds']*1e3:.3f} ms, "
               f"{roof['bound']}-bound (ops {roof['t_ops']*1e3:.3f} ms, "
               f"bytes {roof['t_bytes']*1e3:.3f} ms)")
    return 100.0 * roof["seconds"] / per_step
