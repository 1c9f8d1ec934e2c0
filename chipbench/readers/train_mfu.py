"""Model FLOP/s utilisation of the training window: operations the forward
and backward passes need per token (chipbench/flops.py; recompute not
counted) x tokens per second of the window, over the chip's bf16 peak."""

from chipbench import flops


def read(ctx):
    work = ctx["work"]
    if work.get("kind") != "train" or not ctx["peaks"]:
        return None
    rate = ctx["runner"].tokens_completed() / ctx["window_s"]
    ops = flops.train_ops_per_token(work["published"], work["seq"])
    chips = ctx["cell"].chips
    return 100.0 * ops * rate / (chips * ctx["peaks"]["bf16_flops_per_s"])
