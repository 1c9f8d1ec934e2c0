"""The head of a greedy denoising pass ALONE on the chip: what a pass pays
to keep an argmax and a confidence of its logits, in three forms.

    python benchmarks/head_argmax_probe.py [--rows 256] [--hidden 2048]
        [--vocab 151936] [--seed N] [--loops 60]

`rows` final-normed hidden states times `lm_head` [hidden, vocab] (the
defaults are `sdar-30b-a3b-chat`'s block step: 64 slots x 4 positions),
bf16, under ONE `cond` on a temperature operand that is all zeros, as the
block program has it (a `cond`'s operand is materialised: without the
`cond` XLA folds the three reductions into the product's own fusion and
the plain form reads 1.09 ms where a block program's pass pays 1.58):

- `logits-f32`: the form until PR 54. The product, widened to float32 in
  front of the `cond`; the greedy branch reduces it three times
  (`models/sdar.py:_sample`). XLA writes the product's float32 as it is:
  the bf16 between `lm_head` and the widening never exists.
- `logits-bf16`: the lesser cure. The product stays bf16 in front of the
  `cond`, and the greedy branch keeps (max, argmax, sum of exp) in one
  variadic reduce that reads it once. It ROUNDS the logits, which the
  form above does not: `argmax_equal` reads 0.977-0.988 for it.
- `kernel`: `ops/head_argmax.py`, which writes no logits; the branch that
  draws runs the head itself.
- `product`: the product alone with a max behind it, no `cond`: what XLA's
  own matmul takes to read the weights.

Each form runs in a jitted loop that carries its results into the next
turn's operand (a form timed by a call of its own from the host reads a
dispatch; a loop whose results are not carried is dead code to XLA).
Prints one JSON line: ms a pass and form, the weights' bytes over that time
as a share of the chip's 819 GB/s, and whether the forms agree. Needs a
TPU; nothing here is a cell's number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_GB_S = 819.0     # TPU v5e HBM (chipbench/peaks.json)


def forms(rows: int):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import sdar
    from ray_tpu.ops.head_argmax import head_argmax

    zeros = jnp.zeros((rows,), jnp.float32)
    top_k = jnp.zeros((rows,), jnp.int32)
    keys = jnp.zeros((rows, 2), jnp.uint32)

    def logits_f32(x, w, temp):
        x0, conf = sdar._sample(jnp.dot(x, w).astype(jnp.float32)[:, None],
                                temp, top_k, keys)
        return x0[:, 0], conf[:, 0]

    def one_reduce(logits):
        idx = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)

        def fold(a, b):
            (m1, i1, s1), (m2, i2, s2) = a, b
            m = jnp.maximum(m1, m2)
            at = jnp.where(m == -jnp.inf, 0.0, m)
            return (m, jnp.where((m1 > m2) | ((m1 == m2) & (i1 < i2)),
                                 i1, i2),
                    s1 * jnp.exp(m1 - at) + s2 * jnp.exp(m2 - at))

        top, arg, s = jax.lax.reduce(
            (logits.astype(jnp.float32), idx,
             jnp.ones(logits.shape, jnp.float32)),
            (jnp.float32(-jnp.inf), jnp.int32(2 ** 31 - 1), jnp.float32(0)),
            fold, (1,))
        return arg, 1.0 / s

    def logits_bf16(x, w, temp):
        logits = jnp.dot(x, w)

        def drawn(_):
            x0, conf = sdar._sample(logits.astype(jnp.float32)[:, None],
                                    temp, top_k, keys)
            return x0[:, 0], conf[:, 0]

        return jax.lax.cond(jnp.any(temp > 0), drawn,
                            lambda _: one_reduce(logits), None)

    def kernel(x, w, temp):
        def greedy(_):
            arg, top, lse = head_argmax(x, w)
            return arg, jnp.exp(top - lse)

        return jax.lax.cond(jnp.any(temp > 0),
                            lambda _: logits_f32(x, w, temp), greedy, None)

    def product(x, w, temp):
        return (jnp.zeros((rows,), jnp.int32),
                jnp.max(jnp.dot(x, w), axis=-1).astype(jnp.float32))

    return zeros, {"logits-f32": logits_f32, "logits-bf16": logits_bf16,
                   "kernel": kernel, "product": product}


def timed(fn, x, w, temp, loops: int):
    import jax
    import jax.numpy as jnp

    def loop(x, w, temp):
        def body(_, carry):
            arg, conf = fn(x + (carry[1][:, None] * 1e-12).astype(x.dtype),
                           w, temp)
            return arg, conf
        return jax.lax.fori_loop(
            0, loops, body, (jnp.zeros(x.shape[:1], jnp.int32),
                             jnp.zeros(x.shape[:1], jnp.float32)))

    run = jax.jit(loop)
    out = jax.block_until_ready(run(x, w, temp))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = jax.block_until_ready(run(x, w, temp))
        best = min(best, (time.perf_counter() - t0) / loops)
    return best, out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=151936)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--loops", type=int, default=60)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit("no TPU found: this probe times the chip")
    key = jax.random.PRNGKey(args.seed)
    x = jax.random.normal(key, (args.rows, args.hidden), jnp.bfloat16)
    w = (jax.random.normal(jax.random.fold_in(key, 1),
                           (args.hidden, args.vocab), jnp.float32)
         * args.hidden ** -0.5).astype(jnp.bfloat16)
    temp, table = forms(args.rows)
    weights_gb = args.hidden * args.vocab * 2 / 1e9
    out, want = {"device": device.device_kind, "rows": args.rows,
                 "hidden": args.hidden, "vocab": args.vocab}, None
    for name, fn in table.items():
        seconds, (arg, conf) = timed(fn, x, w, temp, args.loops)
        out[name] = {"ms": round(seconds * 1e3, 4), "weights_share_of_peak":
                     round(weights_gb / seconds / PEAK_GB_S, 3)}
        if name == "logits-f32":
            want = (arg, conf)
        elif name != "product":
            out[name]["argmax_equal"] = float((arg == want[0]).mean())
            out[name]["conf_max_diff"] = float(
                jnp.abs(conf - want[1]).max())
    print(json.dumps(out))


if __name__ == "__main__":
    main()
