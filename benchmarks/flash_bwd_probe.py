"""The flash kernels ALONE on the chip at a trainer's shape: what the forward
and the backward each take, and how far the backward's gradients lie from
the float32 reference's.

    python benchmarks/flash_bwd_probe.py [--shape pretrain|llama1b|d128-4k|all]
        [--packed] [--blocks QxK,...] [--iters N] [--seed N]

One jitted call each of `_fwd` and `_bwd` (the backward's whole device work:
`delta`, the kernel, nothing of the forward) on random bf16 operands,
`[B, H, S, D]` as the kernels take them, timed over `--iters` calls; beside
each the share of the bf16 peak on the REAL causal pairs, counted as
`chipbench/flops.py` counts them (forward two products a pair, backward
four: the score tile's recomputation is not counted). `--packed` gives every
row four segments (the masked path on every tile), `--blocks` tries other
tile edges than the file's rule gives. Prints one JSON line a shape and
pair of edges. Needs a TPU; nothing here is a cell's number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_FLOPS = 197e12     # TPU v5e bf16 (chipbench/peaks.json)
# name -> (B, S, Hq, Hkv, D): the pretrain cell's step, chip_smoke's
# trainer, tests/test_chip_compile.py's longest backward
SHAPES = {
    "pretrain": (4, 2048, 32, 8, 128),
    "llama1b": (3, 2048, 32, 8, 64),
    "d128-4k": (1, 4096, 32, 8, 128),
}


def probe(name: str, packed: bool, iters: int, seed: int,
          blocks: tuple | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops.attention import reference_attention

    b, s, hq, hkv, d = SHAPES[name]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, do = (jax.random.normal(key, (b, hq, s, d), jnp.bfloat16)
             for key in ks[:2])
    k, v = (jax.random.normal(key, (b, hkv, s, d), jnp.bfloat16)
            for key in ks[2:])
    seg = kv_seg = None
    if packed:
        seg = jnp.tile(jnp.repeat(jnp.arange(4, dtype=jnp.int32), s // 4),
                       (b, 1))
        kv_seg = seg[..., None]
    scale = d ** -0.5
    bq, bk = blocks or fa._pick_blocks(s, s, fa.BLOCK, fa.BLOCK)
    static = (True, scale, bq, bk, False, s, s)
    fwd = jax.jit(lambda q, k, v: fa._fwd(q, k, v, kv_seg, kv_seg, *static))
    bwd = jax.jit(lambda q, k, v, o, lse, do: fa._bwd(
        q, k, v, kv_seg, kv_seg, o, lse, do, *static))

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    o, lse = fwd(q, k, v)
    t_fwd, t_bwd = timed(fwd, q, k, v), timed(bwd, q, k, v, o, lse, do)

    # the gradients against the float32 reference's on the first batch row
    # (float32 operands, so that its gradients are not rounded to bf16: two
    # roundings of nearly one value mostly agree, and hide what differs)
    def ref_loss(q, k, v):
        t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
        out = reference_attention(
            t(q), t(k), t(v), causal=True,
            segment_ids=None if seg is None else seg[:1])
        return jnp.sum(out * t(do[:1].astype(jnp.float32)))

    want = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(
        *(x[:1].astype(jnp.float32) for x in (q, k, v)))
    got = bwd(q, k, v, o, lse, do)
    err = {n: float(jnp.linalg.norm(g[:1].astype(jnp.float32) - w)
                    / jnp.linalg.norm(w))
           for n, g, w in zip(("dq", "dk", "dv"), got, want)}
    pairs = s * (s + 1) // 2 * hq * b
    return {"shape": name, "packed": packed, "blocks": [bq, bk],
            "fwd_ms": t_fwd * 1e3, "bwd_ms": t_bwd * 1e3,
            "fwd_peak_pct": 100 * 2 * 2 * d * pairs / PEAK_FLOPS / t_fwd,
            "bwd_peak_pct": 100 * 4 * 2 * d * pairs / PEAK_FLOPS / t_bwd,
            "rel_err": err}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="pretrain",
                    choices=[*SHAPES, "all"])
    ap.add_argument("--packed", action="store_true")
    ap.add_argument("--blocks", default="",
                    help="tile edges to try, both kernels', in place of "
                         "the file's rule: 512x512,256x512 (query x key); "
                         "start a process a pair when the times matter")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("no TPU found: the probe times compiled kernels",
              file=sys.stderr)
        return 1
    tries = [tuple(int(e) for e in t.split("x"))
             for t in args.blocks.split(",") if t] or [None]
    for name in (SHAPES if args.shape == "all" else [args.shape]):
        for blocks in tries:
            print(json.dumps(probe(name, args.packed, args.iters, args.seed,
                                   blocks)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
