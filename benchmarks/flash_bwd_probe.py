"""The flash kernels ALONE on the chip at a trainer's shape: what the forward
and the backward each take, and how far the backward's gradients lie from
the float32 reference's.

    python benchmarks/flash_bwd_probe.py [--shape pretrain|llama1b|d128-4k|all]
        [--packed] [--blocks QxK,...] [--iters N] [--seed N]
        [--layout bshd|bhsd]
    python benchmarks/flash_bwd_probe.py --fwd-shapes [--blocks QxK,...]
        [--only NAME,...] [--all-edge] [--no-fold] [--layout bshd|bhsd]

One jitted call each of `_fwd` and `_bwd` (the backward's whole device work:
`delta`, the kernel, nothing of the forward) on random bf16 operands as the
kernels take them, timed over `--iters` calls: `--layout bshd` (the
default) `[B, S, H, D]`, read as rows of `[B, S, H x D]` where a head is
whole lane tiles and `[B, H, S, D]` by the module's own rule where not;
`--layout bhsd` `[B, H, S, D]` at every width (PR 60: the kernels in the
layout they had, for a before and after in one tree; patches the module's
rule for the probe's process only); beside
each the share of the bf16 peak on the REAL causal pairs, counted as
`chipbench/flops.py` counts them (forward two products a pair, backward
four: the score tile's recomputation is not counted). `--packed` gives every
row four segments (the masked path on every tile), `--blocks` tries other
tile edges than the file's rule gives. Prints one JSON line a shape and
pair of edges. `--fwd-shapes` times `_fwd` ALONE at `FWD_SHAPES` (the
pretrain step's call, a dense `[1 x 4096]` pass, a latent family's pass over
a chunk of its context: keys of 192, values of 128), causal and not, with and
without a row's lengths: the forward's before and after in one command;
and a chat bucket's query block of 128 and of 256 rows over a context of
2560 columns, where a grid step folds a kv head's query heads along the
lanes. `--all-edge` sends every tile through the masked body (what the
key loop's split by class is worth), `--no-fold` gives a grid step one
query head whatever the block (what the fold is worth): both patch the
module for the probe's process only.
Needs a TPU; nothing here is a cell's number.
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
# name -> (B, Sq, Sk, Hq, Hkv, D, Dv): the forward alone (`--fwd-shapes`), at
# the pretrain cell's step, a dense family's [1 x 4096] pass and a latent
# family's pass over one materialised chunk of its context (models/kimi.py)
FWD_SHAPES = {
    "pretrain": (4, 2048, 2048, 32, 8, 128, 128),
    "d128-4k": (1, 4096, 4096, 32, 8, 128, 128),
    "mla-4k": (1, 4096, 4096, 64, 64, 192, 128),
    "bucket128-ctx": (1, 128, 2560, 32, 8, 128, 128),
    "bucket256-ctx": (1, 256, 2560, 32, 8, 128, 128),
    # a sliding layer of models/laguna.py: an eighth name, the window
    "laguna-w512": (1, 4096, 4096, 64, 8, 128, 128, 512),
}


def _operands(fa, shapes, keys):
    """Random bf16 operands `[B, H, S, D]` for `shapes` [(B, H, S, D), ...],
    the two functions that give the kernels their layout of them and take
    an output (rows `[B, S, H x D]` or `[B, S, H, D]` or `[B, H, S, D]`)
    back to `[B, H, S, D]`, and the layout's name."""
    import jax
    import jax.numpy as jnp

    xs = [jax.random.normal(key, shape, jnp.bfloat16)
          for key, shape in zip(keys, shapes)]
    if not fa._heads_on_lanes(shapes[0][3], shapes[-1][3]):
        return xs, (lambda x: x), (lambda x, h: x), "bhsd"

    def back(x, h):
        return x.reshape(*x.shape[:2], h, -1).transpose(0, 2, 1, 3)

    return xs, (lambda x: x.transpose(0, 2, 1, 3)), back, "bshd"


def probe(name: str, packed: bool, iters: int, seed: int,
          blocks: tuple | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops.attention import reference_attention

    b, s, hq, hkv, d = SHAPES[name]
    (q, do, k, v), lay, back, layout = _operands(
        fa, [(b, hq, s, d)] * 2 + [(b, hkv, s, d)] * 2,
        jax.random.split(jax.random.PRNGKey(seed), 4))
    seg = kv_seg = None
    if packed:
        seg = jnp.tile(jnp.repeat(jnp.arange(4, dtype=jnp.int32), s // 4),
                       (b, 1))
        kv_seg = seg[..., None]     # (the kernels' forms: [B, S], [B, S, 1])
    scale = d ** -0.5
    bq, bk = blocks or fa._pick_blocks(s, s, fa.BLOCK, fa.BLOCK)
    static = (True, scale, bq, bk, False, s, s)
    fwd = jax.jit(lambda q, k, v: fa._fwd(q, k, v, seg, kv_seg, *static))
    bwd = jax.jit(lambda q, k, v, o, lse, do: fa._bwd(
        q, k, v, seg, kv_seg, o, lse, do, *static))

    args = tuple(lay(x) for x in (q, k, v))
    o, lse = fwd(*args)
    t_fwd = _timed(fwd, iters, *args)
    t_bwd = _timed(bwd, iters, *args, o, lse, lay(do))

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
    got = (back(g, h) for g, h in zip(bwd(*args, o, lse, lay(do)),
                                      (hq, hkv, hkv)))
    err = {n: float(jnp.linalg.norm(g[:1].astype(jnp.float32) - w)
                    / jnp.linalg.norm(w))
           for n, g, w in zip(("dq", "dk", "dv"), got, want)}
    pairs = s * (s + 1) // 2 * hq * b
    return {"shape": name, "packed": packed, "blocks": [bq, bk],
            "layout": layout,
            "fwd_ms": t_fwd * 1e3, "bwd_ms": t_bwd * 1e3,
            "fwd_peak_pct": 100 * 2 * 2 * d * pairs / PEAK_FLOPS / t_fwd,
            "bwd_peak_pct": 100 * 4 * 2 * d * pairs / PEAK_FLOPS / t_bwd,
            "rel_err": err}


def _timed(fn, iters, *args):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def probe_fwd(name: str, causal: bool, lens: bool, iters: int, seed: int,
              blocks: tuple | None = None) -> dict:
    """`_fwd` alone at one of `FWD_SHAPES`: a call's time and its share of
    the bf16 peak on the (query, key) pairs a row really has. `lens`: every
    row 300 queries and 300 keys short of the operands' (a part-padded last
    query block, the keys' end inside a tile; half the queries where there
    are fewer than 600), as data."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import flash_attention as fa

    b, sq, sk, hq, hkv, d, dv, *window = FWD_SHAPES[name]
    window = window[0] if window else None
    (q, k, v), lay, back, layout = _operands(
        fa, [(b, hq, sq, d), (b, hkv, sk, d), (b, hkv, sk, dv)],
        jax.random.split(jax.random.PRNGKey(seed), 3))
    bq, bk = blocks or fa._pick_blocks(sq, sk, fa.BLOCK, fa.BLOCK)
    short = min(300, sq // 2) if lens else 0
    nq, nk = sq - short, sk - short
    row_lens = jnp.tile(jnp.asarray([[nq], [nk]], jnp.int32), (1, b))
    fwd = jax.jit(lambda q, k, v, n: fa._fwd(
        q, k, v, None, None, causal, d ** -0.5, bq, bk, False, sq, sk,
        n if lens else None, 0, window))
    args = tuple(lay(x) for x in (q, k, v))
    t = _timed(fwd, iters, *args, row_lens)
    # the first kv head's query group against the float32 reference over
    # the row's real queries and keys
    from ray_tpu.ops.attention import reference_attention

    rep = hq // hkv
    tr = lambda x, n: x[:1, :, :n].astype(  # noqa: E731
        jnp.float32).transpose(0, 2, 1, 3)
    err = None
    if window is None:
        want = reference_attention(tr(q[:, :rep], nq), tr(k[:, :1], nk),
                                   tr(v[:, :1], nk), causal=causal)
        got = tr(back(fwd(*args, row_lens)[0], hq)[:, :rep], nq)
        err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    # query i of nq sees keys [0, i + sk - sq] of nk if causal, else all
    # nk; under a window the last `window` of them (the context part of a
    # pass that resumes: the `window` keys before the row's end + i)
    off = sk - sq
    seen = [min(nk, i + off + 1) if causal else nk for i in range(nq)]
    if window is not None:
        seen = [min(n, window) for n in seen]
    pairs = sum(seen) * hq * b
    return {"fwd_shape": name, "causal": causal, "lens": lens,
            "layout": layout,
            "blocks": [bq, bk], "fwd_ms": t * 1e3,
            "fwd_peak_pct": 100 * 2 * (d + dv) * pairs / PEAK_FLOPS / t,
            "o_rel_err": err}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="pretrain",
                    choices=[*SHAPES, "all"])
    ap.add_argument("--packed", action="store_true")
    ap.add_argument("--blocks", default="",
                    help="tile edges to try, both kernels', in place of "
                         "the file's rule: 512x512,256x512 (query x key); "
                         "start a process a pair when the times matter")
    ap.add_argument("--fwd-shapes", action="store_true",
                    help="the forward alone at FWD_SHAPES, causal and not, "
                         "with and without lengths, a line each")
    ap.add_argument("--only", default="",
                    help="with --fwd-shapes: these of FWD_SHAPES")
    ap.add_argument("--all-edge", action="store_true",
                    help="every tile takes the forward's masked body")
    ap.add_argument("--no-fold", action="store_true",
                    help="a grid step of the forward holds one query head")
    ap.add_argument("--layout", default="bshd", choices=["bshd", "bhsd"],
                    help="bhsd: [B, H, S, D] operands at every width, the "
                         "kernels' layout before PR 60")
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
    if args.all_edge or args.no_fold or args.layout == "bhsd":
        import jax.numpy as jnp

        from ray_tpu.ops import flash_attention as fa

        trips = fa._fwd_trips
        if args.layout == "bhsd":
            fa._heads_on_lanes = lambda d, dv: False
        if args.all_edge:
            fa._fwd_trips = lambda *a, **kw: (jnp.int32(0),
                                              trips(*a, **kw)[1])
        if args.no_fold:
            fa._fold = lambda n_rep, bq: 1
    if args.fwd_shapes:
        for name in (args.only.split(",") if args.only else FWD_SHAPES):
            for causal in (True, False):
                for lens in (False, True):
                    for blocks in tries:
                        print(json.dumps(probe_fwd(
                            name, causal, lens, args.iters, args.seed,
                            blocks)), flush=True)
        return 0
    for name in (SHAPES if args.shape == "all" else [args.shape]):
        for blocks in tries:
            print(json.dumps(probe(name, args.packed, args.iters, args.seed,
                                   blocks)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
