"""The expert layer's grouped matmul ALONE on the chip: what a (tile, expert)
visit costs at each tile, so that the tile rule of `ops/grouped_matmul.py`
(`row_tile`, `tile_for`) rests on times and not on arithmetic.

    python benchmarks/moe_gmm_probe.py [--rows 512,1024,2048,4096]
        [--real 0.69,1.0] [--tiles 128x1024x1024,256x1024x1024,...|rule]
        [--product gate_up|down|all] [--aligned] [--block ROWS] [--seed N]
        [--experts E] [--hidden H] [--width F]

The two products of an expert layer (gate-and-up `K` h / `N` 2f, down `K` f
/ `N` h; Mixtral's 4096 / 14336 on 8 experts unless `--hidden`, `--width`
and `--experts` say another model's), the experts read in place from a
`[3 x E, K, N]` bf16 stack with `layer` as the engine's programs read them,
`M` sorted assignments of which a
share is real (the rest are in no group, as a length bucket's padding is) in
near-uniform groups (a multinomial draw: what seeded random weights route).
Each tile is the megablox kernel called as `_moe_gmm` calls it
(`grouped_matmul._megablox`), in a jitted loop over the layers.

One JSON line a product, `M`, real share and tile: seconds a call, the
visits (`tile_visits`), the rows multiplied (visits x tm), the kernel's VMEM
at that tile, the share of the K x N it multiplies that the weights have
(`tile_fit`: a tile that does not divide is timed too, as the kernel pads
it; `rule` among the tiles is `_tile`'s for the shape), the time its
visits' products and weight bytes would take at the chip's peaks, and the
share of the bound the benchmark holds it to
(`chipbench/moe_work.py`: the real assignments' operations, the touched
experts' weights once; the two products' bounds add up to `gmm_ops` /
`gmm_bytes`). `--aligned` adds the layout step 3 of PR 34's issue weighed:
every expert's rows start on a tile boundary (`M + E x tm` rows, the padding
inside the groups), which takes the boundary visits away; with `--block` a
call is one block of that many rows out of the aligned layout (the middle
one), as `MoEMLP._dropless` cuts a long pass. The last line names
the tile and the layout the rule (`row_tile`, `tile_for`) gives each shape.

    python benchmarks/moe_gmm_probe.py --layer [--experts E --topk K
        --hidden H --width F --routed R] [--tokens 4096] [--real 0.92]
        [--blocks 0,4096,16384] [--tree DIR]

`--layer` times one WHOLE dropless expert layer (`models/llama.py: MoEMLP`,
router to fold, the experts read in place from a `[3, E, ...]` stack as a
serving program reads them) at a configuration's widths over a pass of
`--tokens` tokens of which `--real` (its first) are no padding (Mellum2:
`--experts 64 --topk 8 --hidden 2304 --width 896`; Kimi's share: `--experts
12 --topk 8 --routed 384 --hidden 7168 --width 2048`), one JSON line a block
size (`--blocks`: rows ONE grouped-matmul call of a long pass gets; 0: the
tree's own `_MOE_ROWS`), with the blocks that makes. Then the layer BY
PARTS at the tree's layout, each part a jitted loop that carries its
outputs (a part called from the host times the dispatch, a loop whose
outputs nobody carries is dead code): the layout's integer arrays
(`dropless_layout`), the row gather over all rows at once, the two products
with `silu * up` between them in ONE call over all rows, the pass cut into
blocks as the layer cuts it (gathers, products and the stack of blocks: less
the two lines above it is what cutting costs), the un-sort gather and the
fold. `--tree DIR` imports `ray_tpu` from another checkout (a parent
unpacked under `.scratch/`), for the whole layer only where that tree has
no `dropless_layout`: a before and after in one call.
Needs a TPU; nothing here is a cell's number.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LAYERS = 3
PRODUCTS = ("gate_up", "down")
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9     # TPU v5e (chipbench/peaks.json)
TILES = ("128x1024x1024", "256x1024x1024", "512x1024x1024",
         "128x2048x1024", "256x2048x1024", "128x1024x2048", "256x1024x2048",
         "256x512x2048", "128x512x4096", "256x4096x512", "256x2048x512")


def draw_groups(m: int, real: float, seed: int, e: int) -> np.ndarray:
    """[e] sizes of near-uniform groups holding `real` x m assignments."""
    rng = np.random.default_rng(seed)
    return rng.multinomial(int(round(m * real)), [1.0 / e] * e).astype(
        np.int32)


def block_of(real: np.ndarray, tm: int, block: int) -> tuple:
    """(real sizes, padded sizes) of the middle `block` rows of the layout
    that starts every group of `real` on a tile boundary: what one call of
    a long pass gets (`MoEMLP._dropless`: `experts_on`)."""
    padded = -(-real // tm) * tm
    ends = np.cumsum(padded)
    lo = int(ends[-1]) // block // 2 * block
    starts = np.clip(ends - padded, lo, lo + block)

    def rows(sizes):
        return (np.clip(ends - padded + sizes, lo, lo + block)
                - starts).astype(np.int32)
    return rows(real), rows(padded)


def bound_s(sizes: np.ndarray, k: int, n: int) -> tuple:
    """(least seconds, which side binds) for the REAL assignments of one
    product: `chipbench.moe_work`'s count, cut to this product."""
    real, touched = int(sizes.sum()), int((sizes > 0).sum())
    ops = 2.0 * k * n * real
    nbytes = 2.0 * (k * n * touched + (k + n) * real)
    by_ops, by_bytes = ops / PEAK_FLOPS, nbytes / PEAK_BYTES
    return max(by_ops, by_bytes), "ops" if by_ops >= by_bytes else "bytes"


def time_tile(stack, m: int, sizes_list, tile, reps: int):
    """Median seconds of one kernel call for each group-size vector."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import grouped_matmul as gm

    k = stack.shape[2]

    @jax.jit
    def loop(lhs, stack, sizes):
        def body(i, acc):
            rhs, groups = gm.stacked_groups(stack, sizes, i % LAYERS)
            out = gm._megablox(lhs, rhs, groups, tile, False)
            return acc + out[:8, :128].astype(jnp.float32)
        return jax.lax.fori_loop(0, reps * LAYERS, body,
                                 jnp.zeros((8, 128), jnp.float32))

    lhs = jax.random.normal(jax.random.PRNGKey(m), (m, k), jnp.bfloat16)
    out = []
    for sizes in sizes_list:
        out.append(timed(loop, lhs, stack, jnp.asarray(sizes))
                   / (reps * LAYERS))
    return out


def timed(fn, *args, reps: int = 5):
    """Median seconds of `fn(*args)` after one call that compiles it."""
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def timed_part(fn, args, vary: int, inner: int = 16):
    """Median seconds of ONE `fn(*args)` among `inner` in a jitted loop (a
    part of a layer is tens of microseconds: a call of its own from the
    host would time the dispatch). The loop CARRIES the part's outputs, so
    every iteration computes all of them, and the next iteration's
    `args[vary]` (a small array) depends on them through a zero the
    compiler cannot know, so nothing is hoisted out of the loop."""
    import jax
    import jax.numpy as jnp

    def zero_of(out):
        zero = jnp.int32(0)
        for leaf in jax.tree.leaves(out):
            corner = leaf.reshape(-1)[0]
            zero += (jnp.minimum(corner, 0)               # none negative
                     if jnp.issubdtype(leaf.dtype, jnp.integer)
                     else corner != corner).astype(jnp.int32)     # no NaN
        return zero

    @jax.jit
    def loop(*args):
        def body(_, out):
            moved = list(args)
            moved[vary] = moved[vary] + zero_of(out).astype(
                moved[vary].dtype)
            return fn(*moved)
        return jax.lax.fori_loop(0, inner, body, jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(fn, *args)))

    return timed(loop, *args) / inner


def layer_probe(args, dev):
    """`--layer`: see the module docstring."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    e, k, h, f, tokens, layers = (args.experts, args.topk, args.hidden,
                                  args.width, args.tokens, args.layers)
    cfg = llama.get_config(
        "tiny-moe", dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        hidden_size=h, num_experts=e, num_experts_per_tok=k,
        moe_intermediate_size=f, n_routed_experts=args.routed or None,
        expert_first=0)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)

    def stack(key, shape):
        # an expert at a time: a whole stack's normal draw would hold its
        # float32 form too
        return jax.lax.map(
            lambda kk: jax.random.normal(kk, shape, jnp.bfloat16) * 0.02,
            jax.random.split(key, layers * e)).reshape((layers, e) + shape)

    w_gu, w_dn = stack(keys[0], (h, 2 * f)), stack(keys[1], (f, h))
    router = jax.random.normal(keys[2], (h, cfg.routed_experts), jnp.float32)
    x = jax.random.normal(keys[3], (1, tokens, h), jnp.bfloat16)
    real = float(args.real.split(",")[0])      # the first, where several
    mask = (jnp.arange(tokens) < int(round(tokens * real)))[None]
    layer = llama.MoEMLP(cfg)
    params = {"router": router, "experts_gate_up": w_gu[0],
              "experts_down": w_dn[0]}

    def whole(x, w_gu, w_dn):
        # the residual stream through 2 x `--layers` layers: every token's
        # output is the next layer's input, so nothing of a layer is dead
        return jax.lax.fori_loop(
            0, 2 * layers, lambda i, x: x + layer.apply(
                {"params": params}, x, token_mask=mask,
                stacked=(w_gu, w_dn, i % layers)), x)

    row_bytes = 2 * f * 2
    own = llama._MOE_ROWS
    base = {"device": dev.device_kind, "experts": e, "topk": k, "hidden": h,
            "width": f, "routed": cfg.routed_experts, "tokens": tokens,
            "real": real, "tree": args.tree or "."}
    for block in (int(b) for b in args.blocks.split(",")):
        llama._MOE_ROWS = block or own
        tm, aligned, rows, per_call = llama.moe_row_layout(tokens, cfg)
        # a wrapper of its own a block: jit keys its traces by the function
        secs = timed(jax.jit(lambda *a: whole(*a)), x, w_gu,
                     w_dn) / (2 * layers)
        print(json.dumps({
            **base, "part": "whole layer", "tm": tm, "aligned": aligned,
            "rows": rows, "block": per_call, "blocks": rows // per_call,
            "call_mb": round(per_call * row_bytes / 2 ** 20, 1),
            "ms": round(1e3 * secs, 4)}), flush=True)
    if not hasattr(llama, "dropless_layout"):
        return
    llama._MOE_ROWS = own
    tm, aligned, rows, block = llama.moe_row_layout(tokens, cfg)
    m = tokens * k
    xt = x[0]
    # the router's own choice, as the layer makes it
    _, idx = jax.lax.top_k(jax.nn.softmax(
        xt.astype(jnp.float32) @ router, axis=-1), k)
    if cfg.routed_experts != e:
        idx = jnp.where(idx < e, idx, e)
    expert = jnp.where(jnp.repeat(mask[0], k), idx.reshape(m), e)
    layout = jax.jit(lambda ex: llama.dropless_layout(ex, e, tm, aligned,
                                                      rows))
    counts, ends, at, row_of = layout(expert)
    sizes = jnp.diff(ends, prepend=0)
    gather = jax.jit(lambda xt, at: xt[at // k])
    rows_in = gather(xt, at)

    def products(lhs, w_gu, w_dn, sizes):
        gate_p, up_p = jnp.split(
            grouped_matmul(lhs, w_gu, sizes, jnp.int32(1), tm), 2, axis=-1)
        return grouped_matmul(nn.silu(gate_p) * up_p, w_dn, sizes,
                              jnp.int32(1), tm)

    def blocks(xt, at, ends, w_gu, w_dn):
        # as `MoEMLP._dropless` cuts a pass
        def experts_on(lo, n):
            here = jnp.diff(jnp.clip(ends, lo, lo + n), prepend=lo)
            return products(xt[jax.lax.dynamic_slice(at, (lo,), (n,)) // k],
                            w_gu, w_dn, here)
        if block == rows:
            return experts_on(0, rows)
        return jax.lax.map(
            lambda lo: jax.lax.cond(
                lo < ends[-1], lambda: experts_on(lo, block),
                lambda: jnp.zeros((block, h), jnp.bfloat16)),
            jnp.arange(0, rows, block)).reshape(rows, h)

    y = jax.jit(blocks)(xt, at, ends, w_gu, w_dn)
    unsort = jax.jit(lambda y, row_of: y[row_of])
    gate = jnp.full((tokens, k), 1.0 / k, jnp.float32)
    fold = jax.jit(lambda y, gate: jnp.einsum(
        "tkh,tk->th", y.reshape(tokens, k, h).astype(jnp.float32),
        gate).astype(jnp.bfloat16))
    real_rows = int(ends[-1])
    # (part, function, operands, the small operand that varies, bytes moved)
    for part, fn, operands, vary, nbytes in (
            ("layout arrays", layout, (expert,), 0, 0),
            ("row gather, all rows", gather, (xt, at), 1, 2 * rows * h * 2),
            ("products, one call", products, (rows_in, w_gu, w_dn, sizes), 3,
             0),
            ("the pass in blocks", blocks, (xt, at, ends, w_gu, w_dn), 2, 0),
            ("un-sort gather", unsort, (y, row_of), 1, 2 * m * h * 2),
            ("fold", fold, (unsort(y, row_of), gate), 1, 0)):
        secs = timed_part(fn, operands, vary)
        print(json.dumps({
            **base, "part": part, "tm": tm, "aligned": aligned, "rows": rows,
            "block": block, "real_rows": real_rows,
            "ms": round(1e3 * secs, 4),
            **({"gb_s": round(nbytes / secs / 1e9, 1)} if nbytes else {})}),
            flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="512,1024,2048,4096")
    ap.add_argument("--real", default="0.69,1.0")
    ap.add_argument("--tiles", default=",".join(TILES))
    ap.add_argument("--product", default="all", choices=[*PRODUCTS, "all"])
    ap.add_argument("--aligned", action="store_true")
    ap.add_argument("--block", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=4096)
    ap.add_argument("--width", type=int, default=14336)
    ap.add_argument("--layer", action="store_true")
    ap.add_argument("--topk", type=int, default=2)
    ap.add_argument("--routed", type=int, default=0)
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--blocks", default="0")
    ap.add_argument("--tree", default="")
    ap.add_argument("--layers", type=int, default=LAYERS)
    args = ap.parse_args()
    if args.tree:
        sys.path.insert(0, os.path.join(ROOT, args.tree))

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import grouped_matmul as gm

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("moe_gmm_probe: no TPU found")
    if args.layer:
        return layer_probe(args, dev)
    e, h, f = args.experts, args.hidden, args.width
    widths = {"gate_up": (h, 2 * f), "down": (f, h)}
    rows = [int(r) for r in args.rows.split(",")]
    reals = [float(r) for r in args.real.split(",")]
    products = list(PRODUCTS) if args.product == "all" else [args.product]
    for product in products:
        k, n = widths[product]
        # one expert at a time: a [24, K, N] normal draw at once would hold
        # its float32 form too
        stack = jax.lax.map(
            lambda key: jax.random.normal(key, (k, n), jnp.bfloat16),
            jax.random.split(jax.random.PRNGKey(args.seed), LAYERS * e)
        ).reshape(LAYERS, e, k, n)
        for m in rows:
            groups = [draw_groups(m, real, args.seed + m, e)
                      for real in reals]
            tiles = dict.fromkeys(
                gm.tile_for(m, e, k, n) if t == "rule"
                else tuple(int(x) for x in t.split("x"))
                for t in args.tiles.split(","))
            for tile in tiles:
                tm = tile[0]
                if m % tm or gm.tile_vmem_bytes(tile) > gm.VMEM_BYTES:
                    continue
                layouts = [("sorted", m, groups, groups)]
                if args.aligned and args.block:
                    cut = [block_of(g, tm, args.block) for g in groups]
                    layouts.append((f"block{args.block}", args.block,
                                    [c[0] for c in cut], [c[1] for c in cut]))
                elif args.aligned:
                    # every group padded to whole tiles: the padding rows
                    # are multiplied as the group's own
                    layouts.append(("aligned", m + e * tm, groups,
                                    [-(-g // tm) * tm for g in groups]))
                for layout, m_call, real_list, sizes_list in layouts:
                    # about 50 ms a timed call at the rows' peak rate
                    reps = max(1, int(0.05 / (LAYERS * 2.0 * m_call * k * n
                                              / PEAK_FLOPS)))
                    secs = time_tile(stack, m_call, sizes_list, tile, reps)
                    for real, real_sizes, sizes, s in zip(
                            reals, real_list, sizes_list, secs):
                        visits = gm.tile_visits(sizes, tm)
                        least, side = bound_s(real_sizes, k, n)
                        fit = gm.tile_fit(k, n, tile)
                        print(json.dumps({
                            "product": product, "k": k, "n": n, "m": m,
                            "real": real, "layout": layout,
                            "tile": list(tile),
                            "tile_fit": round(fit, 4),
                            "steps_a_visit": -(-k // tile[1])
                            * -(-n // tile[2]),
                            "vmem_mib": round(
                                gm.tile_vmem_bytes(tile) / 2 ** 20, 2),
                            "seconds": s, "visits": visits,
                            "us_a_visit": round(1e6 * s / max(visits, 1), 2),
                            "rows_multiplied": visits * tm,
                            "fill": round(int(real_sizes.sum())
                                          / max(visits * tm, 1), 4),
                            "visit_ops_s": visits * tm * 2.0 * k * n / fit
                            / PEAK_FLOPS,
                            "visit_weight_bytes_s": visits * 2.0 * k * n
                            / PEAK_BYTES,
                            "bound_s": least, "bound_side": side,
                            "pct_of_bound": round(100 * least / s, 2)}),
                            flush=True)
        del stack
    print(json.dumps({
        "device": dev.device_kind, "seed": args.seed,
        "tile_for": {f"{product}:{m}": [
            *gm.tile_for(m, e, *widths[product]),
            "aligned" if gm.row_tile(m, e)[1] else "sorted"]
            for product in products for m in (64, 256, *rows)}}))


if __name__ == "__main__":
    main()
