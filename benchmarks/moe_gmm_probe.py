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

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

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
        sizes = jnp.asarray(sizes)
        jax.block_until_ready(loop(lhs, stack, sizes))     # compile + warm
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(loop(lhs, stack, sizes))
            times.append((time.perf_counter() - t0) / (reps * LAYERS))
        out.append(statistics.median(times))
    return out


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
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import grouped_matmul as gm

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("moe_gmm_probe: no TPU found")
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
