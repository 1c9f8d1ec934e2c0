"""The paged-decode kernel ALONE on the chip: which side of a work item sets
its pace, the copy or the compute.

    python benchmarks/paged_decode_probe.py [--shape docbatch|chat|mixtral|all]
        [--variant whole|dma|compute|all] [--pages-per-chunk N] [--seed N]

One jitted loop over the 16 layers of a random bf16 pool at Mistral's widths
(Hq 32, Hkv 8, D 128, page 16), block tables drawn as `PageAllocator` draws
them (a prompt is a descending run of pages, decode growth interleaves the
rows), at the three serving cells' decode shapes. Three variants, built here
from the kernel's own body (`_decode_kernel`'s `stream` / `attend`; neither
`paged_attention_decode` nor `_decode_call` knows of them):

- `whole`:   the kernel as the engine runs it (`paged_attention_decode`);
- `dma`:     every page copy started and waited for, the item's compute skipped;
- `compute`: no copy started, the compute runs on the buffers as they lie.

Prints one JSON line: per shape and variant the time of one kernel call, the
GB/s of the REAL rows' KV bytes (`chipbench/kernel_work.py`'s count: tokens,
not pages) and that as a share of the chip's 819 GB/s. Needs a TPU; nothing
here is a cell's number.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HQ, HKV, D, PAGE, LAYERS = 32, 8, 128, 16, 16
PEAK_GB_S = 819.0     # TPU v5e HBM (chipbench/peaks.json)
# name -> (max_batch, block-table columns, pool pages, live rows, (lo, hi)
# tokens of a live row): the engine sizes of the three serving cells
SHAPES = {
    "docbatch": (8, 520, 1900, 7, (3000, 8000)),
    "chat": (32, 168, 2800, 3, (200, 900)),
    "mixtral": (32, 168, 2800, 15, (200, 900)),
}
VARIANTS = {"whole": {}, "dma": {"attend": False}, "compute": {"stream": False}}


def draw(shape: str, seed: int):
    """(block_tables [B, MP], lengths [B]) as a running engine holds them."""
    b, mp, num_pages, live, (lo, hi) = SHAPES[shape]
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi, size=live)
    while sum(-(-int(n) // PAGE) for n in lens) > num_pages - 1:
        lens[np.argmax(lens)] -= PAGE          # the pool is the limit
    free = list(range(1, num_pages))
    need = [-(-int(n) // PAGE) for n in lens]
    prompt = [int(n * rng.uniform(0.75, 0.98)) for n in need]
    rows = [[free.pop() for _ in range(p)] for p in prompt]
    while any(len(r) < n for r, n in zip(rows, need)):
        for r, n in zip(rows, need):           # decode: a page a row in turn
            if len(r) < n:
                r.append(free.pop())
    slots = rng.permutation(b)[:live]
    bt = np.zeros((b, mp), np.int32)
    lengths = np.zeros((b,), np.int32)
    for s, r, n in zip(slots, rows, lens):
        bt[s, :len(r)] = r
        lengths[s] = n
    return bt, lengths


def kv_bytes(lengths) -> float:
    """`chipbench.kernel_work.paged_decode`'s bytes, summed over the rows."""
    return float(sum(2 * D * (2 * int(n) * HKV + 2 * HQ)
                     for n in lengths if n))


def build(variant: str, chunk):
    """fn(q, pool, block_tables, lengths, layer) for one variant."""
    from ray_tpu.ops import paged_attention as pa

    if variant == "whole":
        return functools.partial(pa.paged_attention_decode,
                                 pages_per_chunk=chunk, interpret=False)
    kernel = functools.partial(pa._decode_kernel, **VARIANTS[variant])

    def fn(q, pool, bt, lengths, layer):
        c = min(chunk or pa.default_pages_per_chunk(pool), bt.shape[1])
        return pa._decode_pallas(kernel, q, pool, bt, lengths, layer,
                                 scale=D ** -0.5, chunk=c, interpret=False)
    return fn


def time_variant(fn, q, pool, bt, lengths, reps: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loop(q, pool, bt, lengths):
        def body(i, acc):
            out = fn(q, pool, bt, lengths, layer=i % LAYERS)
            return acc + out.astype(jnp.float32)
        return jax.lax.fori_loop(0, reps * LAYERS, body,
                                 jnp.zeros(q.shape, jnp.float32))

    jax.block_until_ready(loop(q, pool, bt, lengths))     # compile + warm
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(q, pool, bt, lengths))
        times.append((time.perf_counter() - t0) / (reps * LAYERS))
    return statistics.median(times), min(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="all", choices=[*SHAPES, "all"])
    ap.add_argument("--variant", default="all", choices=[*VARIANTS, "all"])
    ap.add_argument("--pages-per-chunk", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("paged_decode_probe: no TPU found")
    out = {"device": dev.device_kind, "pages_per_chunk": args.pages_per_chunk,
           "seed": args.seed, "results": []}
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    variants = list(VARIANTS) if args.variant == "all" else [args.variant]
    for shape in shapes:
        b, mp, num_pages = SHAPES[shape][:3]
        bt, lengths = draw(shape, args.seed)
        key = jax.random.PRNGKey(args.seed)
        pool = jax.random.normal(
            key, (LAYERS, num_pages, HKV, PAGE, 2 * D), jnp.bfloat16)
        q = jax.random.normal(key, (b, HQ, D), jnp.bfloat16)
        nbytes = kv_bytes(lengths)
        # at least 50 ms a timed call even where the copies run at the
        # chip's bandwidth: the host's part of a call is then under 1%
        reps = max(1, int(0.05 / (LAYERS * nbytes / (PEAK_GB_S * 1e9))))
        for variant in variants:
            med, best = time_variant(build(variant, args.pages_per_chunk),
                                     q, pool, jnp.asarray(bt),
                                     jnp.asarray(lengths), reps)
            gb_s = nbytes / med / 1e9
            out["results"].append({
                "shape": shape, "variant": variant,
                "live_rows": int((lengths > 0).sum()),
                "ctx_tokens": int(lengths.sum()),
                "call_us": round(med * 1e6, 2),
                "call_us_best": round(best * 1e6, 2),
                "kv_gb_s": round(gb_s, 1),
                "pct_of_peak": round(100 * gb_s / PEAK_GB_S, 2)})
        del pool
    print(json.dumps(out))


if __name__ == "__main__":
    main()
