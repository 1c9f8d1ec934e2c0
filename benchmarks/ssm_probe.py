"""What a state-space model adds to the kernels, ALONE on the chip
(Jamba2-3B's shapes: d_inner 5120, N 16, 20 q heads on 1 kv head, D 128):

    python benchmarks/ssm_probe.py [--seed N]

- the selective-scan kernel (ops/selective_scan.py) against the chunked
  jax.numpy form at row lengths 128 / 512 / 2048, from a non-zero h_0 and
  with a padded tail, and the time of a row (eight in one program) with
  its share of 819 GB/s for the real tokens' bytes (chipbench/ssm_work.py's
  count);
- the one-token state update (`_ssm_update`) over 64 slots, all live and
  a third live: the kernel against the jax.numpy form, and the time of
  an update (26 in one program) with the live state's bytes as a share
  of 819 GB/s;
- the flash forward at 20 / 1 heads and the paged-decode kernel at q
  [B, 20, 128] over a POISONED pool of one kv head (NaN in every row at or
  past a length and in every page no live row owns), at 8 / 32 / 256 pages
  an item, against their references.

Prints one JSON line. Needs a TPU; nothing here is a cell's number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

D_INNER, N_STATE, HQ, HKV, HEAD, PAGE = 5120, 16, 20, 1, 128, 16
PEAK_GB_S = 819.0     # TPU v5e HBM (chipbench/peaks.json)


def _time(fn, *args, reps: int = 20) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def scan_rows(seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench import ssm_work
    from ray_tpu.ops.selective_scan import (selective_scan, selective_update,
                                            state_shape)

    out = {}
    d, n = D_INNER, N_STATE
    for s in (128, 512, 2048):
        ks = jax.random.split(jax.random.PRNGKey(seed + s), 8)
        real = s - s // 5
        x = jax.random.normal(ks[0], (s, d), jnp.bfloat16)
        z = jax.random.normal(ks[1], (s, d), jnp.bfloat16)
        dt = jax.nn.softplus(jax.random.normal(ks[2], (s, d)) - 3.0)
        dt = jnp.where(jnp.arange(s)[:, None] < real, dt, 0.0)
        a = -jnp.broadcast_to(jnp.arange(1.0, n + 1)[:, None], (n, d))
        b, c = (jax.random.normal(k, (s, n)) for k in ks[3:5])
        skip, h0 = jnp.ones((d,)), jax.random.normal(ks[5], (n, d))
        args = (x, dt, a, b, c, skip, h0, z)
        y_k, h_k = selective_scan(*args, length=real)
        y_r, h_r = selective_scan(*args, length=real, impl="jnp")
        err = float(jnp.max(jnp.abs(y_k[:real].astype(jnp.float32)
                                    - y_r[:real].astype(jnp.float32))))
        herr = float(jnp.max(jnp.abs(h_k - h_r)))
        # eight rows in one program, each from the state the last left:
        # a call alone is the host's 0.9 ms, whatever the row
        many = jax.jit(lambda h, impl: jax.lax.fori_loop(
            0, 8, lambda i, h: selective_scan(
                x, dt, a, b, c, skip, h, z, length=real, impl=impl)[1], h),
            static_argnums=1)
        for name, impl in (("kernel", "pallas"), ("jnp", "jnp")):
            t = _time(many, h0, impl) / 8
            gbs = ssm_work.scan_bytes(real, d, n) / t / 1e9
            out[f"scan_{s}_{name}"] = {
                "us": t * 1e6, "real_tokens": real,
                "pct_of_hbm_peak": 100 * gbs / PEAK_GB_S}
        out[f"scan_{s}_kernel"].update(y_max_err=err, h_max_err=herr)
    slots, layers = 64, 4
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    pool = jax.random.normal(ks[0], (layers, slots) + state_shape(n, d))
    x = jax.random.normal(ks[1], (slots, d), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (slots, d)) - 3.0)
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1)[:, None], (n, d))
    b, c = (jax.random.normal(k, (slots, n)) for k in ks[3:5])
    for n_live in (64, 21):
        live = jnp.arange(slots) % 3 == 0 if n_live < slots \
            else jnp.ones((slots,), bool)
        args = (x, dt, a, b, c, jnp.ones((d,)), pool, 2, live, x)
        y_k, p_k = selective_update(*args)
        y_r, p_r = selective_update(*args, impl="jnp")
        # a loop of 26 updates in one program: a call alone is the host's
        many = jax.jit(lambda p, impl: jax.lax.fori_loop(
            0, 26, lambda i, p: selective_update(
                x, dt, a, b, c, jnp.ones((d,)), p, i % layers, live, x,
                impl=impl)[1], p), static_argnums=1)
        live_bytes = 2 * int(live.sum()) * n * d * 4
        for name, impl in (("kernel", "pallas"), ("jnp", "jnp")):
            t = _time(many, pool, impl) / 26
            out[f"update_{int(live.sum())}_live_{name}"] = {
                "us": t * 1e6,
                "live_state_pct_of_hbm_peak":
                    100 * (live_bytes / t / 1e9) / PEAK_GB_S}
        out[f"update_{int(live.sum())}_live_kernel"].update(
            y_max_err=float(jnp.max(jnp.abs(
                y_k.astype(jnp.float32) - y_r.astype(jnp.float32)))),
            h_max_err=float(jnp.max(jnp.abs(p_k - p_r))))
    return out


def mqa_kernels(seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention, reference_attention
    from ray_tpu.ops.paged_attention import (paged_attention_decode,
                                             paged_attention_reference)

    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(key, (2, 512, heads, HEAD), jnp.bfloat16)
               for key, heads in zip(keys, (HQ, HKV, HKV)))
    out = {"flash_20_1_max_err": float(jnp.max(jnp.abs(
        jax.jit(attention)(q, k, v).astype(jnp.float32)
        - jax.jit(reference_attention)(q, k, v).astype(jnp.float32))))}
    b, mp, num_pages, layers, layer = 8, 300, 2600, 2, 1
    lengths = [1, PAGE, PAGE + 1, 40 * PAGE + 5, mp * PAGE - 1, mp * PAGE,
               7, 0]
    bt = rng.permutation(num_pages - 1)[:b * mp].reshape(b, mp) + 1
    clean = rng.standard_normal(
        (layers, num_pages, HKV, PAGE, 2 * HEAD)).astype(np.float32)
    live = np.zeros(clean.shape[:2] + (1, PAGE, 1), bool)
    for i, n in enumerate(lengths):
        for col in range(-(-n // PAGE)):
            live[layer, bt[i, col], 0, :min(PAGE, n - col * PAGE)] = True
        bt[i, -(-n // PAGE):] = 0
    poisoned = jnp.asarray(np.where(live, clean, np.nan), jnp.bfloat16)
    zeroed = jnp.asarray(np.where(live, clean, 0.0), jnp.bfloat16)
    bt, lens = jnp.asarray(bt, jnp.int32), jnp.asarray(lengths, jnp.int32)
    qd = jnp.asarray(rng.standard_normal((b, HQ, HEAD)), jnp.bfloat16)
    want = paged_attention_reference(
        qd[:, None], zeroed, bt, jnp.maximum(lens - 1, 0)[:, None],
        layer=layer)[:, 0]
    want = jnp.where((lens > 0)[:, None, None], want, 0).astype(jnp.float32)
    for pages in (8, 32, 256):
        got = paged_attention_decode(qd, poisoned, bt, lens, layer=layer,
                                     pages_per_chunk=pages
                                     ).astype(jnp.float32)
        got = jnp.where((lens > 0)[:, None, None], got, 0)
        out[f"paged_decode_20_1_items_of_{pages}_pages"] = {
            "max_err": float(jnp.max(jnp.abs(got - want))),
            "finite": bool(jnp.isfinite(got).all())}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU found: {dev}", file=sys.stderr)
        return 1
    print(json.dumps({"device": dev.device_kind, **scan_rows(args.seed),
                      **mqa_kernels(args.seed)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
