"""Streaming data plane A/B: pull-based pipeline vs full materialization.

Three measurements on a 100-block pipeline with a non-trivial map stage:

- ``data_ttfb_ms`` vs ``data_ttfb_materialized_ms``: time until the
  FIRST batch is in the consumer's hands — streamed (the pump yields
  block 1 while upstream tasks still run) vs materialize-then-iterate.
  The acceptance bar is >= 5x (``data_ttfb_speedup``).
- ``data_rows_per_s``: sustained streamed row throughput end to end.
- ``data_peak_store_frac`` vs ``data_peak_store_frac_materialized``:
  peak object-store fill during consumption — streaming must stay
  queue-depth-proportional while materialization holds every block.
- ``data_split_rows_per_s``: two concurrent streaming_split consumers
  driven to epoch completion (disjoint exactly-once coverage asserted).

Run: ``python benchmarks/data_streaming.py [--blocks 100] [--rows 4000]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # runnable from anywhere


def _slow_map(delay):
    def fn(batch):
        time.sleep(delay)
        return batch

    return fn


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--blocks", type=int, default=100)
    parser.add_argument("--rows", type=int, default=4000)
    parser.add_argument("--map-ms", type=float, default=30.0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    import ray_tpu
    from ray_tpu import data as rd
    from ray_tpu.data.executor import _store_used_fraction

    ray_tpu.init(num_cpus=4)
    results = {"blocks": args.blocks, "rows": args.rows,
               "map_ms": args.map_ms}
    delay = args.map_ms / 1e3

    def build():
        # tensor rows so blocks have real bytes in the store
        return rd.range_tensor(args.rows, shape=(512,),
                               parallelism=args.blocks).map_batches(
            _slow_map(delay))

    rd.range(16, parallelism=8).count()  # warm the worker pool

    # --- streamed: TTFB + sustained throughput + peak store ------------
    t0 = time.perf_counter()
    ds = build()
    it = ds.iter_batches(batch_size=64, batch_format="numpy")
    first = next(it)
    ttfb = time.perf_counter() - t0
    rows = len(first["data"])
    for batch in it:
        rows += len(batch["data"])
    stream_total = time.perf_counter() - t0
    assert rows == args.rows, (rows, args.rows)
    stats = ds._last_stream_stats or {}
    results["data_ttfb_ms"] = round(ttfb * 1e3, 1)
    results["data_rows_per_s"] = round(rows / stream_total, 1)
    results["data_peak_store_frac"] = round(
        stats.get("peak_store_frac", 0.0), 4)
    results["stream_peak_in_flight_blocks"] = stats.get(
        "peak_in_flight_blocks")

    # --- materialized: TTFB + peak store -------------------------------
    t0 = time.perf_counter()
    mat = build().materialize()
    mat_it = mat.iter_batches(batch_size=64, batch_format="numpy")
    next(mat_it)
    ttfb_mat = time.perf_counter() - t0
    results["data_ttfb_materialized_ms"] = round(ttfb_mat * 1e3, 1)
    results["data_peak_store_frac_materialized"] = round(
        _store_used_fraction(), 4)
    results["data_ttfb_speedup"] = round(ttfb_mat / max(ttfb, 1e-9), 1)

    # --- streaming_split: two concurrent consumers, one epoch ----------
    split_ds = rd.range(args.rows, parallelism=args.blocks)
    its = split_ds.streaming_split(2)
    out = {}

    def consume(rank):
        got = []
        for batch in its[rank].iter_batches(batch_size=256,
                                            batch_format="numpy"):
            got.extend(int(x) for x in batch["id"])
        out[rank] = got

    t0 = time.perf_counter()
    threads = [threading.Thread(target=consume, args=(r,), daemon=True)
               for r in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    split_dt = time.perf_counter() - t0
    assert sorted(out[0] + out[1]) == list(range(args.rows)), (
        len(out[0]), len(out[1]))
    assert not set(out[0]) & set(out[1])
    results["data_split_rows_per_s"] = round(args.rows / split_dt, 1)
    results["data_split_exactly_once"] = True

    ray_tpu.shutdown()
    print(json.dumps(results))  # one line
    if args.out:
        with open(args.out, "w") as f:
            f.write(json.dumps(results, indent=2))
    ok = results["data_ttfb_speedup"] >= 5.0
    print(f"[data_streaming] ttfb {results['data_ttfb_ms']}ms vs "
          f"materialized {results['data_ttfb_materialized_ms']}ms "
          f"({results['data_ttfb_speedup']}x; bar 5x) "
          f"{'OK' if ok else 'BELOW BAR'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
