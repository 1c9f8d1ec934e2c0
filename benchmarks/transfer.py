"""Cross-host object-pull throughput microbenchmark.

Measures the data plane on the simulated two-host localhost setup (an
extra nodelet with its own RTPU_HOST_ID + RTPU_SHM_ROOT, as in
tests/test_multihost.py): the driver puts multi-MB objects, tasks pinned
to the simulated host pull them, and the pull time is clocked INSIDE the
task around ray_tpu.get. Runs the same protocol twice — bulk stream
enabled (default) and forced onto the om_read RPC fallback
(RTPU_bulk_transfer_enabled=0) — so the stream's advantage has its own
trend line (`object_pull_gb_s` vs `object_pull_gb_s_rpc`).

Run: `python benchmarks/transfer.py [--size-mb 64] [--pulls 4] [--out f]`
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # runnable from anywhere


def _child(stream: bool, size_mb: int, pulls: int) -> int:
    """One measured session (subprocess: the config knob must bind before
    any ray_tpu state exists, and sessions must not leak across modes)."""
    import numpy as np

    import ray_tpu
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    session = ray_tpu.init(num_cpus=2)
    pool = tempfile.mkdtemp(prefix="rtpu_xferbench_")
    node_b = session.add_node(
        num_cpus=2,
        env={"RTPU_HOST_ID": "xferbench-host-b",
             "RTPU_SHM_ROOT": pool,
             "RTPU_bulk_transfer_enabled": "1" if stream else "0"})

    nbytes = size_mb << 20
    rng = np.random.default_rng(0)

    @ray_tpu.remote
    def pull_timed(refs):
        t0 = time.perf_counter()
        arr = ray_tpu.get(refs[0])
        dt = time.perf_counter() - t0
        return dt, arr.nbytes, float(arr[-1])

    strategy = NodeAffinitySchedulingStrategy(node_id=node_b)
    # warmup: one small pull compiles nothing but opens connections
    warm = ray_tpu.put(np.zeros(1 << 20, dtype=np.uint8))
    ray_tpu.get(pull_timed.options(
        scheduling_strategy=strategy).remote([warm]), timeout=120)

    rates = []
    for i in range(pulls):
        payload = rng.integers(0, 255, nbytes, dtype=np.uint8)
        ref = ray_tpu.put(payload)  # fresh object: no pool cache hit
        dt, got_bytes, last = ray_tpu.get(pull_timed.options(
            scheduling_strategy=strategy).remote([ref]), timeout=300)
        assert got_bytes == nbytes and last == float(payload[-1])
        rates.append(got_bytes / dt / 1e9)
        del ref
    out = {"mode": "stream" if stream else "rpc",
           "gb_s": round(sum(rates) / len(rates), 3),
           "gb_s_best": round(max(rates), 3),
           "pulls": pulls, "size_mb": size_mb}
    print("CHILD_RESULT " + json.dumps(out))
    ray_tpu.shutdown()
    return 0


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--size-mb", type=int, default=64)
    parser.add_argument("--pulls", type=int, default=4)
    parser.add_argument("--out", default=None)
    parser.add_argument("--child-mode", choices=["stream", "rpc"],
                        default=None, help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child_mode:
        return _child(args.child_mode == "stream", args.size_mb, args.pulls)

    results = {"size_mb": args.size_mb, "pulls": args.pulls}
    here = os.path.abspath(__file__)
    for mode in ("stream", "rpc"):
        env = dict(os.environ)
        if mode == "rpc":
            env["RTPU_bulk_transfer_enabled"] = "0"
        run = subprocess.run(
            [sys.executable, here, "--child-mode", mode,
             "--size-mb", str(args.size_mb), "--pulls", str(args.pulls)],
            capture_output=True, text=True, timeout=600, env=env)
        child = None
        for line in reversed(run.stdout.strip().splitlines()):
            if line.startswith("CHILD_RESULT "):
                child = json.loads(line[len("CHILD_RESULT "):])
                break
        if child is None:
            results[f"error_{mode}"] = (run.stderr or run.stdout)[-300:]
            continue
        key = "object_pull_gb_s" if mode == "stream" \
            else "object_pull_gb_s_rpc"
        results[key] = child["gb_s"]
        results[key + "_best"] = child["gb_s_best"]
    if "object_pull_gb_s" in results and "object_pull_gb_s_rpc" in results \
            and results["object_pull_gb_s_rpc"] > 0:
        results["stream_speedup"] = round(
            results["object_pull_gb_s"] / results["object_pull_gb_s_rpc"],
            2)
    print(json.dumps(results))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
