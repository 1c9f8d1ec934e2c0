"""Remote-connect client overhead vs in-cluster driver.

Mirrors the reference's Ray Client microbenchmark (ref: python/ray/
_private/ray_client_microbenchmark.py; the reference's Ray Client row
shows ~4x overhead vs direct calls). Runs the client in a subprocess
(client mode owns the process-global core) against an in-process head +
proxy, and merges `client_*` keys into the `--out` file.

Run: `python benchmarks/client_overhead.py [--out FILE.json]`.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CLIENT_BENCH = textwrap.dedent("""
    import json
    import sys
    import time

    import ray_tpu

    ray_tpu.init(sys.argv[1])

    @ray_tpu.remote
    def nop():
        return 0

    ray_tpu.get(nop.remote(), timeout=60)

    def timeit(fn, n, warmup=3):
        for _ in range(warmup):
            fn()
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return n / (time.perf_counter() - t0)

    out = {}
    out["client_tasks_sync_per_s"] = round(
        timeit(lambda: ray_tpu.get(nop.remote(), timeout=60), 150), 1)
    batch = 100
    out["client_tasks_async_per_s"] = round(timeit(
        lambda: ray_tpu.get([nop.remote() for _ in range(batch)],
                            timeout=120), 5) * batch, 1)
    out["client_put_get_per_s"] = round(
        timeit(lambda: ray_tpu.get(ray_tpu.put(1), timeout=60), 150), 1)
    ray_tpu.shutdown()
    print("RESULT " + json.dumps(out))
""")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default=None,
                        help="merge client_* keys into this golden JSON")
    args = parser.parse_args()

    import ray_tpu

    session = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    address = session.start_client_proxy()
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", CLIENT_BENCH, address],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-1000:])
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    results = json.loads(line[len("RESULT "):])
    print(json.dumps(results))
    if args.out:
        merged = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                merged = json.load(f)
        merged.update(results)
        with open(args.out, "w") as f:
            json.dump(merged, f, indent=2)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
