"""Prefill→decode KV-handoff microbenchmark (the KV-cache plane's A/B).

Measures the decode-side pull of a sealed KV handoff on the simulated
two-host localhost setup (extra nodelet with its own RTPU_HOST_ID +
RTPU_SHM_ROOT, as in benchmarks/transfer.py): the driver plays the prefill
side — `seal_handoff` puts the KV blob into its host pool and yields the
small descriptor — and a task pinned to the simulated host plays the decode
side, timing `fetch_handoff` (descriptor → dense blob) inside the task.

Two modes, same protocol:
- bulk plane (default): the pull rides the zero-copy chunk stream
  (`kv_handoff_gb_s`);
- RPC fallback (`RTPU_bulk_transfer_enabled=0`): the same bytes ride the
  `om_read` control-RPC path (`kv_handoff_gb_s_rpc`) — the pre-KV-plane
  handoff transport.

`handoff_speedup` is the ratio (the stable signal on a loaded shared box —
judge ratios, not absolutes). The bulk child also runs one tiny in-process
prefill/decode pair end-to-end and reports `pd_ttft_ms` plus the mean TTFT
breakdown (queue/prefill/handoff).

Run: `python benchmarks/pd_handoff.py [--size-mb 16] [--pulls 3] [--out f]`
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


def _measure_pd_ttft() -> dict:
    """One tiny in-process PD pair: warm request, then measured requests
    through prefill→seal→fetch→inject→decode. CPU tiny-model numbers
    track the handoff machinery's overhead, not TPU serving latency."""
    import asyncio

    from ray_tpu.serve.llm import EngineConfig, LLMConfig
    from ray_tpu.serve.llm.disagg import DecodeServer, PrefillServer

    cfg = LLMConfig(
        model_id="pd-bench", warmup=False,
        engine=EngineConfig(model="tiny", page_size=8, num_pages=64,
                            max_model_len=128, prefill_buckets=(64,),
                            max_batch=4, dtype="float32",
                            model_overrides={"vocab_size": 512}))
    prefill = PrefillServer.func_or_class(cfg)
    decode = DecodeServer.func_or_class(cfg)
    sampling = {"max_tokens": 8, "temperature": 0.0, "top_k": 0,
                "seed": None}
    prompt = list(range(1, 40))

    async def one():
        t0 = time.perf_counter()
        handoff = await prefill.prefill(prompt, sampling)
        ttft = time.perf_counter() - t0
        result = await decode.decode(handoff, sampling)
        return ttft, {
            "queue_s": handoff.get("queued_s", 0.0),
            "prefill_s": handoff.get("prefill_s", 0.0),
            "handoff_s": (handoff.get("seal_s", 0.0)
                          + result.get("handoff_pull_s", 0.0)),
        }

    async def run():
        await one()  # warm: compiles both engines' shapes
        ttfts, parts = [], []
        for _ in range(3):
            ttft, bd = await one()
            ttfts.append(ttft)
            parts.append(bd)
        return ttfts, parts

    ttfts, parts = asyncio.run(run())
    ttfts.sort()
    n = len(parts)
    return {
        "pd_ttft_ms": round(ttfts[len(ttfts) // 2] * 1e3, 2),
        "pd_ttft_breakdown_ms": {
            k: round(sum(p[k] for p in parts) / n * 1e3, 2)
            for k in parts[0]},
    }


def _child(stream: bool, size_mb: int, pulls: int) -> int:
    """One measured session (subprocess: the transfer-mode knob must bind
    before any ray_tpu state exists, and sessions must not leak across
    modes)."""
    import numpy as np

    import ray_tpu
    from ray_tpu.serve.llm.kv_transfer import seal_handoff
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    session = ray_tpu.init(num_cpus=2)
    pool = tempfile.mkdtemp(prefix="rtpu_pdhandoff_")
    node_b = session.add_node(
        num_cpus=2,
        env={"RTPU_HOST_ID": "pdhandoff-host-b",
             "RTPU_SHM_ROOT": pool,
             "RTPU_bulk_transfer_enabled": "1" if stream else "0"})

    nbytes = size_mb << 20
    rng = np.random.default_rng(0)

    @ray_tpu.remote
    def decode_side(desc):
        from ray_tpu.serve.llm.kv_transfer import fetch_handoff

        t0 = time.perf_counter()
        blob = fetch_handoff(desc)
        dt = time.perf_counter() - t0
        kv = np.asarray(blob["kv"])
        return dt, int(kv.nbytes), float(kv.reshape(-1)[-1])

    strategy = NodeAffinitySchedulingStrategy(node_id=node_b)

    def make_blob(n):
        kv = rng.standard_normal(n // 4).astype(np.float32)
        return {"kv": kv.reshape(2, -1), "prompt_ids": list(range(64)),
                "output_ids": [7]}

    # warmup: opens connections / resolves endpoints
    warm = seal_handoff(make_blob(1 << 20))
    ray_tpu.get(decode_side.options(
        scheduling_strategy=strategy).remote(warm), timeout=120)

    rates = []
    for _ in range(pulls):
        blob = make_blob(nbytes)
        desc = seal_handoff(blob)  # fresh object: no pool cache hit
        dt, got, last = ray_tpu.get(decode_side.options(
            scheduling_strategy=strategy).remote(desc), timeout=300)
        assert got == blob["kv"].nbytes
        assert last == float(blob["kv"].reshape(-1)[-1])
        rates.append(got / dt / 1e9)
    out = {"mode": "plane" if stream else "rpc",
           "gb_s": round(sum(rates) / len(rates), 3),
           "gb_s_best": round(max(rates), 3),
           "pulls": pulls, "size_mb": size_mb}
    if stream:
        try:
            out.update(_measure_pd_ttft())
        except Exception as e:  # noqa: BLE001 — ttft is a bonus datapoint
            out["pd_ttft_error"] = repr(e)[:200]
    print("CHILD_RESULT " + json.dumps(out))
    ray_tpu.shutdown()
    return 0


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--size-mb", type=int, default=16)
    parser.add_argument("--pulls", type=int, default=3)
    parser.add_argument("--out", default=None)
    parser.add_argument("--child-mode", choices=["plane", "rpc"],
                        default=None, help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child_mode:
        return _child(args.child_mode == "plane", args.size_mb, args.pulls)

    results = {"size_mb": args.size_mb, "pulls": args.pulls}
    here = os.path.abspath(__file__)
    for mode in ("plane", "rpc"):
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
        key = "kv_handoff_gb_s" if mode == "plane" else "kv_handoff_gb_s_rpc"
        results[key] = child["gb_s"]
        results[key + "_best"] = child["gb_s_best"]
        for extra in ("pd_ttft_ms", "pd_ttft_breakdown_ms",
                      "pd_ttft_error"):
            if extra in child:
                results[extra] = child[extra]
    if results.get("kv_handoff_gb_s") and results.get("kv_handoff_gb_s_rpc"):
        results["handoff_speedup"] = round(
            results["kv_handoff_gb_s"] / results["kv_handoff_gb_s_rpc"], 2)
    print(json.dumps(results))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
