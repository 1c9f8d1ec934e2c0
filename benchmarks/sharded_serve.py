"""Sharded vs single-chip Serve-LLM decode step latency + pipeline arm.

Measures the fused decode dispatch of the tensor-parallel engine
(ray_tpu/serve/llm/sharding.py) against the single-device engine on the
virtual 8-device CPU mesh, plus a greedy-parity check — the same
bit-exactness contract the dryrun serve tier asserts. Run:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/sharded_serve.py [--tp 2] [--steps 30] [--pp 2]

Prints ONE JSON line with:
  decode_step_ms_single / decode_step_ms_tp / tp_overhead_x — fused
      decode step latency, single vs tensor-parallel;
  tp_scaling_eff — REAL scaling efficiency, speedup/tp =
      single_ms/(tp_ms*tp): 1.0 means perfect linear scaling, 1/tp
      means tp bought nothing. On this 1-vCPU box all virtual devices
      share one core so the honest ceiling is ~1/tp + partitioning
      overhead — the key exists so real chips get a trend line, not so
      this box looks good;
  --pp arm (pipeline-parallel serving, ray_tpu/serve/llm/pp.py):
      decode_tok_s_pp vs decode_tok_s_single (same steady-decode window,
      tokens actually emitted), pp_bubble_frac — starved-read fraction
      of stage channel reads measured AFTER a stats reset so warmup
      never pollutes the steady-state number — and pp_greedy_parity.
      pp_bubble_frac > 0.35 fails the round unless the box is
      measurably overloaded (loadavg > 1.5x cores), in which case the
      miss is downgraded to pp_bubble_downgraded — parity failures are
      never downgraded.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # runnable from anywhere

ENGINE_CFG = dict(model="tiny", page_size=8, num_pages=64,
                  max_model_len=128, max_batch=4,
                  prefill_buckets=(16, 32, 64), dtype="float32",
                  model_overrides={"vocab_size": 512})


def _setup_devices(n: int) -> None:
    # APPEND the device-count flag when XLA_FLAGS is already set: child
    # processes take their device count from the environment
    flag = f"--xla_force_host_platform_device_count={n}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:  # a backend already exists: too late, keep it
        pass


def parity_prompts():
    """The fixed prompt set of the greedy bit-exactness contract —
    shared with the dryrun serve tier (__graft_entry__.py), so bench and
    dryrun assert the SAME parity, defined once."""
    import numpy as np

    return [list(np.random.default_rng(s).integers(0, 500, n))
            for s, n in ((0, 13), (1, 9), (2, 21))]


def greedy_collect(engine, prompts, max_tokens=8):
    """Run `prompts` to completion greedily; returns {rid: token_ids}."""
    from ray_tpu.serve.llm import SamplingParams

    for i, p in enumerate(prompts):
        engine.add_request(f"g{i}", p, SamplingParams(max_tokens=max_tokens))
    out = {f"g{i}": [] for i in range(len(prompts))}
    done = set()
    for _ in range(500):
        for d in engine.step():
            out[d.request_id].extend(d.new_token_ids)
            if d.finished:
                done.add(d.request_id)
        if len(done) == len(prompts):
            break
    return out


def _decode_step_ms(engine, steps: int) -> float:
    """Steady-state decode: fill every slot, drain prefill, then time
    `steps` scheduler iterations of pure fused decode."""
    import numpy as np

    from ray_tpu.serve.llm import SamplingParams

    rng = np.random.default_rng(0)
    budget = steps * max(1, engine.config.decode_steps_per_dispatch) + 16
    for i in range(engine.config.max_batch):
        engine.add_request(f"d{i}", list(rng.integers(0, 400, 12)),
                           SamplingParams(max_tokens=budget))
    # drain prefill + first decode compiles (warm shapes)
    for _ in range(8):
        engine.step()
    t0 = time.perf_counter()
    for _ in range(steps):
        engine.step()
    dt = time.perf_counter() - t0
    for i in range(engine.config.max_batch):
        engine.abort(f"d{i}")
    while engine.has_work():
        engine.step()
    return dt / steps * 1e3


def _decode_tok_window(engine, steps: int):
    """Steady-state decode tokens/s: fill every slot, drain prefill and
    warm the decode shapes, reset the pipeline stats (pipelined engine
    only — so the bubble number covers ONLY this window), then count
    tokens actually emitted over `steps` scheduler iterations. Returns
    (tok_s, pp_bubble_frac_or_None)."""
    import numpy as np

    from ray_tpu.serve.llm import SamplingParams

    rng = np.random.default_rng(0)
    for i in range(engine.config.max_batch):
        engine.add_request(f"w{i}", list(rng.integers(0, 400, 12)),
                           SamplingParams(max_tokens=100))
    for _ in range(12):  # drain prefill + warm decode compiles
        engine.step()
    pipelined = hasattr(engine, "pp_stats")
    if pipelined:
        engine.pp_stats(reset=True)  # steady-state window only
    toks = 0
    t0 = time.perf_counter()
    for _ in range(steps):
        for d in engine.step():
            toks += len(d.new_token_ids)
    dt = time.perf_counter() - t0
    bubble = engine.pp_stats()["pp_bubble_frac"] if pipelined else None
    for i in range(engine.config.max_batch):
        engine.abort(f"w{i}")
    while engine.has_work():
        engine.step()
    return (toks / dt if dt else 0.0), bubble


def _overloaded() -> bool:
    """The usual downgrade guard: on a measurably starved box a missed
    timing bar is environment, not regression (same rule as
    benchmarks/overload_drill.py)."""
    try:
        return os.getloadavg()[0] > 1.5 * (os.cpu_count() or 1)
    except OSError:  # pragma: no cover - platform without getloadavg
        return False


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--tp", type=int, default=2)
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--pp", type=int, default=0,
                        help="pipeline stages for the --pp arm (0 = off)")
    args = parser.parse_args()
    _setup_devices(args.devices)

    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    prompts = parity_prompts()

    single = LLMEngine(EngineConfig(**ENGINE_CFG))
    ref_out = greedy_collect(single, prompts)
    single_ms = _decode_step_ms(single, args.steps)
    single_tok_s, _ = _decode_tok_window(single, args.steps)

    sharded = LLMEngine(EngineConfig(**ENGINE_CFG, tp=args.tp))
    tp_out = greedy_collect(sharded, prompts)
    parity = tp_out == ref_out
    tp_ms = _decode_step_ms(sharded, args.steps)

    out = {
        "metric": "sharded_serve_decode_step",
        "tp": args.tp,
        "devices": args.devices,
        "steps": args.steps,
        "batch": ENGINE_CFG["max_batch"],
        "decode_step_ms_single": round(single_ms, 2),
        "decode_step_ms_tp": round(tp_ms, 2),
        "tp_overhead_x": round(tp_ms / single_ms, 2) if single_ms else None,
        # speedup/tp: 1.0 = perfect linear scaling, 1/tp = tp bought
        # nothing (the honest ceiling on this shared-core box)
        "tp_scaling_eff": (round(single_ms / (tp_ms * args.tp), 3)
                           if tp_ms else None),
        "decode_tok_s_single": round(single_tok_s, 1),
        "greedy_parity": parity,
        "sharding": sharded.stats().get("sharding"),
    }

    pp_parity = True
    if args.pp and args.pp > 1:
        import ray_tpu
        from ray_tpu.serve.llm import PipelinedEngine

        ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
        # microbatch depth 2*S: one in-flight frame per stage boundary
        # (the classic 2(S-1) GPipe fill floor) plus a cushion so the
        # host's harvest+dispatch latency never drains a stage queue —
        # on this box depth 2(S-1) measures ~0.5 bubble purely from the
        # 1-vCPU host being in the loop between consecutive frames
        ppe = PipelinedEngine(EngineConfig(**ENGINE_CFG, pp=args.pp,
                                           pp_microbatches=2 * args.pp))
        try:
            pp_out = greedy_collect(ppe, prompts)
            pp_parity = pp_out == ref_out
            pp_tok_s, bubble = _decode_tok_window(ppe, args.steps)
            stats = ppe.pp_stats()
        finally:
            ppe.shutdown()
            ray_tpu.shutdown()
        bubble_ok = bubble is not None and bubble <= 0.35
        out.update({
            "pp": args.pp,
            "pp_microbatches": stats["pp_microbatches"],
            "decode_tok_s_pp": round(pp_tok_s, 1),
            "pp_bubble_frac": (round(bubble, 3)
                               if bubble is not None else None),
            "pp_greedy_parity": pp_parity,
            "pp_bubble_ok": bubble_ok,
        })
        if not bubble_ok and _overloaded():
            out["pp_bubble_downgraded"] = True  # environment, not code
            bubble_ok = True
        parity = parity and pp_parity
        if not bubble_ok:
            out["pp_green"] = False
            print(json.dumps(out))
            sys.exit(1)
        out["pp_green"] = pp_parity

    print(json.dumps(out))
    if not parity:
        sys.exit(1)


if __name__ == "__main__":
    main()
