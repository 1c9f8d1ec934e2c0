"""Headline benchmark: Llama training MFU on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline: the reference publishes no TPU training numbers; the north-star
target from BASELINE.json is >=40% MFU for Llama-class training, so
vs_baseline = measured_mfu / 40.

Order: the serving bench runs first, on an otherwise-idle device; the
training bench follows; the CPU-side runtime microbench runs last.

Superseded by the cells benchmark of ROADMAP A1 and not the chip smoke
(`chip_smoke.py` is): kept until A1 lands, not grown.
"""

import gc
import json
import sys
import time


def _peak_flops(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    # bf16 peak TFLOP/s per chip
    table = {
        "tpu v5 lite": 197e12, "tpu v5e": 197e12,
        "tpu v5p": 459e12, "tpu v5": 459e12,
        "tpu v4": 275e12, "tpu v6e": 918e12, "tpu v6 lite": 918e12,
    }
    for key, val in table.items():
        if key in kind:
            return val
    raise ValueError(f"no peak FLOP/s on record for device kind {kind!r}")


def bench_serve(on_tpu: bool) -> dict:
    """Paged-KV engine on the chip (north star: p50 TTFT < 200 ms; the
    reference publishes no serving goldens — it delegates the engine to
    vLLM). Two measurements:
    - burst: all requests submitted at once (driver protocol since r02),
      TTFT aggregated over 3 bursts;
    - sustained: Poisson arrivals at ~0.75x the engine's decode capacity,
      p50/p99 TTFT + token throughput."""
    import numpy as np

    from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams

    if on_tpu:
        cfg = EngineConfig(model="llama-1b", page_size=16, num_pages=1024,
                           max_model_len=512, max_batch=8,
                           prefill_buckets=(128, 256, 512),
                           dtype="bfloat16",
                           decode_steps_per_dispatch=8,
                           pipeline_depth=3)
        prompt_len, gen_len, n_req = 128, 24, 6
    else:
        cfg = EngineConfig(model="tiny", page_size=8, num_pages=64,
                           max_model_len=128, max_batch=4,
                           prefill_buckets=(16, 32, 64, 128),
                           dtype="float32",
                           model_overrides={"vocab_size": 512})
        prompt_len, gen_len, n_req = 16, 4, 3
    t_bench = time.perf_counter()
    engine = LLMEngine(cfg)
    rng = np.random.default_rng(0)

    def prompt():
        return list(rng.integers(0, 400, prompt_len))

    def run_wave(tag, n, submit_at=None, wave_budget_s=90.0):
        """Drive n requests; returns (sorted ttfts_ms, tok_s). With
        submit_at (relative seconds), requests are injected on schedule
        while the engine steps (Poisson mode); otherwise all submit up
        front (burst mode). Raises if the wave produced no tokens inside
        its budget, so a stalled engine surfaces as the serve 'error'
        field instead of starving the headline training metric."""
        submit, first_tok, last_tok = {}, {}, {}
        n_tokens = 0
        t_start = time.perf_counter()
        pending = list(range(n))
        if submit_at is None:
            for i in pending:
                rid = f"{tag}{i}"
                submit[rid] = time.perf_counter()
                engine.add_request(rid, prompt(),
                                   SamplingParams(max_tokens=gen_len))
            pending = []
        finished = 0
        deadline = t_start + wave_budget_s
        while time.perf_counter() < deadline:
            if pending:
                now_rel = time.perf_counter() - t_start
                while pending and submit_at[pending[0]] <= now_rel:
                    i = pending.pop(0)
                    rid = f"{tag}{i}"
                    submit[rid] = time.perf_counter()
                    engine.add_request(rid, prompt(),
                                       SamplingParams(max_tokens=gen_len))
                if not engine.has_work():
                    time.sleep(0.002)
            for d in engine.step():
                now = time.perf_counter()
                if d.request_id not in first_tok and d.new_token_ids:
                    first_tok[d.request_id] = now
                n_tokens += len(d.new_token_ids)
                last_tok[d.request_id] = now
                if d.finished:
                    finished += 1
            if finished >= n and not pending:
                break
        ttfts = sorted((first_tok[r] - submit[r]) * 1e3 for r in submit
                       if r in first_tok)
        span = max(last_tok.values()) - min(submit.values())
        return ttfts, n_tokens / span

    # warmup: one full UNTIMED wave at the measured concurrency, so every
    # bucketed shape (batched prefill rb, fused-decode chunk) compiles
    # before the clock starts — a persistent server amortizes these once
    run_wave("warm", n_req, wave_budget_s=240.0)  # budget covers compiles

    # burst protocol (same as r01/r02): all requests at once, 3 trials
    all_ttfts = []
    tok_s = 0.0
    for trial in range(3):
        if trial and time.perf_counter() - t_bench > 300:
            break  # slow-but-alive engine: keep the driver budget intact
        ttfts, tok_s = run_wave(f"b{trial}_", n_req)
        all_ttfts.extend(ttfts)
    all_ttfts.sort()

    out = {"ttft_ms_p50": round(all_ttfts[len(all_ttfts) // 2], 1),
           "ttft_ms_max": round(all_ttfts[-1], 1),
           "decode_tok_s": round(tok_s, 1),
           "n_requests": n_req, "prompt_len": prompt_len,
           "burst_trials": 3}

    # sustained Poisson arrivals: ~12 req over ~4s (rate chosen well
    # under the decode capacity so the queue stays bounded)
    if time.perf_counter() - t_bench > 400:
        return out  # protect the headline metric's time budget
    n_sus = 12 if on_tpu else 6
    rate = 3.0 if on_tpu else 10.0  # req/s
    gaps = np.random.default_rng(7).exponential(1.0 / rate, n_sus)
    submit_at = np.cumsum(gaps)
    ttfts, sus_tok_s = run_wave("p", n_sus, submit_at=list(submit_at))
    out["sustained"] = {
        "rate_rps": rate, "n_requests": n_sus,
        "ttft_ms_p50": round(ttfts[len(ttfts) // 2], 1),
        "ttft_ms_p99": round(ttfts[min(len(ttfts) - 1,
                                       int(len(ttfts) * 0.99))], 1),
        "tok_s": round(sus_tok_s, 1),
    }
    p50_low = ttfts[len(ttfts) // 2]

    # saturation search (VERDICT r4 #7): ramp the arrival rate until
    # TTFT degrades, reporting the highest sustained token throughput
    # with a still-bounded queue. The previous fixed 0.75x tier proved
    # only that an under-driven engine keeps up; the CAPACITY ceiling
    # is the number operators plan against.
    best = dict(out["sustained"], tok_s=sus_tok_s)
    trial_rate = rate
    for step_i in range(4):
        if time.perf_counter() - t_bench > 460:
            break  # headline training metric owns the rest of the budget
        trial_rate *= 1.6
        gaps = np.random.default_rng(11 + step_i).exponential(
            1.0 / trial_rate, n_sus)
        ttfts_r, tok_s_r = run_wave(f"s{step_i}_", n_sus,
                                    submit_at=list(np.cumsum(gaps)))
        if not ttfts_r:
            break
        p50_r = ttfts_r[len(ttfts_r) // 2]
        # queue unbounded: median TTFT blew past 4x the low-rate median
        # (requests are now waiting on each other, not the engine)
        if p50_r > max(4 * p50_low, 1000.0):
            break
        if tok_s_r >= best["tok_s"]:
            best = {"rate_rps": round(trial_rate, 2),
                    "n_requests": n_sus,
                    "ttft_ms_p50": round(p50_r, 1),
                    "ttft_ms_p99": round(
                        ttfts_r[min(len(ttfts_r) - 1,
                                    int(len(ttfts_r) * 0.99))], 1),
                    "tok_s": round(tok_s_r, 1)}
        elif tok_s_r < 0.9 * best["tok_s"]:
            break  # past the knee: throughput is falling, stop ramping
    out["max_sustained"] = best
    out["max_sustained_tok_s"] = best["tok_s"]
    return out


def bench_serve_tp() -> dict:
    """Tensor-parallel + pipeline-parallel serve datapoint: sharded vs
    single-chip decode step latency with real scaling efficiency
    (tp_scaling_eff = speedup/tp), the 2-stage pipelined engine's
    decode_tok_s_pp and steady-state pp_bubble_frac (loadavg-downgraded
    bar at 0.35), and greedy parity for BOTH arms on the virtual
    8-device CPU mesh (benchmarks/sharded_serve.py). Runs in a
    subprocess so its CPU device config never touches this process's
    TPU backend."""
    import os
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="", JAX_PLATFORM_NAME="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(here, "benchmarks",
                                      "sharded_serve.py"),
         "--tp", "2", "--steps", "15", "--pp", "2"],
        capture_output=True, text=True, timeout=420, cwd=here, env=env)
    for line in reversed(out.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(
        f"sharded_serve produced no JSON: {out.stderr[-300:]}")


def bench_runtime() -> dict:
    """Core-runtime microbenchmarks (tasks/s, actor calls/s) — the
    BASELINE.md table companion, measured on this host."""
    import os
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    out = subprocess.run(
        [sys.executable, os.path.join(here, "benchmarks", "ray_perf.py"),
         "--scale", "0.5"],
        capture_output=True, text=True, timeout=300, cwd=here)
    for line in reversed(out.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(f"ray_perf produced no JSON: {out.stderr[-300:]}")


def bench_transfer() -> dict:
    """Cross-host object-pull throughput on the simulated two-host
    localhost setup (benchmarks/transfer.py): the bulk-stream data plane
    (`object_pull_gb_s`) vs the om_read RPC fallback
    (`object_pull_gb_s_rpc`), so the data plane has its own trend line."""
    import os
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    out = subprocess.run(
        [sys.executable, os.path.join(here, "benchmarks", "transfer.py"),
         "--size-mb", "48", "--pulls", "3"],
        capture_output=True, text=True, timeout=600, cwd=here)
    for line in reversed(out.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(f"transfer bench produced no JSON: {out.stderr[-300:]}")


def bench_pd_handoff() -> dict:
    """Prefill→decode KV handoff on the simulated two-host setup
    (benchmarks/pd_handoff.py): bulk-plane descriptor pull
    (`kv_handoff_gb_s`) vs the om_read RPC fallback
    (`kv_handoff_gb_s_rpc`), plus the tiny in-process PD pair's
    `pd_ttft_ms` with its queue/prefill/handoff breakdown. Runs on the
    CPU backend in a subprocess so the engines never touch the chip
    this process holds."""
    import os
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="", JAX_PLATFORM_NAME="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(here, "benchmarks", "pd_handoff.py"),
         "--size-mb", "16", "--pulls", "3"],
        capture_output=True, text=True, timeout=600, cwd=here, env=env)
    for line in reversed(out.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(
        f"pd_handoff produced no JSON: {out.stderr[-300:]}")


def _run_bench_json(script: str, timeout: int, args: tuple = ()) -> dict:
    """Run a benchmarks/<script> in a subprocess and return the last
    JSON line it printed — the shared shape of every script-backed
    bench tier."""
    import os
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    out = subprocess.run(
        [sys.executable, os.path.join(here, "benchmarks", script),
         *args],
        capture_output=True, text=True, timeout=timeout, cwd=here)
    for line in reversed(out.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(
        f"{script} produced no JSON: {out.stderr[-300:]}")


def bench_dag() -> dict:
    """Compiled-graph cross-host data plane on the simulated two-host
    setup (benchmarks/dag_pipeline.py): steady-state per-step latency
    (`dag_step_us`, zero-RPC asserted), stage-handoff GB/s compiled vs
    the actor-RPC DAG path (`dag_handoff_gb_s` / `dag_handoff_gb_s_rpc`),
    and the cross-host ring allreduce with exactness check."""
    return _run_bench_json("dag_pipeline.py", 600,
                           ("--size-mb", "4", "--steps", "16"))


def bench_data_streaming() -> dict:
    """Streaming data plane A/B (benchmarks/data_streaming.py):
    time-to-first-batch streamed vs materialized (`data_ttfb_ms`,
    >=5x bar), sustained `data_rows_per_s`, peak store fill
    (`data_peak_store_frac` — queue-depth-bounded vs whole-dataset),
    and two-consumer streaming_split throughput with exactly-once
    coverage asserted in-bench."""
    return _run_bench_json("data_streaming.py", 300)


def bench_chaos_drill() -> dict:
    """Robustness signal for the trajectory files: a time-guarded mini
    failure drill (benchmarks/chaos_drill.py — controller kill+restart
    under a live actor, node death with placement failover, then a
    persist-dir restart replaying journal+snapshot with a torn tail)
    emits recovery_controller_ms / recovery_node_death_ms /
    recovery_controller_persist_ms / persist_drill_green /
    chaos_drills_green so every round carries recovery time next to
    throughput. The pp stage-rank kill drill rides along
    (recovery_pp_rank_ms / pp_drill_green): SIGKILL one rank of a
    2-stage pipelined serve gang mid-decode, typed ActorDiedError,
    replacement gang's first token timed."""
    return _run_bench_json("chaos_drill.py", 480)


def bench_overload_drill() -> dict:
    """Serve admission plane under overload (benchmarks/
    overload_drill.py): open-loop arrival at 1x-10x of measured
    capacity against a slow deployment — goodput held at 10x
    (serve_goodput_rps vs serve_capacity_rps), typed-429 shedding
    (serve_shed_rate, serve_reject_p99_ms < 1s), bounded p99 of
    admitted traffic (serve_admitted_p99_ms), zero untyped timeouts,
    and a chaos wave with delay(execute_task) injected mid-overload."""
    return _run_bench_json("overload_drill.py", 300)


def bench_engine_sched() -> dict:
    """Continuous-batching scheduler A/B (benchmarks/engine_sched.py):
    chunked-prefill interleave TTFT under mixed short/512-token arrivals
    (ttft_ms_p99_longmix on vs off, >=2x bar), bounded inter-token
    latency (itl_ms_p99), continuous-batching decode throughput
    (decode_tok_s_cb), and prompt-lookup speculative decoding on an
    in-bench-trained repetitive model (spec_tok_s vs
    decode_tok_s_spec_base, >=1.3x bar, greedy bit-parity asserted as
    spec_exact). Forces the CPU backend internally — the scheduler
    effects under test are compute-ordering effects. Full-length waves
    (not --quick): the p99 keys are max-of-collisions and need the
    larger sample to sit stably above their bars."""
    return _run_bench_json("engine_sched.py", 420)


def bench_broadcast_spill() -> dict:
    """Tiered object store (benchmarks/broadcast_spill.py): replica
    broadcast tree vs sequential owner fan-out under a modeled
    fixed-bandwidth uplink (broadcast_gb_s / broadcast_ab_speedup,
    >=2x asserted in-bench), spill/restore throughput through the
    shm->disk tier API (spill_restore_mb_s), and the memory-pressure
    drill — a put storm that must stay under the high-watermark with
    every spilled object reading back bit-exact (spill_storm_green)."""
    return _run_bench_json("broadcast_spill.py", 300)


def bench_scale_envelope() -> dict:
    """Scheduler scale envelope over the in-process 100-node harness
    (benchmarks/scale_envelope.py): many_tasks_per_s /
    many_actors_per_s / many_pgs_per_s against real
    controller/gossip/spill paths with fake workers,
    gossip_entries_per_beat (O(changed) bar), and the warm-standby
    failover drill — recovery_controller_failover_ms < 1000 with every
    actor reattached, never re-created (failover_drill_green)."""
    return _run_bench_json("scale_envelope.py", 480)


def bench_train(on_tpu: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import LlamaModel, get_config
    from ray_tpu.parallel.mesh import create_mesh, MeshConfig
    from ray_tpu.parallel.train_lib import ShardedTrainer, default_optimizer

    if on_tpu:
        # bf16 params, dots-saveable remat (minimal recompute that
        # still fits), flash-attention 512 blocks, fused chunked
        # cross-entropy (no [B,S,V] fp32 logits)
        cfg = get_config("llama-1b", param_dtype=jnp.bfloat16,
                         remat_policy="dots")
        batch_size, seq = 3, 2048
        steps, warmup = 20, 3
    else:  # CPU smoke so the bench always emits a line
        cfg = get_config("tiny")
        batch_size, seq = 4, 128
        steps, warmup = 3, 1

    model = LlamaModel(cfg)
    mesh = create_mesh(MeshConfig(dp=1, fsdp=1, sp=1, tp=1),
                       devices=jax.devices()[:1])
    trainer = ShardedTrainer(model, mesh, optimizer=default_optimizer())
    rng = np.random.default_rng(0)
    # forward length == seq exactly (block-aligned: the flash kernel
    # tiles at 512, so 2049 would pad 25% of query rows away)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, (batch_size, seq), dtype=np.int32)}

    state = trainer.init(jax.random.PRNGKey(0), batch)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))

    for _ in range(warmup):
        state, metrics = trainer.step(state, batch)
    jax.block_until_ready(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = trainer.step(state, batch)
    # the final loss depends on every step: full sync
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0

    tokens = batch_size * seq * steps
    tokens_per_s = tokens / dt
    # training FLOPs: 6*N per token (fwd+bwd) + attention term
    hd = cfg.head_dim_
    attn_flops_per_tok = 12 * cfg.num_layers * cfg.num_heads * hd * seq
    flops_per_tok = 6 * n_params + attn_flops_per_tok
    achieved = tokens_per_s * flops_per_tok
    peak = _peak_flops(jax.devices()[0])
    mfu = 100.0 * achieved / peak
    return {
        "mfu": mfu,
        "tokens_per_s": round(tokens_per_s, 1),
        "params": n_params,
        "batch": batch_size, "seq": seq,
        "loss": round(float(metrics["loss"]), 4),
    }


def main():
    import jax

    start = globals().get("_T0", time.perf_counter())
    on_tpu = jax.default_backend() == "tpu"

    # 1. serving latency on an idle device (see module docstring)
    try:
        serve = bench_serve(on_tpu)
    except Exception as e:  # noqa: BLE001 — report, never block the line
        serve = {"error": repr(e)[:200]}
    gc.collect()  # free engine params + KV pages before training

    # 2. headline training MFU
    train = bench_train(on_tpu)
    mfu = round(train.pop("mfu"), 2)
    result = {
        "metric": ("llama1b_train_mfu_1chip" if on_tpu
                   else "llama_tiny_cpu_smoke"),
        "value": mfu,
        "unit": "% MFU",
        "vs_baseline": round(mfu / 40.0, 3),
        "detail": {**train, "backend": jax.default_backend(),
                   "serve": serve},
    }
    gc.collect()

    # 3. core-runtime microbench (CPU-side), time-guarded so the primary
    # line always lands inside the driver's budget
    if time.perf_counter() - start < 480:
        try:
            result["detail"]["runtime"] = bench_runtime()
            # hoist the scheduling-plane headline (argument GB/s with
            # locality-aware placement) next to the other plane keys
            if "multi_locality_gb_s" in result["detail"]["runtime"]:
                result["detail"]["multi_locality_gb_s"] = \
                    result["detail"]["runtime"]["multi_locality_gb_s"]
        except Exception as e:  # noqa: BLE001
            result["detail"]["runtime"] = {"error": repr(e)[:200]}

    # 4. tensor-parallel serve datapoint (virtual-mesh subprocess),
    # same time guard
    if time.perf_counter() - start < 420:
        try:
            serve_tp = bench_serve_tp()
            result["detail"]["serve_tp"] = serve_tp
            # hoist the scaling + pipeline headlines next to the other
            # plane keys (tp_scaling_eff = speedup/tp; pp_bubble_frac =
            # steady-state starved-read fraction of the 2-stage gang)
            for key in ("tp_scaling_eff", "pp_bubble_frac",
                        "decode_tok_s_pp", "pp_green"):
                if key in serve_tp:
                    result["detail"][key] = serve_tp[key]
        except Exception as e:  # noqa: BLE001
            result["detail"]["serve_tp"] = {"error": repr(e)[:200]}

    # 5. cross-host data plane: bulk-stream pull GB/s vs the RPC
    # fallback (object_pull_gb_s key), same time guard
    if time.perf_counter() - start < 440:
        try:
            transfer = bench_transfer()
            result["detail"]["transfer"] = transfer
            if "object_pull_gb_s" in transfer:
                result["detail"]["object_pull_gb_s"] = \
                    transfer["object_pull_gb_s"]
        except Exception as e:  # noqa: BLE001
            result["detail"]["transfer"] = {"error": repr(e)[:200]}

    # 6. KV-cache plane: prefill→decode handoff GB/s (bulk vs RPC) +
    # tiny-PD TTFT breakdown (pd_handoff keys), same time guard
    if time.perf_counter() - start < 460:
        try:
            pd = bench_pd_handoff()
            result["detail"]["pd_handoff"] = pd
            if "kv_handoff_gb_s" in pd:
                result["detail"]["kv_handoff_gb_s"] = pd["kv_handoff_gb_s"]
        except Exception as e:  # noqa: BLE001
            result["detail"]["pd_handoff"] = {"error": repr(e)[:200]}

    # 7. compiled-graph data plane: per-step latency + cross-host stage
    # handoff GB/s, compiled channels vs the actor-RPC DAG path
    # (dag_step_us / dag_handoff_gb_s keys), same time guard
    if time.perf_counter() - start < 470:
        try:
            dag = bench_dag()
            result["detail"]["dag_pipeline"] = dag
            for key in ("dag_step_us", "dag_handoff_gb_s"):
                if key in dag:
                    result["detail"][key] = dag[key]
        except Exception as e:  # noqa: BLE001
            result["detail"]["dag_pipeline"] = {"error": repr(e)[:200]}

    # 7b. streaming data plane: time-to-first-batch streamed vs
    # materialized, sustained rows/s, bounded peak store fill, and
    # two-consumer streaming_split throughput (data_* keys), same guard
    if time.perf_counter() - start < 475:
        try:
            stream = bench_data_streaming()
            result["detail"]["data_streaming"] = stream
            for key in ("data_rows_per_s", "data_ttfb_ms",
                        "data_ttfb_speedup", "data_peak_store_frac"):
                if key in stream:
                    result["detail"][key] = stream[key]
        except Exception as e:  # noqa: BLE001
            result["detail"]["data_streaming"] = {"error": repr(e)[:200]}

    # 8. failure drill: controller restart + node death recovery times
    # (chaos_drill keys), same time guard — robustness alongside speed
    if time.perf_counter() - start < 480:
        try:
            drill = bench_chaos_drill()
            result["detail"]["chaos_drill"] = drill
            for key in ("recovery_controller_ms",
                        "recovery_node_death_ms",
                        "recovery_controller_persist_ms",
                        "recovery_pp_rank_ms",
                        "persist_drill_green", "chaos_drills_green",
                        "pp_drill_green"):
                if key in drill:
                    result["detail"][key] = drill[key]
        except Exception as e:  # noqa: BLE001
            result["detail"]["chaos_drill"] = {"error": repr(e)[:200]}
            result["detail"]["chaos_drills_green"] = False

    # 8b. overload drill: the Serve admission plane at 1x-10x offered
    # load (serve_goodput_rps / serve_shed_rate / serve_admitted_p99_ms
    # keys), same time guard — graceful degradation alongside recovery
    if time.perf_counter() - start < 480:
        try:
            overload = bench_overload_drill()
            result["detail"]["overload_drill"] = overload
            for key in ("serve_capacity_rps", "serve_goodput_rps",
                        "serve_shed_rate", "serve_admitted_p99_ms",
                        "serve_untyped_timeouts", "overload_green"):
                if key in overload:
                    result["detail"][key] = overload[key]
        except Exception as e:  # noqa: BLE001
            result["detail"]["overload_drill"] = {"error": repr(e)[:200]}
            result["detail"]["overload_green"] = False

    # 8c. engine scheduler A/B: chunked-prefill interleave + speculative
    # decoding (engine_sched keys), same time guard — the inference
    # engine's raw-speed trend line next to decode_tok_s / pd_ttft_ms
    if time.perf_counter() - start < 480:
        try:
            sched = bench_engine_sched()
            result["detail"]["engine_sched"] = sched
            for key in ("decode_tok_s_cb", "itl_ms_p99",
                        "ttft_ms_p99_longmix", "ttft_longmix_speedup",
                        "spec_accept_rate", "spec_tok_s", "spec_exact"):
                if key in sched:
                    result["detail"][key] = sched[key]
        except Exception as e:  # noqa: BLE001
            result["detail"]["engine_sched"] = {"error": repr(e)[:200]}

    # 8d. tiered object store: broadcast-tree A/B under the modeled
    # uplink, spill/restore throughput, memory-pressure storm drill
    # (broadcast_* / spill_* keys), same time guard
    if time.perf_counter() - start < 480:
        try:
            tier = bench_broadcast_spill()
            result["detail"]["broadcast_spill"] = tier
            for key in ("broadcast_gb_s", "broadcast_ab_speedup",
                        "spill_restore_mb_s", "spill_storm_green"):
                if key in tier:
                    result["detail"][key] = tier[key]
            if "spill_storm_green" not in tier:
                result["detail"]["spill_storm_green"] = False
        except Exception as e:  # noqa: BLE001
            result["detail"]["broadcast_spill"] = {"error": repr(e)[:200]}
            result["detail"]["spill_storm_green"] = False

    # 8e. scheduler scale envelope: the 100-node in-process harness
    # (many_tasks / many_actors / many_pgs throughput, O(changed)
    # gossip fan-out) + the warm-standby controller failover drill
    # (recovery_controller_failover_ms, zero actor re-creation), same
    # time guard
    if time.perf_counter() - start < 480:
        try:
            scale = bench_scale_envelope()
            result["detail"]["scale_envelope"] = scale
            for key in ("many_tasks_per_s", "many_actors_per_s",
                        "many_pgs_per_s", "gossip_entries_per_beat",
                        "recovery_controller_failover_ms",
                        "failover_drill_green", "scale_envelope_green"):
                if key in scale:
                    result["detail"][key] = scale[key]
            if "failover_drill_green" not in scale:
                result["detail"]["failover_drill_green"] = False
        except Exception as e:  # noqa: BLE001
            result["detail"]["scale_envelope"] = {"error": repr(e)[:200]}
            result["detail"]["failover_drill_green"] = False

    # 9. static analysis: rtpulint per-file rules over the WHOLE package
    # (cheap, ~2s). lint_clean records when the tree regresses on a
    # concurrency invariant; unsuppressed_findings is the count behind it.
    import os as _os

    _repo = _os.path.dirname(_os.path.abspath(__file__))
    try:
        from tools.rtpulint import run as _lint_run

        _findings, _ = _lint_run([_os.path.join(_repo, "ray_tpu")])
        _bad = sum(1 for f in _findings if not f.suppressed)
        result["detail"]["lint_clean"] = _bad == 0
        result["detail"]["lint_unsuppressed_findings"] = _bad
    except Exception as e:  # noqa: BLE001
        result["detail"]["lint_clean"] = False
        result["detail"]["lint_unsuppressed_findings"] = -1
        result["detail"]["lint_error"] = repr(e)[:200]

    # 10. protocol analysis: the rtpuproto whole-program pass
    # (RTPU101-106) over the package with tests/benchmarks as evidence.
    # proto_clean regresses when an RPC edge, failure classification,
    # fault-rule string, config knob or metric name goes stale.
    try:
        from tools.rtpulint.proto import default_aux_paths as _aux
        from tools.rtpulint.proto import run_proto as _proto_run

        _pkg = _os.path.join(_repo, "ray_tpu")
        _pfindings, _ = _proto_run([_pkg], aux_paths=_aux(_pkg))
        _pbad = sum(1 for f in _pfindings if not f.suppressed)
        result["detail"]["proto_clean"] = _pbad == 0
        result["detail"]["proto_unsuppressed_findings"] = _pbad
    except Exception as e:  # noqa: BLE001
        result["detail"]["proto_clean"] = False
        result["detail"]["proto_unsuppressed_findings"] = -1
        result["detail"]["proto_error"] = repr(e)[:200]
    print(json.dumps(result))


if __name__ == "__main__":
    _T0 = time.perf_counter()
    main()
