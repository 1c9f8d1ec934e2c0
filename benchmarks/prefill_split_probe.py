"""What a prefill pass costs on the chip, fresh and resumed, bucket by
bucket, and whether a prompt prefilled in several passes comes out as the
same prompt prefilled whole: what `serve/llm/engine.py:plan_passes` (a
prompt is prefilled in the passes that cost least) rests on.

    python benchmarks/prefill_split_probe.py --cell mistral7b-chat
        [--jobs times,parity] [--parity-lens 1120,1326] [--seed N] [--reps 5]
        [--floor TOKENS]

One cell of BENCHMARK.json a process (`mistral7b-chat`, `mistral7b-docbatch`,
`mixtral-chat`, `minicpm-sala-longdoc`; `tiny-chat` on the CPU to rehearse):
the cell's own runner builds the engine at the configuration's sizes with
the benchmark's seeded weights; the output check and the window are left
out.

`times`: for every length bucket one `[1 x bucket]` pass through
`LLMEngine._dispatch_prefill_batch` and its harvest (the host's dispatch
and the fetch included: what a pass adds to a first token's wait), FRESH
(it starts at 0: the program without a context part; from 1024 tokens up
also with half of the bucket real, the rest padding) and
RESUMED at three contexts (one full pass of the next larger bucket in, as
deep as the model length leaves room for, and a quarter of that: ONE
program, whose context part attends the context a row has). One JSON line a (bucket, start, real): milliseconds, least and
median of `--reps`, the (query block, key block) visits and the pairs in
them that the flash calls make a layer and head
(`ops.paged_attention.prefill_block_visits`), and the pass in tokens' worth
of the largest fresh pass, beside what the engine's `PassCost` says it
costs. The last line: the least fresh pass over the largest one's time a
token (the floor), and for every pass but a bucket's full fresh one the
milliseconds a million pairs it differs by from that one.

`parity` (a dense Llama-family cell): for each length in `--parity-lens`,
(a) the logits at every position of the prompt prefilled in the plan's
passes (each pass the engine's model, params and `serving_cache` with the
program's context part over a pool of the probe's own) and prefilled
whole, each against the float32 reference (`chipbench/references/`) with the
configuration file's limits; (b) 16 greedy tokens through `add_request` /
`step()` with the engine's plan and with the plan forced whole, and how far
the split run's tokens lie under the reference's best logit.

Lines go to standard output and `chiprun_out/prefill_split_probe.jsonl`.
Needs a TPU for times that mean anything; nothing here is a cell's number.
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



def log(msg: str) -> None:
    print(f"[probe {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def build_engine(cell, seed: int):
    """The cell's engine as its runner sets it up, check and warm-up left
    out (each program is built by its first timed use's warm-up rep)."""
    from chipbench import cell as cell_mod

    Runner = cell_mod.load_module("runners", cell.runner).Runner
    runner = Runner(cell, seed, 1.0, log)
    runner._check_outputs = lambda: {}
    runner.setup(warm=False)
    return runner


def one_pass(engine, sb: int, start: int, ids, real=None) -> float:
    """Seconds from the dispatch of one `[1 x sb]` pass that starts at
    `start`, `real` of its tokens a prompt's (None: all), to its harvest.
    The request is the probe's own and never enters the scheduler; its
    pages are the pool's first."""
    from ray_tpu.serve.llm.engine import RUNNING, Request, SamplingParams

    page = engine.config.page_size
    real = sb if real is None else real
    req = Request("probe", list(ids[:start + real]),
                  SamplingParams(max_tokens=1))
    req.pages = list(range(1, 2 + (start + sb) // page))
    req.slot, req.state, req.n_prefilled = 0, RUNNING, start
    t0 = time.perf_counter()
    engine._dispatch_prefill_batch(sb, [(req, real)])
    engine._harvest(engine._inflight.pop(0), [])
    return time.perf_counter() - t0


def job_times(engine, emit, reps: int, seed: int) -> None:
    from ray_tpu.ops.paged_attention import prefill_block_visits

    buckets = list(engine.config.prefill_buckets)
    room = engine.config.max_model_len
    width = engine.max_pages_per_seq * engine.config.page_size
    ids = np.random.default_rng(seed).integers(
        0, engine.model_cfg.vocab_size, room).tolist()
    rows = []
    for i, sb in enumerate(buckets):
        # fresh whole, fresh with half of its query blocks padding, and
        # resumed: the plan's case (behind one pass of the next bucket up,
        # or the largest that leaves room), the deepest start the model
        # length leaves room for, and a quarter of that
        cases = [(0, sb)] + ([(0, sb // 2)] if sb >= 1024 else [])
        deep = (room - sb - 1) // 128 * 128
        fits = [b for b in buckets if b + sb < room]
        if engine._resumes and fits:
            cases += [(start, sb) for start in sorted({
                min(buckets[min(i + 1, len(buckets) - 1)], fits[-1]),
                max(128, deep // 512 * 128), deep})]
        for start, real in cases:
            one_pass(engine, sb, start, ids, real)   # builds the program
            secs = [one_pass(engine, sb, start, ids, real)
                    for _ in range(reps)]
            visits, pairs = prefill_block_visits(
                sb, width if start else 0, real, start)
            rows.append({"bucket": sb, "start": start, "real": real,
                         "ms_min": 1e3 * min(secs),
                         "ms_median": 1e3 * statistics.median(secs),
                         "visits": visits, "pairs": pairs})
    fresh = {r["bucket"]: r for r in rows
             if r["start"] == 0 and r["real"] == r["bucket"]}
    per_token = fresh[buckets[-1]]["ms_min"] / buckets[-1]
    cost = engine._pass_cost
    rates = {}
    for r in rows:
        r["tokens_worth"] = r["ms_min"] / per_token
        if cost is not None:
            r["model_tokens"] = cost(r["bucket"], r["real"], r["start"])
        base = fresh[r["bucket"]]
        if r["pairs"] != base["pairs"]:
            rates["%d@%d/%d" % (r["bucket"], r["start"], r["real"])] = (
                1e6 * (r["ms_min"] - base["ms_min"])
                / (r["pairs"] - base["pairs"]))
        emit("pass", r)
    emit("fit", {
        "ms_a_token_at_largest": per_token,
        "floor_tokens": min(r["ms_min"] for r in fresh.values()) / per_token,
        "engine_cost": cost and vars(cost),
        "ms_a_million_pairs_against_the_fresh_pass": rates})


def logits_in_passes(engine, prompt, plan):
    """float32 logits [len(prompt), V] of `prompt` prefilled in `plan`'s
    passes (full buckets, then the bucket that holds the rest) through the
    engine's model and params, each pass past the first with the
    programs' context part, over a pool of the probe's own."""
    import jax
    import jax.numpy as jnp

    cfg, family = engine.model_cfg, engine.compute.family
    page, mp = engine.config.page_size, engine.max_pages_per_seq
    shape, dtype = family.pool_spec(cfg, cfg.num_layers, 1 + mp, page, 1)
    pool = jnp.zeros(shape, dtype)
    bt = jnp.arange(1, 1 + mp, dtype=jnp.int32)[None]

    def run(params, pool, total, ids, positions, cp):
        pc = family.serving_cache(cfg, pool, bt, total, None, ctx_pages=cp,
                                  ref_attention=False)
        logits, new = engine.model.apply({"params": params}, ids,
                                         positions=positions, kv_caches=pc)
        return logits.astype(jnp.float32), new.pool

    step = jax.jit(run, static_argnums=(5,), donate_argnums=(1,))
    out, start = [], 0
    for sb in plan:
        n = min(sb, len(prompt) - start)
        ids = np.zeros((1, sb), np.int32)
        ids[0, :n] = prompt[start:start + n]
        logits, pool = step(
            engine.params, pool, jnp.asarray([start + n], jnp.int32),
            jnp.asarray(ids), jnp.asarray(start + np.arange(sb)[None]),
            mp if start else 0)
        out.append(np.asarray(logits[0, :n]))
        start += n
    return np.concatenate(out, 0)


def job_parity(runner, emit, lens, seed: int) -> None:
    import jax

    from chipbench import compare, control
    from chipbench.runners.engine import _engine_generate
    from ray_tpu.serve.llm.cache import PageAllocator
    from ray_tpu.serve.llm.engine import PassCost, _bucket, plan_passes

    engine, cell = runner.engine, runner.cell
    ref, cfg = runner.reference, dict(runner.published)
    limits = cell.config["limits"]
    ref_w = ref.weights_from_program_tree(engine.params)
    ref_fwd = jax.jit(lambda w, ids: ref.forward(w, ids, cfg))
    buckets, cost = engine.config.prefill_buckets, engine._pass_cost
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 0x5917])
    for n in lens:
        prompt = rng.integers(0, engine.model_cfg.vocab_size, n).tolist()
        plan = plan_passes(n, buckets, engine.config.page_size, cost)
        want = np.asarray(ref_fwd(ref_w, control.padded(prompt))[0][:n])
        split = logits_in_passes(engine, prompt, plan)
        whole = logits_in_passes(engine, prompt, [_bucket(n, buckets)])
        st0 = engine.stats()
        toks_split = _engine_generate(engine, [prompt], 16)[0]
        st1 = engine.stats()
        # an idle engine: a new allocator forgets the split run's pages, or
        # the whole run would find them by their hashes and prefill nothing
        engine.allocator = PageAllocator(engine.config.num_pages,
                                         engine.config.page_size)
        engine._pass_cost = PassCost(float("inf"), 0.0)   # no split pays
        toks_whole = _engine_generate(engine, [prompt], 16)[0]
        engine._pass_cost = cost
        assert engine.stats()["prefix_token_hits"] == 0
        rows = control.reference_rows(ref, ref_w, cfg, "float32", [prompt],
                                      [toks_split])[0]
        results = {}
        for name, logits in (("split", split), ("whole", whole)):
            check = compare.LogitCheck()
            check.add_logits(logits, want)
            if name == "split":
                check.add_tokens(rows, toks_split)
            res = check.result(limits)
            results[name] = {
                "correct": res["correct"],
                **{r["name"]: r["value"] for r in res["numbers"]},
                "tokens_flipped": res["notes"]["tokens_flipped"]}
        between = compare.LogitCheck()
        between.add_logits(split, whole)
        emit("parity", {
            "prompt_tokens": n, "plan": plan, "limits": limits,
            "split_vs_reference": results["split"],
            "whole_vs_reference": results["whole"],
            "split_vs_whole_rel_rms": between.result(
                {**limits, "logit_rel_rms_err": 1.0,
                 "logit_max_err_over_rms": 1e9})["numbers"][0]["value"],
            "engine_tokens_equal": sum(
                a == b for a, b in zip(toks_split, toks_whole)),
            "engine_tokens": len(toks_whole),
            "split_prompts": st1["prefill_split_prompts_total"]
            - st0["prefill_split_prompts_total"],
            "resumed_passes": st1["prefill_resumed_passes_total"]
            - st0["prefill_resumed_passes_total"]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--jobs", default="times,parity")
    ap.add_argument("--parity-lens", default="1120,1326")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--floor", type=float, default=None,
                    help="a plan by this floor alone, in place of the "
                         "engine's own cost (a tiny cell splits at 4)")
    args = ap.parse_args(argv)

    from chipbench import cell as cell_mod
    from chipbench import run

    cell = cell_mod.load_cell(args.cell)
    import jax

    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = run.device_facts()
    log(f"device {device}")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out",
                            "prefill_split_probe.jsonl"), "a")

    def emit(kind: str, row: dict) -> None:
        line = json.dumps({"cell": cell.name, "config": cell.config_name,
                           "kind": kind, "device": device["kind"], **row})
        print("PROBE " + line, flush=True)
        out.write(line + "\n")
        out.flush()

    runner = build_engine(cell, args.seed)
    engine = runner.engine
    if args.floor is not None:
        from ray_tpu.serve.llm.engine import PassCost

        engine._pass_cost = PassCost(args.floor, 0.0)
    log(f"engine: buckets {engine.config.prefill_buckets} page "
        f"{engine.config.page_size} {engine._pass_cost}")
    jobs = args.jobs.split(",")
    if "times" in jobs:
        job_times(engine, emit, args.reps, args.seed)
    if "parity" in jobs:
        if cell.runner != "engine":
            log(f"parity: runner {cell.runner!r} has no dense reference "
                f"here; skipped")
        else:
            job_parity(runner, emit,
                       [int(x) for x in args.parity_lens.split(",") if x],
                       args.seed)
    while engine.has_work():
        engine.step()
    jax.block_until_ready(engine.compute.kv_pages)
    return 0


if __name__ == "__main__":
    sys.exit(main())
