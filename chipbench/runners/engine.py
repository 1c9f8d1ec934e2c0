"""Runner `engine`: the in-process `LLMEngine` (paged KV, continuous
batching) under a serving traffic mix.

Set-up: weights from the seed (chipbench/weights.py) handed to the engine
as its `params`; `engine.warmup` on the cell's own buckets; the output
check against the plain reference. Window: one thread offers the schedule
(open loop or backlog) and drives `engine.step()`.

The configuration file sizes the deployment (`engine`: page_size,
num_pages, max_model_len, max_batch, prefill_buckets); a mix may force only
the lengths (`engine.max_model_len`, `engine.prefill_buckets` in the mix
file). Scheduling knobs are never set here: they stay at the program's
defaults.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from chipbench import compare, control, generator, stats, weights
from chipbench.cell import BenchError, Cell, load_module
from chipbench.stats import RequestRecord

# what a mix may force of the configuration's engine sizes: its lengths
FORCED_BY_MIX = ("max_model_len", "prefill_buckets")


PUBLISHED_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
                  "num_hidden_layers", "num_attention_heads",
                  "num_key_value_heads", "head_dim", "rope_theta",
                  "rms_norm_eps")


def published_keys(config: Dict[str, Any]) -> Dict[str, Any]:
    """The architecture's keys, which sit at the top level of a
    configuration file under their config.json names."""
    return {k: config[k] for k in PUBLISHED_KEYS if k in config}


def model_overrides(published: Dict[str, Any]) -> Dict[str, Any]:
    """The published config.json keys as the program's LlamaConfig names."""
    return {
        "vocab_size": published["vocab_size"],
        "hidden_size": published["hidden_size"],
        "intermediate_size": published["intermediate_size"],
        "num_layers": published["num_hidden_layers"],
        "num_heads": published["num_attention_heads"],
        "num_kv_heads": published["num_key_value_heads"],
        "head_dim": published.get("head_dim"),
        "rope_theta": float(published["rope_theta"]),
        "rms_norm_eps": float(published["rms_norm_eps"]),
    }


class Runner:
    def __init__(self, cell: Cell, seed: int, seconds: float, log):
        self.cell, self.seed, self.seconds, self.log = cell, seed, seconds, log
        self.mix = cell.traffic
        sizes = dict(cell.config["engine"])
        forced = self.mix.get("engine", {})
        unknown = set(forced) - set(FORCED_BY_MIX)
        if unknown:
            raise BenchError(f"mix {cell.traffic_name} sets {unknown}: a mix "
                             f"may only force {FORCED_BY_MIX}; other sizes "
                             f"make another configuration")
        sizes.update(forced)
        self.sizes = sizes
        self.published = published_keys(cell.config)
        self.reference = load_module("references", cell.config["reference"])
        self.samples: Dict[str, List[float]] = {
            "running": [], "waiting": [], "free_pages": [], "t": []}
        self.counters: Dict[str, Any] = {}
        self.records: List[RequestRecord] = []

    # ------------------------------------------------------------ set-up
    def setup(self, warm: bool = True) -> Dict[str, Any]:
        """`warm=False` (tools/read_limits.py only) skips the warm-up of
        the window's programs: the check loads the ones it needs."""
        import jax

        from ray_tpu.serve.llm import EngineConfig, LLMEngine

        sz = self.sizes
        econf = EngineConfig(
            model=self.cell.config["program_preset"],
            model_overrides=model_overrides(self.published),
            dtype=self.cell.config["dtype"], page_size=sz["page_size"],
            num_pages=sz["num_pages"], max_model_len=sz["max_model_len"],
            max_batch=sz["max_batch"],
            prefill_buckets=tuple(sz["prefill_buckets"]),
            eos_token_id=None,      # lengths are the generator's
            seed=self.seed & 0x7FFFFFFF)
        # the program's tree layout, shapes only; then the benchmark's own
        # weights in that layout
        t0 = time.monotonic()
        probe = _shape_probe(econf)
        params = weights.make_params(probe, self.seed)
        jax.block_until_ready(params)
        self.log(f"weights: {sum(x.size for x in jax.tree.leaves(params)):,}"
                 f" params made on the device in {time.monotonic()-t0:.1f} s")
        t0 = time.monotonic()
        self.engine = engine = LLMEngine(econf, params=params)
        self.vocab = engine.model_cfg.vocab_size
        self.log(f"engine: {engine.stats()['attention']} "
                 f"wave_rows={engine._wave_rb} "
                 f"decode_steps_per_dispatch="
                 f"{econf.decode_steps_per_dispatch} pipeline_depth="
                 f"{econf.pipeline_depth} (program defaults)")
        if warm:
            n = engine.warmup(prompt_buckets=tuple(sz["prefill_buckets"]))
            self.log(f"warm-up: {n} programs in {time.monotonic()-t0:.1f} s")
        self.schedule = generator.make_schedule(
            self.mix, self.seed, self.seconds, self.vocab)
        t0 = time.monotonic()
        check = self._check_outputs()
        self.log(f"output check in {time.monotonic()-t0:.1f} s")
        return check

    def _check_outputs(self) -> Dict[str, Any]:
        """Outside the timed window, against the plain reference: (A) the
        logits of prefill + token-by-token decode through the engine's own
        model, params and a PagedCache, at every position; (B) the tokens
        the ENGINE emits, greedy, through add_request/step() (the programs
        that are timed) for a batch of seeded prompts, each judged by the
        reference's logits on the engine's own sequence."""
        import jax

        spec = self.mix["check"]
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 0xC4EC])
        prompts = [rng.integers(0, self.vocab, int(n)).tolist()
                   for n in spec["prompt_lens"]]
        ep = spec["engine_prompts"]
        eprompts = [rng.integers(0, self.vocab, int(n)).tolist()
                    for n in np.rint(np.linspace(ep["min_len"], ep["max_len"],
                                                 ep["count"]))]
        emitted = _engine_generate(self.engine, eprompts,
                                   int(ep["decode_tokens"]))
        prog_logits, fed = _paged_logits(self.engine, prompts,
                                         int(spec["decode_tokens"]))
        cfg = dict(self.published)
        ref_w = self.reference.weights_from_program_tree(self.engine.params)
        ref_fwd = jax.jit(lambda w, ids: self.reference.forward(w, ids, cfg))
        out = compare.LogitCheck()
        for p, toks, logits in zip(prompts, fed, prog_logits):
            seq = p + toks[:-1]
            out.add_logits(logits, np.asarray(
                ref_fwd(ref_w, control.padded(seq))[0][:len(seq)]))
        for rows, toks in zip(control.reference_rows(
                self.reference, ref_w, cfg, "float32", eprompts, emitted),
                emitted):
            out.add_tokens(rows, toks)
        # what the control is read on (control.serve_numbers)
        self.check_sample = {
            "logit_seqs": list(zip(prompts, fed)),
            "engine_prompts": eprompts, "engine_tokens": emitted}
        return out.result(self.cell.config["limits"])

    # ------------------------------------------------------------ window
    def run_window(self, tracer) -> None:
        """Offer the schedule and drive the engine. Times are relative to
        the window's start, which is `ramp_s` after the first request."""
        from jax.profiler import TraceAnnotation

        from ray_tpu.serve.llm import SamplingParams

        engine, sched, seconds = self.engine, self.schedule, self.seconds
        ramp = float(self.mix.get("ramp_s", 0))
        grace = float(self.mix.get("grace_s", 0))
        recs = {r.rid: RequestRecord(
            rid=r.rid, due_s=r.due_s, counted=r.counted,
            prompt_tokens=len(r.prompt_ids), max_tokens=r.max_tokens)
            for r in sched}
        sampling = {r.rid: SamplingParams(max_tokens=r.max_tokens,
                                          temperature=0.0) for r in sched}
        open_counted = sum(1 for r in sched if r.counted)
        backlog = self.mix["arrivals"]["process"] == "backlog"
        stats0 = engine.stats()
        samples = self.samples
        token_events = self.token_events = []
        i, n = 0, len(sched)
        t0 = self.t0 = time.monotonic() + ramp
        while True:
            now = time.monotonic() - t0
            if now >= seconds and (backlog or open_counted == 0
                                   or now >= seconds + grace):
                break
            tracer.poll(now)
            while i < n and sched[i].due_s <= now:
                r = sched[i]
                engine.add_request(r.rid, r.prompt_ids, sampling[r.rid])
                recs[r.rid].sent_s = now
                i += 1
            if engine.has_work():
                with TraceAnnotation("chipbench.engine.step"):
                    deltas = engine.step()
                tnow = time.monotonic() - t0
                for d in deltas:
                    rec = recs[d.request_id]
                    if d.new_token_ids:
                        if rec.first_s is None:
                            rec.first_s = tnow
                        rec.out_tokens += len(d.new_token_ids)
                        token_events.append((tnow, len(d.new_token_ids)))
                    if d.finished:
                        rec.finish_s = tnow
                        rec.finish_reason = d.finish_reason
                        if rec.counted:
                            open_counted -= 1
                if 0.0 <= tnow < seconds:
                    st = engine.stats()
                    samples["t"].append(tnow)
                    samples["running"].append(st["running"])
                    samples["waiting"].append(st["waiting"])
                    samples["free_pages"].append(st["free_pages"])
            else:
                wait = (sched[i].due_s - now) if i < n else 0.005
                with TraceAnnotation("chipbench.gen.wait"):
                    time.sleep(max(0.0, min(wait, 0.005)))
        self.window_end = time.monotonic() - t0
        tracer.finish()
        st = engine.stats()
        self.counters = {
            "num_pages": self.sizes["num_pages"],
            "preempted": st["preempted_total"] - stats0["preempted_total"],
            "expired": st["expired_total"] - stats0["expired_total"],
            "prefix_token_hits": st["prefix_token_hits"],
            "prefix_token_lookups": st["prefix_token_lookups"],
            "steps_sampled": len(samples["t"]),
            "sent": i, "scheduled": n,
        }
        self.records = list(recs.values())
        if backlog and i >= n and not engine.has_work():
            raise BenchError(
                f"the backlog of {n} requests ran dry before the window "
                f"ended: raise arrivals.max_rate_per_s in the mix")
        # leave the engine idle: nothing of this run is timed any more
        for rec in self.records:
            if rec.sent_s is not None and rec.finish_s is None:
                engine.abort(rec.rid)
        engine.step()

    # ----------------------------------------------------------- results
    def counts(self) -> Dict[str, int]:
        """attempted / failed as the cell counts them."""
        if self.mix["arrivals"]["process"] == "backlog":
            started = [r for r in self.records if r.first_s is not None
                       and r.first_s <= self.seconds]
            failed = [r for r in started
                      if r.finish_s is not None and not r.ok]
            return {"attempted": len(started), "failed": len(failed)}
        counted = [r for r in self.records if r.counted]
        return {"attempted": len(counted),
                "failed": sum(1 for r in counted if not r.ok)}

    def window_seconds(self) -> float:
        return self.seconds

    def tokens_completed(self) -> int:
        """Tokens processed inside the window (stats.py says which)."""
        return stats.processed_tokens(self.records, self.token_events,
                                      0.0, self.seconds)

    def work_facts(self) -> Dict[str, Any]:
        return {"kind": "serve"}


# ------------------------------------------------------------------ helpers
def _shape_probe(econf):
    """The program's parameter tree as shapes (no arrays are made)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaModel, get_config

    dtype = jnp.bfloat16 if econf.dtype == "bfloat16" else jnp.float32
    cfg = get_config(econf.model, scan_layers=True, remat=False, dtype=dtype,
                     param_dtype=dtype, max_seq_len=econf.max_model_len,
                     **econf.model_overrides)
    return jax.eval_shape(lambda: nn.meta.unbox(LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))


def _engine_generate(engine, prompts: List[List[int]], g: int
                     ) -> List[List[int]]:
    from ray_tpu.serve.llm import SamplingParams

    out: Dict[str, List[int]] = {f"check{i}": [] for i in range(len(prompts))}
    for i, p in enumerate(prompts):
        engine.add_request(f"check{i}", p, SamplingParams(
            max_tokens=g, temperature=0.0))
    done, deadline = 0, time.monotonic() + 300
    while done < len(prompts):
        if time.monotonic() > deadline:
            raise BenchError("the engine did not finish the check prompts")
        for d in engine.step():
            out[d.request_id].extend(d.new_token_ids)
            done += bool(d.finished)
    return [out[f"check{i}"] for i in range(len(prompts))]


def _paged_logits(engine, prompts: List[List[int]], g: int):
    """Prefill, then decode g-1 tokens one at a time, through the engine's
    model and params and a small PagedCache pool of the benchmark's own
    (same layout, page size and kernels as the engine's). Returns per
    sequence the float32 logits at every position [p + g - 1, V] and the g
    greedy tokens that were fed back."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import PagedCache

    model, mc, page = engine.model, engine.model_cfg, engine.config.page_size
    L, b = mc.num_layers, len(prompts)
    longest = max(len(p) for p in prompts)
    sb = -(-longest // 128) * 128
    mp = -(-(longest + g + 1) // page)
    mp = -(-mp // 8) * 8
    pool = jnp.zeros((L, 1 + b * mp, mc.num_kv_heads, page,
                      2 * mc.head_dim_), engine.kv_pages.dtype)
    bt = np.arange(1, 1 + b * mp, dtype=np.int32).reshape(b, mp)

    def run(params, kv_pages, bt, total, ids, positions):
        pc = PagedCache(
            kv_pages=kv_pages,
            block_tables=jnp.broadcast_to(bt, (L,) + bt.shape),
            total_lens=jnp.broadcast_to(total, (L,) + total.shape))
        logits, new_pc = model.apply({"params": params}, ids,
                                     positions=positions, kv_caches=pc)
        return logits.astype(jnp.float32), new_pc.kv_pages

    step = jax.jit(run, donate_argnums=(1,))
    ids = np.zeros((b, sb), np.int32)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
    positions = np.broadcast_to(np.arange(sb, dtype=np.int32), (b, sb))
    logits, pool = step(engine.params, pool, jnp.asarray(bt),
                        jnp.asarray(lens), jnp.asarray(ids),
                        jnp.asarray(positions))
    logits = np.asarray(logits)
    rows = [[logits[i, :n]] for i, n in enumerate(lens)]
    last = np.stack([logits[i, n - 1] for i, n in enumerate(lens)])
    fed = [[int(t)] for t in last.argmax(-1)]
    for j in range(1, g):
        tok = np.asarray([[f[-1]] for f in fed], np.int32)
        total = lens + j
        lg, pool = step(engine.params, pool, jnp.asarray(bt),
                        jnp.asarray(total), jnp.asarray(tok),
                        jnp.asarray((total - 1)[:, None]))
        lg = np.asarray(lg)[:, 0]
        for i in range(b):
            rows[i].append(lg[i][None])
            fed[i].append(int(lg[i].argmax()))
    del pool
    return [np.concatenate(r, 0) for r in rows], fed
