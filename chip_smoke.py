"""The quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py              # one TPU chip: serving + training
    python chip_smoke.py --multichip  # four chips: only the cross-chip paths

One chip (what the driver runs): a `llama-1b` bf16 replica in its own worker
process answers a few `POST /llm/v1/chat/completions` requests over HTTP
(`ray_tpu.init` -> `serve.run(build_openai_app(LLMConfig(...)))`); an
in-process `LLMEngine` with the same config and seed then has to reproduce
those completions token for token out of the compile cache the replica
filled, and the kernels are compared with their jnp references at the same
widths; then `ShardedTrainer` takes a few steps of `llama-1b` at 3x2048 on a
one-chip mesh and the loss has to be finite and fall. Every compiled program
is shown to contain its Pallas kernel (`tpu_custom_call`).

A chip belongs to one process at a time, so each phase runs in its own child
process and this parent never touches a JAX backend; the serving phase's
driver stays off the chip too, because the replica worker owns it.

There is no CPU arm and no size option: without a TPU the script exits
non-zero with "no TPU found". The rehearsal on the CPU drives the phase
functions at tiny sizes from tests/test_chip_smoke.py (`-m slow`).

The last line of standard output is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`;
per-phase facts go on earlier lines. Any failure exits non-zero and prints
no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
RESULT_MARK = "CHIP_SMOKE_PHASE_RESULT "
KERNEL_MARK = "tpu_custom_call"  # how a Mosaic (Pallas TPU) kernel lowers
EOS = 257  # ByteTokenizer's; both serving phases pin it
LOGITS_RTOL = 0.05  # kernel path vs jnp path, of the largest |logit|, bf16


class SmokeFailure(RuntimeError):
    pass


# ------------------------------------------------------------------ sizes
# Real sizes live here; the CPU rehearsal passes its own (tests/).
@dataclasses.dataclass(frozen=True)
class ServeSizes:
    model: str = "llama-1b"
    model_overrides: tuple = ()  # (key, value) pairs; empty = full model
    dtype: str = "bfloat16"
    page_size: int = 16
    num_pages: int = 2048        # 22 x 2048 x 32 KiB = 1.4 GiB of KV pool
    max_model_len: int = 1024
    max_batch: int = 8
    prefill_buckets: tuple = (128, 512)
    decode_steps_per_dispatch: int = 8
    pipeline_depth: int = 3
    max_tokens: int = 24
    ready_timeout_s: float = 420.0
    # three chats: a short one, a long one (second bucket), and the short
    # one's opening again (prefix-cache hit -> the cached-context program)
    chats: tuple = (
        "Name three uses of a paged KV cache in a serving engine.",
        "Summarise, in order, what happens to a request between the "
        "moment it reaches the HTTP proxy and the moment its first token "
        "is returned: routing by prefix, admission against the page "
        "budget, the prefill wave it joins, sampling on the device, and "
        "the harvest that hands the token back to the waiting coroutine.",
        "Name three uses of a paged KV cache in a serving engine. Then "
        "name a fourth.",
    )
    # the expert model of the engine phase: `tiny-moe` (4 experts, top-2,
    # 2 layers) at widths the chip's tiling accepts
    expert_overrides: tuple = (
        ("hidden_size", 512), ("intermediate_size", 1024), ("num_heads", 4),
        ("num_kv_heads", 2), ("head_dim", 128), ("vocab_size", 512))


@dataclasses.dataclass(frozen=True)
class TrainSizes:
    model: str = "llama-1b"
    model_overrides: tuple = (("remat_policy", "dots"),)
    batch: int = 3
    seq: int = 2048
    steps: int = 8
    lr: float = 3e-4


@dataclasses.dataclass(frozen=True)
class MultichipSizes:
    serve: ServeSizes = ServeSizes(chats=ServeSizes.chats[:2])
    # 2 rows: 4 x 2048 leaves the one-chip arm 0.1 GiB of 15.75 (compile)
    train: TrainSizes = TrainSizes(batch=2, steps=4)
    loss_rtol: float = 2e-2      # (b): per-step |mesh - one chip| / one chip
    big_model: str = "llama3-8b"  # (c): 16 GB of bf16 weights, tp=4
    big_overrides: tuple = ()
    big_num_pages: int = 512
    big_bucket: int = 128


# ----------------------------------------------------------------- checks
# The three places a CPU rehearsal has to steer (it patches them in the
# test; the script itself has no switch for any of them).
def require_tpu(device: Dict[str, Any]) -> None:
    if device.get("platform") != "tpu":
        raise SmokeFailure(f"no TPU found: JAX reports {device}")


def require_kernel(program: str, text: str) -> str:
    if KERNEL_MARK not in text:
        raise SmokeFailure(
            f"{program}: no Pallas kernel ({KERNEL_MARK}) in the lowered "
            f"program — attention fell to a reference path")
    return f"pallas ({text.count(KERNEL_MARK)} x {KERNEL_MARK})"


def require_cache_hits(phase: str, hits: int) -> None:
    if hits < 1:
        raise SmokeFailure(
            f"{phase}: rebuilt programs an earlier phase compiled, and the "
            f"persistent compile cache hit none of them")


def _device_facts() -> Dict[str, Any]:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _engine_config(sz: ServeSizes, **over):
    from ray_tpu.serve.llm import EngineConfig

    return EngineConfig(**{**dict(
        model=sz.model, model_overrides=dict(sz.model_overrides),
        dtype=sz.dtype, page_size=sz.page_size, num_pages=sz.num_pages,
        max_model_len=sz.max_model_len, max_batch=sz.max_batch,
        prefill_buckets=sz.prefill_buckets, eos_token_id=EOS,
        decode_steps_per_dispatch=sz.decode_steps_per_dispatch,
        pipeline_depth=sz.pipeline_depth), **over})


def _chat_prompt_ids(chat: str) -> List[int]:
    """The token ids the ingress makes of a one-message chat."""
    from ray_tpu.serve.llm.server import _render_chat
    from ray_tpu.serve.llm.tokenizer import TokenIdTokenizer

    return TokenIdTokenizer().encode(
        _render_chat([{"role": "user", "content": chat}]))


class _CacheCounter:
    """Counts JAX's persistent-compile-cache hits and misses from here on
    (jax.monitoring events; listeners cannot be removed, so one per
    process)."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_):
        if name.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            self.misses += 1


def _generate(engine, rid: str, prompt_ids: List[int], max_tokens: int,
              temperature: float = 0.0, seed: Optional[int] = None
              ) -> List[int]:
    """One request (greedy unless a temperature is given) run to
    completion on an in-process engine. The engine derives a request's
    sampling keys from its id and seed, so engines that are compared get
    the same ids."""
    from ray_tpu.serve.llm import SamplingParams

    engine.add_request(rid, prompt_ids, SamplingParams(
        max_tokens=max_tokens, temperature=temperature, seed=seed))
    out: List[int] = []
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        for delta in engine.step():
            if delta.request_id == rid:
                out.extend(delta.new_token_ids)
                if delta.finished:
                    return out
    raise SmokeFailure(f"request {rid} did not finish in 300 s")


# ------------------------------------------------------------ phase: serve
def phase_serve(sz: ServeSizes = ServeSizes(), prior=None) -> Dict[str, Any]:
    """The serving path as a user drives it. This process is the cluster's
    driver: it must never initialise a JAX backend, because the replica
    worker — a child of the cluster — owns the chip."""
    import urllib.request

    import psutil

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMConfig, build_openai_app
    from ray_tpu.serve.llm.tokenizer import TokenIdTokenizer

    facts: Dict[str, Any] = {"model": sz.model, "dtype": sz.dtype,
                             "page_size": sz.page_size,
                             "prefill_buckets": list(sz.prefill_buckets)}
    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        config = LLMConfig(
            model_id=sz.model, engine=_engine_config(sz),
            # completions come back as their token ids: the weights are
            # random, there is no vocabulary to render
            tokenizer=TokenIdTokenizer(),
            # the replica is the process that is granted the node's chip
            ray_actor_options={"num_tpus": 1})
        t0 = time.monotonic()
        serve.run(build_openai_app(config), route_prefix="/llm",
                  _start_http=True, wait_timeout_s=sz.ready_timeout_s)
        facts["replica_ready_s"] = round(time.monotonic() - t0, 1)
        stats = serve.get_deployment_handle(
            f"LLMServer:{sz.model}").engine_stats.remote().result(
                timeout_s=60)
        facts["replica_device"] = stats["device"]
        facts["replica_warmup_s"] = stats.get("warmup_s")
        facts["replica_attention"] = stats["attention"]
        facts["replica_is_own_process"] = (
            stats["device"]["pid"] != os.getpid())
        require_tpu(stats["device"])
        if not facts["replica_is_own_process"]:
            raise SmokeFailure("the replica runs in the driver process")

        url = serve.get_proxy_url() + "/llm/v1/chat/completions"
        answers = []
        for chat in sz.chats:
            body = json.dumps({
                "model": sz.model, "max_tokens": sz.max_tokens,
                "temperature": 0,
                "messages": [{"role": "user", "content": chat}]}).encode()
            t0 = time.monotonic()
            with urllib.request.urlopen(urllib.request.Request(
                    url, data=body, method="POST"), timeout=300) as resp:
                status, reply = resp.status, json.loads(resp.read())
            text = reply["choices"][0]["message"]["content"]
            token_ids = [int(t) for t in text.split()]
            usage = reply["usage"]
            if (status != 200 or not token_ids
                    or usage["completion_tokens"] != len(token_ids)):
                raise SmokeFailure(f"bad completion: {status} {reply}")
            answers.append({
                "prompt_tokens": usage["prompt_tokens"],
                "token_ids": token_ids,
                "finish_reason": reply["choices"][0]["finish_reason"],
                "seconds": round(time.monotonic() - t0, 3)})
        facts["requests_answered"] = len(answers)
        facts["answers"] = answers
        stats = serve.get_deployment_handle(
            f"LLMServer:{sz.model}").engine_stats.remote().result(
                timeout_s=60)
        facts["prefix_cache_hit_tokens"] = stats.get("prefix_token_hits")
    finally:
        started = psutil.Process().children(recursive=True)
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
        # the chip is free for the next phase only when the replica is gone
        _, alive = psutil.wait_procs(started, timeout=30)
        for proc in alive:
            proc.kill()
        facts["processes_stopped"] = len(started)
    jax_mod = sys.modules.get("jax")
    if jax_mod is not None:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise SmokeFailure(
                "the serving driver initialised a JAX backend: it would "
                "take the chip from its own replica")
    facts["driver_imported_jax"] = jax_mod is not None
    facts["driver_touched_backend"] = False
    return facts


# ----------------------------------------------------------- phase: engine
def phase_engine(sz: ServeSizes = ServeSizes(),
                 prior: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The same engine in this process, which owns the chip now: reproduce
    the replica's completions from the compile cache it filled, show the
    kernels in the programs, compare them with their references."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm import LLMEngine

    counter = _CacheCounter()
    facts: Dict[str, Any] = {"device": _device_facts()}
    require_tpu(facts["device"])

    t0 = time.monotonic()
    engine = LLMEngine(_engine_config(sz))
    jax.block_until_ready(engine.params)
    facts["engine_init_s"] = round(time.monotonic() - t0, 1)
    t0 = time.monotonic()
    facts["programs_warmed"] = engine.warmup()
    facts["warmup_s_cache_warm"] = round(time.monotonic() - t0, 1)
    facts["compile_cache"] = {"hits": counter.hits, "misses": counter.misses}
    require_cache_hits("engine", counter.hits)

    rb, mp = engine._wave_rb, engine.max_pages_per_seq
    facts["attention"] = {
        "decode": require_kernel(
            "decode", engine.program_text("decode",
                                          engine._decode_shape_key())),
        "prefill": require_kernel(
            "prefill", engine.program_text(
                "prefill", (sz.prefill_buckets[0], rb, 0))),
        "prefill_cached_ctx": require_kernel(
            "prefill with cached context", engine.program_text(
                "prefill", (sz.prefill_buckets[0], rb, mp)))}

    expected = [a["token_ids"] for a in (prior or {}).get(
        "serve", {}).get("answers", [])]
    got = [_generate(engine, f"chat{i}", _chat_prompt_ids(chat),
                     sz.max_tokens) for i, chat in enumerate(sz.chats)]
    vocab = engine.model_cfg.vocab_size
    if not all(toks and all(0 <= t < vocab for t in toks) for toks in got):
        raise SmokeFailure(f"engine produced no or out-of-range ids: {got}")
    if expected and got != expected:
        raise SmokeFailure(
            f"in-process engine and HTTP replica disagree (same config, "
            f"seed and requests): {got} != {expected}")
    facts["completions_match_http"] = bool(expected)

    # the whole model's dense forward (the trainer's attention path) on a
    # real prompt, kernels against the pure-jnp reference path: logits of
    # the expected shape, all finite, agreeing within bf16 noise, and
    # naming the token the paged prefill sampled first
    import dataclasses as dc

    from ray_tpu.models.llama import LlamaModel

    ids = jnp.asarray([_chat_prompt_ids(sz.chats[0])], jnp.int32)

    def forward(impl):
        model = LlamaModel(dc.replace(engine.model_cfg, attention_impl=impl))
        return jax.jit(lambda p, x: model.apply({"params": p}, x))(
            engine.params, ids).astype(jnp.float32)

    logits, ref = forward(None), forward("reference")
    scale = float(jnp.max(jnp.abs(ref)))
    facts["logits"] = {
        "shape": list(logits.shape),
        "finite": bool(jnp.isfinite(logits).all()),
        "max_abs": scale,
        "max_abs_err_vs_reference": float(jnp.max(jnp.abs(logits - ref))),
        "argmax_last_is_first_token": int(jnp.argmax(logits[0, -1]))
        == got[0][0]}
    if (logits.shape != (1, ids.shape[1], vocab)
            or not facts["logits"]["finite"]
            or not facts["logits"]["argmax_last_is_first_token"]
            or not facts["logits"]["max_abs_err_vs_reference"]
            <= LOGITS_RTOL * scale):
        raise SmokeFailure(f"bad logits: {facts['logits']}")

    facts["kernel_vs_reference_max_abs_err"] = _kernel_parity(
        engine.model_cfg, sz.page_size)
    del engine
    facts["experts"] = _expert_engine(sz)
    return facts


def _expert_engine(sz: ServeSizes, tol: float = 3e-2) -> Dict[str, Any]:
    """A sparse-expert model through the same engine: the dropless expert
    layer's grouped matmul (ops/grouped_matmul.py) is in the decode
    program, agrees with a plain loop over the experts on the device, and
    the engine's routing counters count every real token k x L times."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.grouped_matmul import grouped_matmul
    from ray_tpu.serve.llm import LLMEngine

    engine = LLMEngine(_engine_config(
        sz, model="tiny-moe", model_overrides=dict(sz.expert_overrides)))
    cfg = engine.model_cfg
    out = {"kernel": require_kernel(
        "expert decode", engine.program_text("decode",
                                             engine._decode_shape_key()))}
    prompt = _chat_prompt_ids(sz.chats[0])
    toks = _generate(engine, "experts", prompt, sz.max_tokens)
    if not toks or not all(0 <= t < cfg.vocab_size for t in toks):
        raise SmokeFailure(f"expert engine produced bad ids: {toks}")
    # every prompt token and every fed-back token passes k experts in each
    # layer; chunks computed past a stop are counted too, so >=
    least = ((len(prompt) + len(toks) - 1) * cfg.num_experts_per_tok
             * cfg.num_layers)
    out["moe_assignments_total"] = engine.stats()["moe_assignments_total"]
    if out["moe_assignments_total"] < least:
        raise SmokeFailure(f"routing counters lost tokens: {out} < {least}")

    # the grouped matmul against a loop over the experts, in the engine's
    # type: a whole [L, E, K, N] stack read by layer, uneven groups, an
    # empty one, and a tail of rows that belongs to no expert (what the
    # kernel leaves there is undefined, so only the groups' rows compare)
    E, h, f = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
    dtype = engine.params["embed"].dtype
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    sizes = [37, 0, 150] + [11] * (E - 3)
    m = sum(sizes) + 58
    lhs = jax.random.normal(keys[0], (m, h), dtype)
    rhs = jax.random.normal(keys[1], (2, E, h, 2 * f), dtype) * h ** -0.5
    got = jax.jit(grouped_matmul)(lhs, rhs, jnp.asarray(sizes),
                                  jnp.int32(1)).astype(jnp.float32)
    got = got[:sum(sizes)]
    want, at = jnp.zeros_like(got), 0
    with jax.default_matmul_precision("highest"):
        for e, n in enumerate(sizes):
            want = want.at[at:at + n].set(
                lhs[at:at + n].astype(jnp.float32)
                @ rhs[1, e].astype(jnp.float32))
            at += n
    out["grouped_matmul_max_abs_err"] = float(jnp.max(jnp.abs(got - want)))
    if not out["grouped_matmul_max_abs_err"] <= tol * float(
            jnp.max(jnp.abs(want))):
        raise SmokeFailure(f"grouped matmul disagrees with the loop: {out}")
    return out


def _kernel_parity(cfg, page: int, tol: float = 3e-2) -> Dict[str, float]:
    """Flash and paged-decode kernels against their jnp references on the
    device, at the model's head widths, bf16, seeded inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import attention, reference_attention
    from ray_tpu.ops.paged_attention import (paged_attention_decode,
                                             paged_attention_reference)

    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    keys = jax.random.split(jax.random.PRNGKey(0), 5)

    def rnd(key, *shape):
        return jax.random.normal(key, shape, jnp.bfloat16)

    def err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    s = 4 * 128
    q, k, v = rnd(keys[0], 2, s, hq, d), rnd(keys[1], 2, s, hkv, d), \
        rnd(keys[2], 2, s, hkv, d)
    out = {"flash": err(jax.jit(attention)(q, k, v),
                        jax.jit(reference_attention)(q, k, v))}

    # a layered pool, as the engine holds it: the kernel streams pages of
    # the layer it is told, the reference reads that layer alone
    b, mp, layers, layer = 8, 16, 3, 1
    kv_pages = rnd(keys[3], layers, b * mp, hkv, page, 2 * d)
    tables = jnp.asarray(np.random.default_rng(0).permutation(b * mp)
                         .reshape(b, mp), jnp.int32)
    lengths = jnp.asarray([1, page, page + 1, 3 * page, mp * page - 1,
                           mp * page, 7, 0], jnp.int32)
    qd = rnd(keys[4], b, hq, d)
    ref = paged_attention_reference(
        qd[:, None], kv_pages[layer], tables,
        jnp.maximum(lengths - 1, 0)[:, None])[:, 0]
    ref = jnp.where((lengths > 0)[:, None, None], ref, 0)
    out["paged_decode"] = err(
        jax.jit(paged_attention_decode)(qd, kv_pages, tables, lengths,
                                        layer=jnp.int32(layer)), ref)
    bad = {name: e for name, e in out.items() if not e <= tol}
    if bad:
        raise SmokeFailure(f"kernel disagrees with its reference: {bad}")
    return out


# ------------------------------------------------------------ phase: train
def _train_steps(sz: TrainSizes, mesh_config, devices) -> Dict[str, Any]:
    """A few steps on one fixed seeded batch; returns losses and facts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import LlamaModel, get_config
    from ray_tpu.parallel.mesh import create_mesh
    from ray_tpu.parallel.train_lib import ShardedTrainer, default_optimizer

    cfg = get_config(sz.model, param_dtype=jnp.bfloat16,
                     **dict(sz.model_overrides))
    # a short warm-up: the smoke takes a handful of steps, not thousands
    trainer = ShardedTrainer(
        LlamaModel(cfg), create_mesh(mesh_config, devices=devices),
        optimizer=default_optimizer(lr=sz.lr, warmup=2, total_steps=100))
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (sz.batch, sz.seq), dtype=np.int32)}
    state = trainer.init(jax.random.PRNGKey(0), batch)
    attention = require_kernel("train step",
                               trainer.program_text(state, batch))
    losses, seconds = [], []
    for _ in range(sz.steps):
        t0 = time.monotonic()
        state, metrics = trainer.step(state, batch)
        jax.block_until_ready(metrics["loss"])
        seconds.append(round(time.monotonic() - t0, 3))
        losses.append(float(metrics["loss"]))
    if not all(map(math.isfinite, losses)) or not losses[-1] < losses[0]:
        raise SmokeFailure(f"loss must be finite and fall: {losses}")
    return {"mesh": {a: n for a, n in trainer.mesh.shape.items() if n > 1},
            "batch": [sz.batch, sz.seq], "steps": sz.steps,
            "params": int(sum(x.size for x in jax.tree.leaves(state.params))),
            "attention": attention, "loss": losses,
            "first_step_s_with_compile": seconds[0],
            "step_s": seconds[1:]}


def phase_train(sz: TrainSizes = TrainSizes(), prior=None) -> Dict[str, Any]:
    import jax

    from ray_tpu.parallel.mesh import MeshConfig

    facts: Dict[str, Any] = {"device": _device_facts(), "model": sz.model}
    require_tpu(facts["device"])
    facts.update(_train_steps(
        sz, MeshConfig(dp=1, fsdp=1, sp=1, tp=1), jax.devices()[:1]))
    return facts


# -------------------------------------------------------- phase: multichip
def phase_multichip(sz: MultichipSizes = MultichipSizes(),
                    prior=None) -> Dict[str, Any]:
    """Only what exists across chips, and what it is compared with, in one
    process that drives all four chips. Every sub-phase runs; the phase
    fails at the end if any of them did."""
    import gc

    import jax

    facts: Dict[str, Any] = {"device": _device_facts()}
    require_tpu(facts["device"])
    if facts["device"]["count"] < 4:
        raise SmokeFailure(f"--multichip needs 4 chips: {facts['device']}")
    failed = []
    for name, fn in (("a_tp4_vs_tp1_tokens", _multichip_tokens),
                     ("b_fsdp2_tp2_vs_one_chip_loss", _multichip_loss),
                     ("c_8b_tp4_engine", _multichip_big_engine)):
        t0 = time.monotonic()
        try:
            sub = fn(sz)
        except Exception as e:  # noqa: BLE001 — every sub-phase reports
            import traceback

            traceback.print_exc()
            sub = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        sub["seconds"] = round(time.monotonic() - t0, 1)
        if not sub["ok"]:
            failed.append(name)
        # the sub-phase's facts go out once, here; the phase's own result
        # line keeps only the verdicts
        _emit({"phase": "multichip", "sub_phase": name, **sub})
        facts[name] = {"ok": sub["ok"], "seconds": sub["seconds"]}
        gc.collect()
    facts["failed"] = failed
    if failed:
        raise SmokeFailure(f"multichip sub-phases failed: {failed}")
    return facts


def _bytes_in_use() -> List[int]:
    import jax

    return [int((d.memory_stats() or {}).get("bytes_in_use", -1))
            for d in jax.devices()[:4]]


def _multichip_tokens(sz: MultichipSizes) -> Dict[str, Any]:
    """(a) README: greedy decode on a sharded engine is token-identical to
    the single-chip engine."""
    from ray_tpu.serve.llm import LLMEngine

    prompts = [_chat_prompt_ids(chat) for chat in sz.serve.chats]
    out: Dict[str, Any] = {}
    tokens, sampled = {}, {}
    for tp in (1, 4):
        engine = LLMEngine(_engine_config(sz.serve, tp=tp))
        out[f"tp{tp}_attention"] = engine.stats()["attention"]
        tokens[tp] = [_generate(engine, f"greedy{i}", p, sz.serve.max_tokens)
                      for i, p in enumerate(prompts)]
        # random weights make greedy output repetitive; seeded sampling
        # spreads it, so report (not judge) how far that agrees too
        sampled[tp] = [_generate(engine, f"sampled{i}", p,
                                 sz.serve.max_tokens, temperature=1.0,
                                 seed=7) for i, p in enumerate(prompts)]
        out[f"tp{tp}_bytes_in_use"] = _bytes_in_use()
        del engine
    common = [next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                   min(len(a), len(b)))
              for a, b in zip(tokens[1], tokens[4])]
    n_same = sum(x == y for a, b in zip(sampled[1], sampled[4])
                 for x, y in zip(a, b))
    out.update(tokens_tp1=tokens[1], tokens_tp4=tokens[4],
               common_prefix_lengths=common,
               seeded_sampling_positions_equal=[
                   n_same, sum(len(a) for a in sampled[1])],
               ok=tokens[1] == tokens[4] and all(tokens[1]))
    if not out["ok"]:
        out["error"] = ("greedy tokens differ between tp=4 and tp=1; "
                        f"common prefix per prompt {common} of "
                        f"{sz.serve.max_tokens}")
    return out


def _multichip_loss(sz: MultichipSizes) -> Dict[str, Any]:
    """(b) the trainer on a 2x2 (fsdp x tp) mesh against the one-chip
    trainer, same seed and batch: per-step loss within loss_rtol. The
    flash kernel has to lower under the mesh (shard_map wrapper)."""
    import gc

    import jax

    from ray_tpu.parallel.mesh import MeshConfig

    one = _train_steps(sz.train, MeshConfig(dp=1, fsdp=1, sp=1, tp=1),
                       jax.devices()[:1])
    gc.collect()
    mesh = _train_steps(sz.train, MeshConfig(dp=1, fsdp=2, sp=1, tp=2),
                        jax.devices()[:4])
    rel = [abs(m - o) / abs(o) for m, o in zip(mesh["loss"], one["loss"])]
    out = {"one_chip": one, "mesh": mesh, "loss_rel_diff": rel,
           "loss_rtol": sz.loss_rtol, "ok": max(rel) <= sz.loss_rtol}
    if not out["ok"]:
        out["error"] = f"per-step loss differs by {max(rel)} > {sz.loss_rtol}"
    return out


def _multichip_big_engine(sz: MultichipSizes) -> Dict[str, Any]:
    """(c) README's serving example: a model whose bf16 weights fill one
    chip's memory, tp=4. It fits only if init and the page pool land
    sharded: no device may hold the whole model."""
    import jax

    from ray_tpu.serve.llm import LLMEngine

    before = _bytes_in_use()
    engine = LLMEngine(_engine_config(
        sz.serve, model=sz.big_model,
        model_overrides=dict(sz.big_overrides), tp=4,
        num_pages=sz.big_num_pages, prefill_buckets=(sz.big_bucket,)))
    jax.block_until_ready(engine.params)
    model_bytes = int(sum(x.nbytes for x in jax.tree.leaves(engine.params)))
    in_use = _bytes_in_use()
    toks = _generate(engine, "chat0", _chat_prompt_ids(sz.serve.chats[0]),
                     sz.serve.max_tokens)
    vocab = engine.model_cfg.vocab_size
    out = {"model": sz.big_model, "model_bytes": model_bytes,
           "bytes_in_use_before": before, "bytes_in_use": in_use,
           "attention": engine.stats()["attention"], "tokens": toks}
    held = [b - b0 for b, b0 in zip(in_use, before)]
    out["ok"] = (bool(toks) and all(0 <= t < vocab for t in toks)
                 and all(0 <= h < model_bytes / 2 for h in held))
    if not out["ok"]:
        out["error"] = (f"a device holds {max(held)} bytes of a "
                        f"{model_bytes}-byte model, or no tokens: {toks}")
    return out


# ----------------------------------------------------------------- parent
PHASES: Dict[str, Callable] = {
    "serve": phase_serve, "engine": phase_engine, "train": phase_train,
    "multichip": phase_multichip}
ONE_CHIP = ("serve", "engine", "train")
# the one-chip phases together stay inside the driver's 1200 s
TIME_LIMIT_S = {"serve": 600, "engine": 250, "train": 300,
                "multichip": 3000}


def _emit(facts: Dict[str, Any]) -> None:
    print(json.dumps(facts), flush=True)


def probe_device() -> Dict[str, Any]:
    """What JAX finds, asked in a child so this process stays off the chip."""
    code = ("import json, jax; d = jax.devices(); print(json.dumps({"
            "'platform': d[0].platform, 'kind': d[0].device_kind, "
            "'count': len(d)}))")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300)
    if proc.returncode != 0:
        raise SmokeFailure(
            "no TPU found: JAX could not initialise a backend:\n"
            + proc.stderr[-2000:])
    device = json.loads(proc.stdout.strip().splitlines()[-1])
    require_tpu(device)
    return device


def check_native_store() -> Dict[str, Any]:
    """ray_tpu/_native/*.so is git-ignored: build it from csrc/ now, or
    fail loudly — never the pure-Python store in silence."""
    from ray_tpu import _native

    if not _native.ensure_built():
        raise SmokeFailure(
            f"native store did not build from csrc/: {_native.build_error()}")
    return {"phase": "native", "built": True, "library": _native._SO}


def _kill_tree(pid: int) -> None:
    """A phase past its time limit: kill it and every process under it
    (cluster workers start sessions of their own, so no group has them)."""
    import psutil

    try:
        root = psutil.Process(pid)
        procs = root.children(recursive=True) + [root]
    except psutil.NoSuchProcess:
        return
    for proc in procs:
        try:
            proc.kill()
        except psutil.NoSuchProcess:
            pass


def run_phase(name: str, prior: Dict[str, Any]) -> Dict[str, Any]:
    """Run one phase in a child process of its own; echo what it prints;
    return the facts on its result line. Raises when it fails."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=HERE)
    killer = threading.Timer(TIME_LIMIT_S[name], _kill_tree, (proc.pid,))
    killer.start()
    result = None
    try:
        proc.stdin.write(json.dumps(prior))
        proc.stdin.close()
        for line in proc.stdout:
            if line.startswith(RESULT_MARK):
                result = json.loads(line[len(RESULT_MARK):])
            else:
                print(line, end="", flush=True)
        proc.wait()
    finally:
        timed_out = not killer.is_alive()
        killer.cancel()
    if timed_out:
        raise SmokeFailure(f"phase {name} exceeded {TIME_LIMIT_S[name]} s")
    if proc.returncode != 0 or result is None:
        raise SmokeFailure(f"phase {name} failed (exit {proc.returncode})")
    _emit({"phase": name, **result})
    return result


def _child(name: str) -> int:
    prior = json.loads(sys.stdin.read() or "{}")
    t0 = time.monotonic()
    facts = PHASES[name](prior=prior)
    facts["phase_s"] = round(time.monotonic() - t0, 1)
    print(RESULT_MARK + json.dumps(facts), flush=True)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--multichip", action="store_true",
                        help="four chips: only the cross-chip paths")
    parser.add_argument("--phase", choices=sorted(PHASES),
                        help=argparse.SUPPRESS)  # how the parent starts a child
    args = parser.parse_args(argv)
    if args.phase:
        return _child(args.phase)
    t0 = time.monotonic()
    try:
        device = probe_device()
        _emit({"phase": "probe", "device": device})
        from ray_tpu.util.compile_cache import cache_dir

        _emit({"phase": "compile_cache", "dir": cache_dir(),
               "placed_by_env": bool(
                   os.environ.get("JAX_COMPILATION_CACHE_DIR")),
               # 0 = this run's compile seconds are cold ones
               "entries_at_start": len(os.listdir(cache_dir()))
               if os.path.isdir(cache_dir()) else 0})
        _emit(check_native_store())
        facts: Dict[str, Any] = {}
        for name in (("multichip",) if args.multichip else ONE_CHIP):
            facts[name] = run_phase(name, facts)
            seen = facts[name].get("device") or facts[name]["replica_device"]
            require_tpu(seen)
    except Exception as e:  # noqa: BLE001 — any failure: non-zero, no result
        print(f"chip_smoke FAILED after {time.monotonic() - t0:.0f} s: "
              f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 1
    _emit({"phase": "total", "seconds": round(time.monotonic() - t0, 1)})
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
