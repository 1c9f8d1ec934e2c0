"""LLM serving deployments: engine host + OpenAI-compatible ingress.

Parity with the reference's Serve-LLM surface (ref: llm/_internal/serve/
deployments/llm/llm_server.py:410 LLMServer.chat; OpenAI ingress builders
ref: llm/_internal/serve/builders/application_builders.py:19,55
build_openai_app) with the external vLLM engine replaced by the native
paged-KV engine (engine.py).
"""

from __future__ import annotations

import asyncio
import dataclasses
import itertools
import time
from typing import Any, Dict, List, Optional

from ...util import tracing
from .. import deployment
from .engine import EngineConfig, LLMEngine, SamplingParams
from .pp import make_engine
from .tokenizer import get_tokenizer


@dataclasses.dataclass
class LLMConfig:
    """User-facing config (ref: llm/_internal/serve/configs/
    server_models.py:160 LLMConfig — model id + engine kwargs +
    deployment sizing)."""

    model_id: str = "default-llm"
    engine: EngineConfig = dataclasses.field(default_factory=EngineConfig)
    tokenizer: Any = None
    num_replicas: int = 1
    max_ongoing_requests: int = 64
    # compile every engine dispatch shape during replica construction, so
    # a replica is only READY once warmed (ref: serve/_private/
    # deployment_state.py initialization-health path — the reference
    # warms replicas before marking them READY; an unwarmed bucket hit
    # by live traffic is a multi-second TTFT spike)
    warmup: bool = True
    # per-replica actor options (resources, runtime_env — e.g. pin
    # JAX_PLATFORMS for CPU smoke deployments)
    ray_actor_options: Dict[str, Any] = dataclasses.field(
        default_factory=dict)
    # reserve a tp-chip TPU gang per replica: each replica gets its own
    # SLICE_PACK placement group sized engine.tp (one bundle per host,
    # serve/llm/sharding.py tp_bundles), so a tensor-parallel engine is
    # guaranteed ICI-adjacent chips. Off by default — CPU smoke
    # deployments and single-chip replicas need no reservation.
    reserve_tpu_bundle: bool = False
    # KV-cache plane (kv_transfer.py): prefill→decode handoff rides the
    # bulk data plane (seal into the shm pool, ship only a descriptor on
    # the control RPC, decode pulls over the chunk stream). False restores
    # the legacy pickled-blob-in-RPC handoff.
    bulk_kv_handoff: bool = True
    # cache-aware routing: the ingress/PD router computes the prompt's
    # page-chain hashes and routes to the replica whose published prefix
    # frontier matches the longest prefix (cluster registry on the serve
    # controller), falling back to least-outstanding-requests.
    prefix_routing: bool = True
    # sealed-handoff lifetime on the prefill side (HandoffRegistry): a
    # blob the decode tier never pulls is released after the TTL; the cap
    # is a burst backstop and must stay well above max_ongoing_requests
    # (cap-evicting an in-flight handoff fails that request's pull)
    kv_handoff_ttl_s: float = 120.0
    kv_handoff_cap: int = 256


_LLM_METRICS = None
# a token's pace and the three parts of a first token's wait are
# milliseconds to seconds
_MS_BOUNDARIES = (0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0)
# engine.stats() totals published as rtpu_llm_<key> counters: the engine's
# own. What a model family counts is declared by its dispatch facts
# (stage.py: model_family) and is registered when its engine first publishes
_LLM_WORK_TOTALS = {
    "preempted_total": "requests preempted for page pressure",
    "spec_drafted_total":
        "speculative draft tokens dispatched for verification",
    "spec_accepted_total":
        "speculative draft tokens accepted by verification",
    "steps_total": "engine.step() calls",
    "prefill_dispatches_total": "prefill programs enqueued",
    "decode_dispatches_total": "decode programs enqueued",
    "drawn_dispatches_total":
        "of prefill_dispatches_total + decode_dispatches_total, the "
        "programs whose batch had a row at a temperature above 0: their "
        "sampler drew (scaling, top-k cut and categorical) where a greedy "
        "batch's is an argmax (a model that generates by diffusion over "
        "blocks counts its block programs' as "
        "block_drawn_dispatches_total)",
    "prefill_tokens_total": "prompt tokens prefilled (real rows)",
    "prefill_padded_tokens_total":
        "token positions the prefill programs computed (rows x bucket)",
    "decode_rows_total": "real rows of the decode programs",
    "decode_ctx_tokens_total":
        "tokens of KV the decode programs' real rows attended to",
    "queue_wait_s_total":
        "seconds finished requests waited for their first prefill dispatch",
    "programs_built_total": "programs the engine built (jit cache misses)",
    "device_busy_s_total":
        "seconds the device ran the engine's programs, by the engine's own "
        "stamps (a program's end is its fetch's end when the host waited "
        "for it)",
    "device_idle_s_total":
        "seconds the device had no program enqueued between two programs",
    "harvests_late_total":
        "programs that had finished before the host came to fetch them: "
        "the host loop was behind the device (of prefill_dispatches_total "
        "+ decode_dispatches_total)",
    "prefix_reuse_refused_total":
        "admissions that skipped the prefix lookup (recurrent state, or "
        "pages that hold no whole number of a block model's blocks)",
    "prefill_passes_total": "prefill rows dispatched (a prompt takes the "
                            "passes that cost least: often one)",
    "prefill_resumed_passes_total":
        "prefill rows that started mid-prompt, from pages and slot state",
    "prefill_split_prompts_total":
        "prompts that fitted one length bucket and were prefilled in more "
        "than one pass, because that computed less padding",
    "prefill_attn_blocks_total":
        "(query block, key block) visits the prefill passes' flash calls "
        "would make by their shapes (bucket x bucket, bucket x block "
        "table's width), a layer and head",
    "prefill_attn_blocks_skipped_total":
        "of prefill_attn_blocks_total, the visits the rows' true lengths "
        "cut: a bucket's padded query blocks, the table's width past a "
        "row's context",
    "prefill_attn_blocks_masked_total":
        "of the visits made (total less skipped), those that build a mask: "
        "the causal diagonal's tiles and the tile that holds the end of a "
        "row's context; the flash forward runs the others bare",
}
# engine.stats() sizes published as rtpu_llm_<key> gauges, likewise
_LLM_SIZES = {
    "waiting": "requests queued for engine admission",
    "running": "requests holding a decode slot",
    "pages_free": "free KV pages (incl. evictable cached pages)",
}


def _get_llm_metrics(family_facts=()):
    """Engine-scheduler metric family (``rtpu_llm_*``), lazily
    registered so importing the module costs nothing: queue gauges +
    scheduler counters the continuous-batching bench and dashboards
    read. Counters end ``_total``, gauges do not (RTPU106); the nodelet
    ships worker-side counters with get_node_info's serve family. The
    work counters are the flight recorder's totals (``engine.stats()``),
    the engine's own and those `family_facts` (an engine's) declare; the
    five histograms are observed from its finished ``engine.request``
    records (ray_tpu/util/tracing.py)."""
    global _LLM_METRICS
    from ...util.metrics import Counter, Gauge, Histogram

    if _LLM_METRICS is None:
        _LLM_METRICS = {
            "queue_wait": Histogram(
                "rtpu_llm_queue_wait_seconds",
                "arrival to first prefill dispatch, per finished request"),
            "ttft": Histogram(
                "rtpu_llm_ttft_seconds",
                "arrival to first token, per finished request"),
            # first token - first dispatch, its two device parts
            # (engine.request), of the requests whose parts are exact
            "device_wait": Histogram(
                "rtpu_llm_device_wait_seconds",
                "first prefill dispatch to the last pass's end, less the "
                "request's own programs: other programs ahead of it on the "
                "device, per finished request whose programs the host "
                "waited for",
                boundaries=_MS_BOUNDARIES),
            "prefill_device": Histogram(
                "rtpu_llm_prefill_device_seconds",
                "device time of the programs that carried the prompt's "
                "passes, per finished request whose programs the host "
                "waited for",
                boundaries=_MS_BOUNDARIES),
            "tpot": Histogram(
                "rtpu_llm_tpot_seconds",
                "(finish - first token) / (output tokens - 1), per "
                "finished request",
                boundaries=_MS_BOUNDARIES),
        }
    for table in (_LLM_WORK_TOTALS, _LLM_SIZES,
                  *(facts.STATS for facts in family_facts)):
        for key, what in table.items():
            if key not in _LLM_METRICS:
                _LLM_METRICS[key] = (Counter if key.endswith("_total")
                                     else Gauge)(f"rtpu_llm_{key}", what)
    return _LLM_METRICS


class EngineDriverMixin:
    """Single driver coroutine + per-request waiter queues over the
    non-thread-safe engine. Concurrent request coroutines never call
    engine.step() themselves — they register a queue and await deltas —
    so the donated page buffers only ever see one stepping thread.
    """

    def _init_driver(self):
        self._waiters: Dict[str, asyncio.Queue] = {}
        self._driver_task: Optional[asyncio.Task] = None
        # last engine counter values already folded into the rtpu_llm_*
        # counters (engine stats are cumulative; metrics take deltas)
        self._llm_counts: Dict[str, float] = {}
        self._llm_requests_seen = tracing.appended("engine.request")
        self._llm_pub_t = 0.0

    async def _ensure_driver(self):
        if self._driver_task is None or self._driver_task.done():
            self._driver_task = asyncio.get_running_loop().create_task(
                self._drive())

    async def shutdown(self) -> None:
        """The replica is being stopped (serve/replica.py calls this once
        its requests have drained, before the worker is killed): stop the
        driver and leave the engine with nothing queued on the device
        (`LLMEngine.close`, which waits for a step that is running)."""
        if self._driver_task is not None and not self._driver_task.done():
            self._driver_task.cancel()
        await asyncio.get_running_loop().run_in_executor(
            None, self.engine.close)

    async def _drive(self):
        loop = asyncio.get_running_loop()
        while True:
            while self.engine.has_work():
                deltas = await loop.run_in_executor(None, self.engine.step)
                for delta in deltas:
                    queue = self._waiters.get(delta.request_id)
                    if queue is not None:
                        queue.put_nowait(delta)
                now = time.monotonic()
                if now - self._llm_pub_t > 2.0:
                    self._llm_pub_t = now
                    self._publish_llm_metrics(self.engine.stats())
                if not deltas:
                    await asyncio.sleep(0.005)
            # Linger one tick before exiting: work enqueued between the
            # check above and task completion is picked up here. There is
            # no await between the final has_work() and return, so (the
            # event loop being single-threaded) no add_request can slip
            # into that window unseen.
            await asyncio.sleep(0.005)
            if not self.engine.has_work():
                self._publish_llm_metrics(self.engine.stats())
                return

    async def _await_request(self, request_id: str,
                             queue: "asyncio.Queue"):
        """Yield deltas for request_id until the finished one (caller
        registered the queue in self._waiters)."""
        await self._ensure_driver()
        while True:
            delta = await queue.get()
            yield delta
            if delta.finished:
                return

    def _publish_llm_metrics(self, stats: Dict[str, Any]) -> None:
        m = _get_llm_metrics(getattr(getattr(self, "engine", None),
                                     "family_facts", ()))
        for key, metric in m.items():
            if metric.metric_type == "gauge" and key in stats:
                metric.set(stats[key])
            elif metric.metric_type == "counter":
                cur = stats.get(key, 0)
                delta = cur - self._llm_counts.get(key, 0)
                if delta > 0:
                    metric.inc(delta)
                self._llm_counts[key] = cur
        # latencies of the requests that finished since the last call
        seen = tracing.appended("engine.request")
        if seen != self._llm_requests_seen:
            fields = tracing.FIELDS["engine.request"]
            for rec in tracing.records("engine.request",
                                       since=self._llm_requests_seen):
                r = dict(zip(fields, rec))
                if r["dispatched_ns"] is not None:
                    m["queue_wait"].observe(
                        (r["dispatched_ns"] - r["arrival_ns"]) / 1e9)
                if r["first_token_ns"] is None:
                    continue
                m["ttft"].observe(
                    (r["first_token_ns"] - r["arrival_ns"]) / 1e9)
                if r["parts_exact"] and not r["preemptions"]:
                    for part in ("device_wait", "prefill_device"):
                        m[part].observe(r[part + "_ns"] / 1e9)
                if r["output_tokens"] > 1:
                    m["tpot"].observe(
                        (r["finish_ns"] - r["first_token_ns"]) / 1e9
                        / (r["output_tokens"] - 1))
            self._llm_requests_seen = seen

    def engine_stats(self) -> Dict[str, Any]:
        stats = self.engine.stats()
        self._publish_llm_metrics(stats)
        # seconds the constructor spent compiling every dispatch shape
        stats["warmup_s"] = getattr(self, "_warmup_s", None)
        return stats

    def kv_frontier(self,
                    known_rev: Optional[int] = None) -> Optional[Dict[str, Any]]:
        """Prefix-cache frontier snapshot for the cluster registry: the
        allocator's cached chain-hash set + rev, and the replica's
        running prefix hit rate (published to the rtpu_kv_prefix_hit_rate
        gauge). When the caller already holds `known_rev` and the
        frontier has not changed, the hash list is omitted — the
        steady-state poll ships O(1) bytes, not the whole cache."""
        engine = getattr(self, "engine", None)
        if engine is None:
            return None
        registry = getattr(self, "_handoffs", None)
        if registry is not None:
            # the controller polls this every second: a free TTL sweep,
            # so an idle prefill replica still releases its sealed blobs
            registry.evict()
        from .kv_transfer import _get_metrics

        rate = engine.allocator.prefix_hit_rate()
        _get_metrics()["hit_rate"].set(rate)
        snap = engine.allocator.frontier_snapshot()
        out = {"page_size": engine.config.page_size,
               "hit_rate": rate, "rev": snap["rev"]}
        if known_rev is None or known_rev != snap["rev"]:
            out["hashes"] = snap["hashes"]
        return out


@deployment
class LLMServer(EngineDriverMixin):
    """Hosts one engine. A single driver coroutine pulls engine steps on an
    executor thread while requests are pending, so the replica's event loop
    stays free (ref: llm_server.py engine loop task)."""

    def __init__(self, llm_config: LLMConfig):
        self.config = llm_config
        self.tokenizer = get_tokenizer(llm_config.tokenizer)
        engine_cfg = llm_config.engine
        if engine_cfg.eos_token_id is None:
            engine_cfg.eos_token_id = getattr(
                self.tokenizer, "eos_token_id", None)
        # pp > 1: the replica becomes the rank-0 scheduler of a
        # pipeline-parallel stage gang (serve/llm/pp.py); same engine
        # surface, so the driver loop and streaming path are unchanged
        self.engine = make_engine(engine_cfg)
        self._warmup_s = None
        if llm_config.warmup:
            t0 = time.monotonic()
            self.engine.warmup()
            self._warmup_s = round(time.monotonic() - t0, 2)
        self._ids = itertools.count()
        self._init_driver()

    async def generate(self, prompt: str = None, *,
                       prompt_ids: Optional[List[int]] = None,
                       max_tokens: int = 64, temperature: float = 0.0,
                       top_k: int = 0, seed: Optional[int] = None,
                       deadline: Optional[float] = None,
                       stream: bool = False) -> Dict[str, Any]:
        """Generate to completion; returns text + token ids + usage.
        ``stream``: also ``chunks``, one per engine delta that brought
        tokens, in the order they came: ``token_ids``, their ``text`` and,
        for a model that generates by diffusion over blocks, the
        ``fixed_pass`` of each (such a model's delta is one block's tokens
        in position order; every other model's is one token). A replica
        materializes a stream (serve/replica.py), so the chunks travel
        with the result.
        ``deadline`` (absolute, time.time() domain) defaults to the
        Serve request deadline propagated into this replica; the engine
        prunes the request from its WAITING queue if it expires before
        admission (surfaced as a typed RequestExpiredError)."""
        if deadline is None:
            from ..replica import get_request_deadline

            deadline = get_request_deadline()
        if prompt_ids is None:
            prompt_ids = self.tokenizer.encode(prompt)
        request_id = f"req-{next(self._ids)}"
        queue: asyncio.Queue = asyncio.Queue()
        self._waiters[request_id] = queue
        sampling = SamplingParams(max_tokens=max_tokens,
                                  temperature=temperature, top_k=top_k,
                                  seed=seed)
        t0 = time.time()
        self.engine.add_request(request_id, prompt_ids, sampling,
                                deadline=deadline)
        await self._ensure_driver()
        out_ids: List[int] = []
        chunks: List[Dict[str, Any]] = []
        finish_reason = None
        ttft = None
        try:
            while True:
                delta = await queue.get()
                if ttft is None and delta.new_token_ids:
                    ttft = time.time() - t0
                out_ids.extend(delta.new_token_ids)
                if stream and delta.new_token_ids:
                    chunks.append({
                        "token_ids": list(delta.new_token_ids),
                        "text": self.tokenizer.decode(delta.new_token_ids),
                        "fixed_pass": delta.fixed_pass})
                if delta.finished:
                    finish_reason = delta.finish_reason
                    break
        finally:
            self._waiters.pop(request_id, None)
        if finish_reason == "expired":
            # the engine pruned this request: the propagated deadline
            # passed while it sat in the WAITING queue or mid-decode
            # (RUNNING slots are pruned at step start too — dead work
            # must not pin pages) — surface the typed expiry, never a
            # silent empty/partial completion
            from ...exceptions import RequestExpiredError

            where = "engine decode" if out_ids else "engine queue"
            raise RequestExpiredError(
                f"request {request_id} expired in the {where}",
                where=where)
        return {
            "request_id": request_id,
            "text": self.tokenizer.decode(out_ids),
            "token_ids": out_ids,
            "finish_reason": finish_reason,
            "usage": {"prompt_tokens": len(prompt_ids),
                      "completion_tokens": len(out_ids),
                      "total_tokens": len(prompt_ids) + len(out_ids)},
            "ttft_s": ttft,
            **({"chunks": chunks} if stream else {}),
        }

    async def check_health(self) -> bool:
        return True


def _render_chat(messages: List[dict]) -> str:
    """Minimal chat template (no model-specific template without a real
    tokenizer)."""
    parts = [f"<|{m.get('role', 'user')}|>\n{m.get('content', '')}"
             for m in messages]
    return "\n".join(parts) + "\n<|assistant|>\n"


@deployment
class OpenAIIngress:
    """OpenAI-compatible HTTP surface: /v1/chat/completions,
    /v1/completions, /v1/models (ref: llm/_internal/serve/deployments/
    routers/router.py)."""

    def __init__(self, llm_handle, model_id: str = "default-llm",
                 llm_config: Optional[LLMConfig] = None):
        self.llm = llm_handle
        self.model_id = model_id
        self._ids = itertools.count()
        # with the LLMConfig, the ingress tokenizes once and routes by
        # the prompt's page-chain hashes against the cluster prefix
        # registry (KV plane); without it, rendezvous string-prefix
        # affinity is the fallback policy
        self.config = llm_config
        self._tokenizer = (get_tokenizer(llm_config.tokenizer)
                           if llm_config is not None else None)

    async def __call__(self, request):
        path = request.path
        if path.endswith("/v1/models") or path == "/v1/models":
            return {"object": "list",
                    "data": [{"id": self.model_id, "object": "model"}]}
        body = request.json()
        if "chat/completions" in path:
            prompt = _render_chat(body.get("messages", []))
            kind = "chat.completion"
        elif "completions" in path:
            prompt = body.get("prompt", "")
            kind = "text_completion"
        else:
            return {"error": {"message": f"unknown path {path}",
                              "type": "invalid_request_error"}}
        # prefix-aware routing: requests sharing a prompt prefix hit the
        # replica whose prefix cache already holds it. Cache-aware when
        # the registry has frontiers (longest matched page chain), string
        # rendezvous affinity otherwise.
        prefix_key = prompt[:256]
        call_kwargs = dict(
            max_tokens=int(body.get("max_tokens", 64)),
            temperature=float(body.get("temperature", 0.0)),
            seed=(int(body["seed"]) if body.get("seed") is not None
                  else None))
        stream = bool(body.get("stream"))
        if stream:
            call_kwargs["stream"] = True
        prefix_hashes = None
        if (self._tokenizer is not None
                and getattr(self.config, "prefix_routing", True)):
            from .kv_transfer import prefix_chain_hashes

            prompt_ids = self._tokenizer.encode(prompt)
            prefix_hashes = prefix_chain_hashes(
                prompt_ids, self.config.engine.page_size) or None
            call_kwargs["prompt_ids"] = prompt_ids
            out = await self.llm.options(
                method_name="generate", routing_key=prefix_key,
                prefix_hashes=prefix_hashes).remote(**call_kwargs)
        else:
            out = await self.llm.options(
                method_name="generate", routing_key=prefix_key).remote(
                prompt, **call_kwargs)
        created = int(time.time())
        if stream:
            return self._chunks(out, kind, created,
                                body.get("model", self.model_id))
        if kind == "chat.completion":
            choice = {"index": 0, "finish_reason": out["finish_reason"],
                      "message": {"role": "assistant",
                                  "content": out["text"]}}
        else:
            choice = {"index": 0, "finish_reason": out["finish_reason"],
                      "text": out["text"]}
        return {
            "id": f"cmpl-{next(self._ids)}",
            "object": kind,
            "created": created,
            "model": body.get("model", self.model_id),
            "choices": [choice],
            "usage": out["usage"],
        }


    def _chunks(self, out: Dict[str, Any], kind: str, created: int,
                model: str) -> List[Dict[str, Any]]:
        """`stream: true`: the response as its chunks, one per delta of
        the engine (a token; a model that generates by diffusion over
        blocks: one block's tokens, in position order, with the denoising
        pass that fixed each beside them), the last carrying the finish
        reason."""
        cid = f"cmpl-{next(self._ids)}"
        events = []
        for i, chunk in enumerate(out["chunks"]):
            last = i == len(out["chunks"]) - 1
            choice = {"index": 0,
                      "finish_reason": out["finish_reason"] if last else None}
            if kind == "chat.completion":
                choice["delta"] = {"content": chunk["text"]}
            else:
                choice["text"] = chunk["text"]
            events.append({
                "id": cid, "object": f"{kind}.chunk", "created": created,
                "model": model, "choices": [choice],
                "token_ids": chunk["token_ids"],
                "fixed_pass": chunk["fixed_pass"]})
        return events


def placement_options(llm_config: LLMConfig) -> Dict[str, Any]:
    """Deployment placement options for an engine-hosting replica: a
    SLICE_PACK bundle set when the config asks for a TPU gang
    reservation — one tp-chip bundle for a single-process engine, one
    PER STAGE for a pipelined one (bundle order follows the ICI snake
    path, so stage k and k+1 land on neighbouring hosts) — else
    nothing."""
    tp = getattr(llm_config.engine, "tp", 1)
    pp = getattr(llm_config.engine, "pp", 1)
    if not llm_config.reserve_tpu_bundle or (tp <= 1 and pp <= 1):
        return {}
    if pp > 1:
        from .sharding import pp_bundles

        return {"placement_bundles": pp_bundles(pp, tp),
                "placement_strategy": "SLICE_PACK"}
    from .sharding import tp_bundles

    return {"placement_bundles": tp_bundles(tp),
            "placement_strategy": "SLICE_PACK"}


def build_openai_app(llm_config: LLMConfig):
    """Application: OpenAI ingress -> LLMServer replicas (ref:
    application_builders.py:55 build_openai_app)."""
    server = LLMServer.options(
        name=f"LLMServer:{llm_config.model_id}",
        num_replicas=llm_config.num_replicas,
        max_ongoing_requests=llm_config.max_ongoing_requests,
        ray_actor_options=llm_config.ray_actor_options,
        **placement_options(llm_config),
    ).bind(llm_config)
    return OpenAIIngress.options(name="OpenAIIngress").bind(
        server, llm_config.model_id, llm_config)
