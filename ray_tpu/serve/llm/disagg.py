"""Prefill/decode disaggregation.

Parity with the reference (ref: llm/_internal/serve/deployments/
prefill_decode_disagg/prefill_decode_disagg.py — separate prefill and
decode vLLM deployment groups with KV transfer between them; the reference
delegates the actual KV movement to vLLM's connector). Here the handoff is
native AND rides the runtime's own data plane (kv_transfer.py): the prefill
engine runs exactly the prompt pass and first token, seals the gathered KV
pages into its host's shared-memory object store, and returns only a small
descriptor over the control RPC; the decode engine pulls the blob — same
host: a bare mmap of the shared pool; cross host: `core.pull_manager` chunk
streams (om_read RPC fallback behind `bulk_transfer_enabled`) — and
`inject_request`s it into its own paged pool. `LLMConfig.bulk_kv_handoff =
False` restores the legacy pickled-blob-in-RPC handoff.

Why disaggregate on TPU: prefill is compute-bound (big MXU matmuls over the
whole prompt) while decode is HBM-bandwidth-bound (one token per step over
the KV cache). Separate engines let each side batch and scale to its own
bottleneck — prefill replicas never stall the decode batch's latency, and
decode replicas keep a full continuous batch resident.

Deployment shape: PrefillServer replicas + DecodeServer replicas behind a
PDIngress that routes prompt→prefill→handoff→decode. The prefill leg is
cache-aware: the router hashes the prompt's page chain and sends it to the
prefill replica whose published prefix frontier matches the longest prefix
(cluster registry on the serve controller).
"""

from __future__ import annotations

import asyncio
import itertools
import time
from typing import Any, Dict, List, Optional

from .. import deployment
from . import kv_transfer
from .engine import LLMEngine, SamplingParams
from .server import EngineDriverMixin, LLMConfig, OpenAIIngress
from .tokenizer import get_tokenizer


@deployment
class PrefillServer(EngineDriverMixin):
    """Runs prompt prefill + first token only, then hands the KV off.

    Concurrency-safe: requests go through the shared driver loop with
    SamplingParams(prefill_only=True); the engine gathers the KV blob
    inside step() (driver thread) and parks it for pop_extracted, so no
    coroutine ever touches the donated page buffers directly."""

    def __init__(self, llm_config: LLMConfig):
        self.config = llm_config
        self.engine = LLMEngine(llm_config.engine)
        self.engine._refuse_handoff()   # pages only: no recurrent state
        if getattr(llm_config, "warmup", True):
            self.engine.warmup(include_decode=False)
        self._ids = itertools.count()
        # sealed handoff refs pinned until the decode side pulls them
        # (TTL'd + capped, mirroring the engine's extracted-blob eviction;
        # also swept via kv_frontier on the controller's registry poll)
        self._handoffs = kv_transfer.HandoffRegistry(
            ttl_s=getattr(llm_config, "kv_handoff_ttl_s", 120.0),
            cap=getattr(llm_config, "kv_handoff_cap", 256))
        self._init_driver()

    async def prefill(self, prompt_ids: List[int],
                      sampling_kwargs: Dict[str, Any]) -> Dict[str, Any]:
        """Returns the handoff descriptor (KV ref + layout metadata +
        first token) — or, with bulk_kv_handoff=False / outside an
        initialized runtime, the legacy dense blob."""
        request_id = f"pf-{next(self._ids)}"
        sampling = SamplingParams(**sampling_kwargs)
        sampling.prefill_only = True
        queue: asyncio.Queue = asyncio.Queue()
        self._waiters[request_id] = queue
        from ..replica import get_request_deadline

        # the Serve-propagated deadline reaches the prefill queue too:
        # an expired entry is pruned instead of burning prefill compute
        self.engine.add_request(request_id, prompt_ids, sampling,
                                deadline=get_request_deadline())
        first: List[int] = []
        reason = None
        try:
            async for delta in self._await_request(request_id, queue):
                first.extend(delta.new_token_ids)
                reason = delta.finish_reason
        finally:
            self._waiters.pop(request_id, None)
        if reason == "expired":
            # pruned by the engine: the propagated deadline passed
            # before admission OR mid-prefill (RUNNING slots are pruned
            # at step start too) — typed, never dead work
            from ...exceptions import RequestExpiredError

            raise RequestExpiredError(
                f"request {request_id} expired in the prefill tier",
                where="prefill queue")
        if reason != "prefill_done":
            # the first token already terminated the request (EOS/stop/
            # length) — nothing to hand off
            return {"done": True, "output_ids": first,
                    "finish_reason": reason}
        handoff = self.engine.pop_extracted(request_id)
        self._handoffs.evict()
        if getattr(self.config, "bulk_kv_handoff", True) \
                and _runtime_initialized():
            loop = asyncio.get_running_loop()
            # seal off the event loop: the store write memcpys the blob
            return await loop.run_in_executor(
                None, lambda: kv_transfer.seal_handoff(
                    handoff, registry=self._handoffs,
                    request_id=request_id))
        handoff["done"] = False
        return handoff


def _runtime_initialized() -> bool:
    # worker-aware: replicas run in worker processes where there is no
    # driver Session (ray_tpu.is_initialized() is False) but a CoreWorker
    # exists — which is all the seal/pull path needs
    from ...runtime.core import get_core

    return get_core(required=False) is not None


@deployment
class DecodeServer(EngineDriverMixin):
    """Adopts prefilled requests and runs batched decode to completion."""

    def __init__(self, llm_config: LLMConfig):
        self.config = llm_config
        self.engine = LLMEngine(llm_config.engine)
        self.engine._refuse_handoff()   # pages only: no recurrent state
        if getattr(llm_config, "warmup", True):
            # full warmup (not decode-only): page-pressure preemption
            # re-prefills on THIS engine, so prefill shapes are hit in
            # traffic too
            self.engine.warmup()
        self._ids = itertools.count()
        self._init_driver()

    async def decode(self, handoff: Dict[str, Any],
                     sampling_kwargs: Dict[str, Any]) -> Dict[str, Any]:
        request_id = f"dec-{next(self._ids)}"
        loop = asyncio.get_running_loop()
        # resolve the descriptor into an injectable blob: same-host mmap
        # or a cross-host bulk-plane pull — off the event loop, which
        # must stay free for other requests' deltas and health checks
        blob = await loop.run_in_executor(
            None, kv_transfer.fetch_handoff, handoff)
        queue: asyncio.Queue = asyncio.Queue()
        self._waiters[request_id] = queue
        self.engine.inject_request(request_id, blob,
                                   SamplingParams(**sampling_kwargs))
        out_ids = list(blob["output_ids"])
        finish_reason = None
        try:
            async for delta in self._await_request(request_id, queue):
                out_ids.extend(delta.new_token_ids)
                if delta.finished:
                    finish_reason = delta.finish_reason
        finally:
            self._waiters.pop(request_id, None)
        return {"output_ids": out_ids, "finish_reason": finish_reason,
                "handoff_pull_s": float(blob.get("pull_s", 0.0)),
                "kv_nbytes": int(blob.get("kv_nbytes", 0))}


@deployment
class PDRouter:
    """LLMServer-compatible facade over the prefill + decode tiers (the
    OpenAI ingress calls .generate exactly as it would a colocated
    LLMServer)."""

    # per-tier health probe budget: probes go DIRECTLY to replica actors
    # (never through serve routing), so a saturated tier cannot time a
    # healthy router out
    HEALTH_PROBE_TIMEOUT_S = 10.0

    def __init__(self, prefill_handle, decode_handle,
                 llm_config: LLMConfig):
        self.prefill = prefill_handle
        self.decode = decode_handle
        self.config = llm_config
        self.tokenizer = get_tokenizer(llm_config.tokenizer)

    async def generate(self, prompt: str = None, *,
                       prompt_ids: Optional[List[int]] = None,
                       max_tokens: int = 64, temperature: float = 0.0,
                       top_k: int = 0,
                       seed: Optional[int] = None) -> Dict[str, Any]:
        if prompt_ids is None:
            prompt_ids = self.tokenizer.encode(prompt)
        sampling = {"max_tokens": max_tokens, "temperature": temperature,
                    "top_k": top_k, "seed": seed}
        hashes = None
        if getattr(self.config, "prefix_routing", True):
            # cache-aware prefill routing: longest matched published
            # prefix wins, least-outstanding otherwise
            hashes = kv_transfer.prefix_chain_hashes(
                prompt_ids, self.config.engine.page_size) or None
        t0 = time.time()
        handoff = await self.prefill.options(
            method_name="prefill",
            prefix_hashes=hashes).remote(prompt_ids, sampling)
        # first token is produced at the prefill tier, so its latency IS
        # the request's TTFT; queue/prefill components come from the
        # engine, the seal/pull (handoff) components from the KV plane
        ttft = time.time() - t0
        queue_s = float(handoff.get("queued_s", 0.0))
        prefill_s = float(handoff.get("prefill_s", 0.0))
        seal_s = float(handoff.get("seal_s", 0.0))
        kv_nbytes = int(handoff.get("kv_nbytes", 0))
        pull_s = 0.0
        if handoff["done"]:
            # the first token terminated the request (EOS/stop/length —
            # the engine's _stop_reason runs before the handoff)
            out_ids = handoff["output_ids"]
            finish_reason = handoff["finish_reason"]
        else:
            result = await self.decode.options(
                method_name="decode").remote(handoff, sampling)
            out_ids = result["output_ids"]
            finish_reason = result["finish_reason"]
            pull_s = float(result.get("handoff_pull_s", 0.0))
            kv_nbytes = kv_nbytes or int(result.get("kv_nbytes", 0))
        handoff_s = seal_s + pull_s
        kv_transfer.observe_ttft(queue_s, prefill_s, handoff_s)
        return {
            "text": self.tokenizer.decode(out_ids),
            "token_ids": out_ids,
            "finish_reason": finish_reason,
            "usage": {"prompt_tokens": len(prompt_ids),
                      "completion_tokens": len(out_ids),
                      "total_tokens": len(prompt_ids) + len(out_ids),
                      "kv_handoff_bytes": kv_nbytes},
            "ttft_s": ttft,
            "ttft_breakdown": {
                "queue_s": queue_s,
                "prefill_s": prefill_s,
                "handoff_s": handoff_s,
                # control-RPC + routing residual of the measured TTFT
                "rpc_s": max(0.0, ttft - queue_s - prefill_s - seal_s),
            },
        }

    async def check_health(self) -> bool:
        """Probe BOTH tiers (the old stub returned True unconditionally,
        so a dead prefill or decode tier never surfaced through serve
        health checks). A tier is healthy when it has >= 1 ready replica
        and at least one answers a direct health probe; probes bypass
        serve routing so saturation never reads as death."""
        await asyncio.gather(
            self._probe_tier(self.prefill, "prefill"),
            self._probe_tier(self.decode, "decode"))
        return True

    async def _probe_tier(self, handle, tier: str) -> None:
        from ..handle import _Router

        loop = asyncio.get_running_loop()
        router = _Router.get(handle.app_name, handle.deployment_name)
        await loop.run_in_executor(
            None, lambda: router.refresh(block_until_nonempty=False))
        with router.cond:
            replicas = list(router.replicas)
        if not replicas:
            raise RuntimeError(
                f"{tier} tier ({handle.deployment_name}) has no ready "
                "replicas")
        probes = [asyncio.wrap_future(r.check_health.remote().future())
                  for r in replicas]
        done, pending = await asyncio.wait(
            probes, timeout=self.HEALTH_PROBE_TIMEOUT_S)
        for p in pending:
            p.cancel()
        if not any(p.exception() is None for p in done):
            raise RuntimeError(
                f"{tier} tier ({handle.deployment_name}) failed health "
                f"probes on all {len(replicas)} replicas")


def build_pd_openai_app(llm_config: LLMConfig, *,
                        num_prefill_replicas: int = 1,
                        num_decode_replicas: int = 1):
    """OpenAI-compatible app with disaggregated prefill/decode tiers
    (ref: prefill_decode_disagg.py build_app)."""
    from .server import placement_options

    placement = placement_options(llm_config)
    prefill = PrefillServer.options(
        name=f"PrefillServer:{llm_config.model_id}",
        num_replicas=num_prefill_replicas,
        ray_actor_options=llm_config.ray_actor_options,
        **placement,
    ).bind(llm_config)
    decode = DecodeServer.options(
        name=f"DecodeServer:{llm_config.model_id}",
        num_replicas=num_decode_replicas,
        ray_actor_options=llm_config.ray_actor_options,
        **placement,
    ).bind(llm_config)
    router = PDRouter.options(
        name=f"PDRouter:{llm_config.model_id}").bind(
        prefill, decode, llm_config)
    return OpenAIIngress.options(name="OpenAIIngress").bind(
        router, llm_config.model_id)
