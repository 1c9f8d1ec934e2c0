"""Batch LLM inference: Processor pipelines over ray_tpu.data.

Parity with the reference's batch stack (ref: llm/_internal/batch/processor/
{vllm_engine_proc,sglang_engine_proc,http_request_proc}.py and
llm/_internal/batch/stages/ — tokenize, chat-template, engine, detokenize
stages composed into a Processor that maps over a Ray Data dataset). The
reference delegates generation to external vLLM/SGLang engines; here the
engine stage drives the native paged-KV continuous-batching LLMEngine
(engine.py), so a whole dataset batch shares one in-flight continuous
batch — prefix cache and page reuse included.

Usage:
    config = ProcessorConfig(engine=EngineConfig(model="tiny"))
    processor = build_llm_processor(
        config,
        preprocess=lambda row: {"messages": [
            {"role": "user", "content": row["question"]}]},
        postprocess=lambda row: {"answer": row["generated_text"]})
    out = processor(ds).take_all()
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .engine import EngineConfig, LLMEngine, SamplingParams
from .tokenizer import get_tokenizer

# One engine per (worker process, engine config): engine construction
# compiles jit buckets and allocates the page pool, so map tasks running
# in the same worker must reuse it across batches. The key is the full
# config dict — including `tp`, so a tensor-parallel engine (sharded
# params + Hkv-split page pool over a tp mesh, serve/llm/sharding.py)
# never aliases a single-device engine's donated buffers. Block tables
# are global under tp (each shard holds Hkv/tp heads of every page), so
# the batching loop below is identical in both modes.
_ENGINE_CACHE: Dict[str, LLMEngine] = {}



# process-wide request-id sequence for the engine stage (stable,
# collision-free across batches — unlike id())
_BATCH_SEQ = itertools.count()

def _get_engine(config: EngineConfig) -> LLMEngine:
    key = repr(dataclasses.asdict(config))
    engine = _ENGINE_CACHE.get(key)
    if engine is None:
        engine = LLMEngine(config)
        _ENGINE_CACHE[key] = engine
    return engine


def _drop_engine(engine: LLMEngine) -> None:
    for key in [k for k, e in _ENGINE_CACHE.items() if e is engine]:
        del _ENGINE_CACHE[key]
    engine.close()


@dataclasses.dataclass
class ProcessorConfig:
    """ref: llm/_internal/batch/processor/vllm_engine_proc.py
    vLLMEngineProcessorConfig — engine args + per-stage batch size +
    concurrency; TPU-native engine config instead of engine_kwargs."""

    engine: EngineConfig = dataclasses.field(default_factory=EngineConfig)
    tokenizer: Optional[str] = None  # None -> byte tokenizer
    batch_size: int = 16
    apply_chat_template: bool = True
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)
    # per-request generation budget in seconds (None = unbounded): each
    # row's deadline is stamped when its batch enters the engine stage,
    # so offline batches participate in the engine's expiry pruning
    # (WAITING entries are shed before prefill, RUNNING slots at step
    # start) exactly like serve traffic. A row may instead carry its own
    # absolute wall-clock "deadline" column, which wins over this knob.
    # Expired rows come back with finish_reason == "expired" and
    # whatever tokens they produced before the deadline.
    deadline_s: Optional[float] = None


def render_chat_template(messages: List[dict]) -> str:
    """Chat-template stage (ref: llm/_internal/batch/stages/
    chat_template_stage.py)."""
    from .server import _render_chat

    return _render_chat(list(messages))


class Processor:
    """A composed preprocess → tokenize → generate → detokenize →
    postprocess pipeline over a Dataset (ref: llm/_internal/batch/
    processor/base.py Processor)."""

    def __init__(self, config: ProcessorConfig,
                 preprocess: Optional[Callable] = None,
                 postprocess: Optional[Callable] = None):
        self.config = config
        self.preprocess = preprocess
        self.postprocess = postprocess

    # ------------------------------------------------------------ stages

    def _tokenize_rows(self, rows: List[dict]) -> List[dict]:
        """Tokenize stage (ref: stages/tokenize_stage.py); renders chat
        messages first when configured (stages/chat_template_stage.py)."""
        tok = get_tokenizer(self.config.tokenizer)
        out = []
        for row in rows:
            row = dict(row)
            if "prompt" not in row:
                if self.config.apply_chat_template and "messages" in row:
                    row["prompt"] = render_chat_template(row["messages"])
                else:
                    raise ValueError(
                        "rows must carry 'prompt' or 'messages'")
            row["prompt_token_ids"] = tok.encode(row["prompt"])
            out.append(row)
        return out

    def _generate_rows(self, rows: List[dict]) -> List[dict]:
        """Engine stage (ref: stages/vllm_engine_stage.py): feed the whole
        batch into the continuous-batching engine and step until drained —
        requests share pages, prefix cache, and decode batches."""
        engine = _get_engine(self.config.engine)
        sampling = self.config.sampling
        by_id: Dict[str, dict] = {}
        # monotonic batch tag, NOT id(rows): the engine is cached across
        # batches, and a recycled list address colliding with a stale
        # request id from an earlier batch would cross-wire their tokens
        # (rtpulint RTPU005 — the PR 4 chain-hash bug class)
        batch_tag = next(_BATCH_SEQ)
        # deadline threading (absolute wall clock, the engine converts
        # to its monotonic domain): per-row "deadline" column wins, the
        # ProcessorConfig.deadline_s budget stamps the rest
        default_deadline = (time.time() + self.config.deadline_s
                            if self.config.deadline_s else None)
        for i, row in enumerate(rows):
            rid = f"batch-{batch_tag}-{i}"
            row = dict(row)
            by_id[rid] = row
            max_new = int(row.get("max_tokens", sampling.max_tokens))
            params = dataclasses.replace(sampling, max_tokens=max_new)
            deadline = row.get("deadline", default_deadline)
            engine.add_request(rid, list(map(int,
                                             row["prompt_token_ids"])),
                               params,
                               deadline=(float(deadline)
                                         if deadline is not None
                                         else None))
        collected: Dict[str, List[int]] = {rid: [] for rid in by_id}
        finish: Dict[str, str] = {}
        try:
            while engine.has_work():
                for delta in engine.step():
                    if delta.request_id in collected:
                        collected[delta.request_id].extend(
                            delta.new_token_ids)
                        if delta.finished:
                            finish[delta.request_id] = delta.finish_reason
        except BaseException:
            # a batch that ends early leaves its requests, and dispatches
            # in flight, in the cached engine: the next batch builds its
            # own, and this one is left with nothing queued on the device
            _drop_engine(engine)
            raise
        tok = get_tokenizer(self.config.tokenizer)
        # per-batch expiry count rides the rows (the engine stage runs in
        # a map_batches worker — driver-side Processor state never sees
        # it; a shared column does)
        n_expired = sum(1 for r in finish.values() if r == "expired")
        out = []
        for rid, row in by_id.items():
            ids = collected[rid]
            row["generated_token_ids"] = ids
            row["generated_text"] = tok.decode(ids)
            row["finish_reason"] = finish.get(rid, "stop")
            row["num_input_tokens"] = len(row["prompt_token_ids"])
            row["num_generated_tokens"] = len(ids)
            row["num_expired_in_batch"] = n_expired
            out.append(row)
        return out

    # ---------------------------------------------------------- pipeline

    def __call__(self, dataset):
        ds = dataset
        if self.preprocess is not None:
            ds = ds.map(self.preprocess)
        batch = self.config.batch_size

        def run(rows: List[dict]) -> List[dict]:
            return self._generate_rows(self._tokenize_rows(rows))

        ds = ds.map_batches(_rows_adapter(run), batch_size=batch)
        if self.postprocess is not None:
            ds = ds.map(self.postprocess)
        return ds


def _rows_adapter(fn: Callable[[List[dict]], List[dict]]) -> Callable:
    """Adapt a rows->rows fn to map_batches' dict-of-columns format."""

    def wrapper(batch: Dict[str, Any]) -> Dict[str, Any]:
        if isinstance(batch, dict):
            keys = list(batch)
            n = len(batch[keys[0]]) if keys else 0
            rows = [{k: batch[k][i] for k in keys} for i in range(n)]
        else:  # already a list of rows
            rows = [dict(r) for r in batch]
        out_rows = fn(rows)
        cols: Dict[str, List[Any]] = {}
        for row in out_rows:
            for key, val in row.items():
                cols.setdefault(key, []).append(val)
        return {k: np.asarray(v, dtype=object)
                if not _is_rectangular(v) else np.asarray(v)
                for k, v in cols.items()}

    return wrapper


def _is_rectangular(values: List[Any]) -> bool:
    try:
        arr = np.asarray(values)
        return arr.dtype != object
    except (ValueError, TypeError):
        return False


def build_llm_processor(config: ProcessorConfig,
                        preprocess: Optional[Callable] = None,
                        postprocess: Optional[Callable] = None
                        ) -> Processor:
    """ref: llm/_internal/batch/processor/__init__.py
    build_llm_processor."""
    return Processor(config, preprocess=preprocess,
                     postprocess=postprocess)


@dataclasses.dataclass
class HttpRequestProcessorConfig:
    """Query an OpenAI-compatible endpoint per row (ref:
    llm/_internal/batch/processor/http_request_proc.py) — for datasets
    scored against an already-deployed ray_tpu.serve.llm app."""

    url: str = "http://127.0.0.1:8000/v1/chat/completions"
    model: str = "default-llm"
    batch_size: int = 8
    concurrency: int = 4
    timeout_s: float = 60.0
    max_tokens: int = 64


def build_http_request_processor(config: HttpRequestProcessorConfig,
                                 preprocess: Optional[Callable] = None,
                                 postprocess: Optional[Callable] = None
                                 ) -> Processor:
    """Processor whose engine stage is an HTTP fan-out to a serving
    endpoint instead of an in-process engine."""
    import concurrent.futures
    import json
    import urllib.request

    def query(row: dict) -> dict:
        row = dict(row)
        messages = row.get("messages") or [
            {"role": "user", "content": row["prompt"]}]
        payload = json.dumps({
            "model": config.model, "messages": list(messages),
            "max_tokens": int(row.get("max_tokens", config.max_tokens)),
        }).encode()
        req = urllib.request.Request(
            config.url, data=payload,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req,
                                    timeout=config.timeout_s) as resp:
            body = json.loads(resp.read())
        row["generated_text"] = \
            body["choices"][0]["message"]["content"]
        row["finish_reason"] = body["choices"][0].get("finish_reason")
        return row

    class _HttpProcessor(Processor):
        def __call__(self, dataset):
            ds = dataset
            if self.preprocess is not None:
                ds = ds.map(self.preprocess)

            def run(rows: List[dict]) -> List[dict]:
                with concurrent.futures.ThreadPoolExecutor(
                        max_workers=config.concurrency) as pool:
                    return list(pool.map(query, rows))

            ds = ds.map_batches(_rows_adapter(run),
                                batch_size=config.batch_size)
            if self.postprocess is not None:
                ds = ds.map(self.postprocess)
            return ds

    return _HttpProcessor(ProcessorConfig(), preprocess=preprocess,
                          postprocess=postprocess)
