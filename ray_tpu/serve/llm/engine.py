"""JAX paged-KV continuous-batching LLM engine.

Replaces the reference's external vLLM dependency (ref: llm/_internal/serve/
deployments/llm/vllm/vllm_engine.py:181 — the reference only wraps
`AsyncLLM`; scheduling, paging and kernels live outside its repo). Engine
loop design follows the same contract a continuous-batching engine exposes:
`add_request` enqueues, `step()` runs ONE scheduler iteration and returns
per-request output deltas.

TPU-first mechanics:
- all jitted shapes are bucketed (prefill length; decode always runs the
  full `max_batch` slot set) so each bucket compiles once; page buffers are
  donated so the cache updates in place without a copy
- the KV cache is paged, one pool [L, P, Hkv, page, 2*D] for all layers;
  the model carries it whole through its layer scan, writes new tokens
  into it by whole pages and attends through block tables
  (ray_tpu/ops/paged_attention.py), so a program holds the pool once and
  moves only the pages it touches (tests/test_chip_compile.py reads the
  compiled programs for that)
- prefix caching: full pages are refcount-shared across requests keyed by
  rolling content hash (cache.py), so shared system prompts prefill once
- tensor parallelism (EngineConfig.tp > 1 or an explicit mesh=): params
  shard by the train-side logical-axis rules and the page pool splits
  its Hkv axis over the mesh's tp axis; block tables and the decode
  carry stay replicated, so the scheduler/allocator logic below is
  IDENTICAL in both modes and all sharding lives with the device side
  (serve/llm/stage.py, serve/llm/sharding.py)

This file is the scheduler. Everything that lives on devices (params, the
pool, the decode carry, the compiled programs) belongs to ONE
`StageCompute` over all layers (serve/llm/stage.py): the engine is a
pipeline of one stage, and reaches it through `_compute_prefill`,
`_compute_verify`, `_compute_decode`, `_fetch_tokens` and, for the flight
records' device stamps, `_handle_ready` only.

Host/device contract: a host-blocking fetch (`np.asarray` of a device
array) costs a device sync — the host waits for every dispatch queued
before it — while uploads are asynchronous and chained dispatches pipeline
on the device without host involvement. Three design rules follow:
1. NEVER run eager device ops on the driver thread (a `toks[-1]` slice is
   its own dispatch plus a sync);
2. sampled tokens feed the next decode dispatch through a device-resident
   `slot_ids` carry (donated through every dispatch), so the token values
   never cross to the host on the critical path;
3. results are pushed host-ward with `copy_to_host_async()` at dispatch
   time and harvested FIFO behind a `pipeline_depth`-deep window — the
   blocking `np.asarray` then completes quickly once landed.
Prefill runs in waves of at most `prefill_wave_size` rows, and a wave
computes as many rows as it has requests: the program takes its arrays
at the wave size (one compiled shape per length bucket, with or without
a prefix part) and loops over the REAL rows only, one row a pass, so one
arrival costs one row and not a wave of padding. Every row count from 1
to the wave size is the same program, so there is nothing more for
`warmup()` to build than one program per length bucket and prefix
variant, all before a replica reports READY (a compiled row bucket
would cost about a second of tracing each on the host, measured; the
loop costs nothing, and above a few hundred tokens a prefill is bound by
compute, where rows computed together take as long as rows computed in
turn). The waves pipeline on-device, so a burst's total prefill compute
is unchanged but the first wave's tokens surface after only its own
share of it. `decode_steps_per_dispatch`, `pipeline_depth` and
`prefill_wave_size` keep the values an earlier deployment chose; ROADMAP
C3 re-measures them on the attached chip.

Attention implementation: on a TPU backend a single-device engine runs
the Pallas paged-decode and flash kernels, and a pool layout the decode
kernel cannot take fails at construction; tensor-parallel engines ask for
the jnp reference by argument (`ref_attention`); a CPU backend
(`JAX_PLATFORMS=cpu`) runs the reference. `stats()["attention"]` reports
which one this engine's programs contain.

Scheduler v2 (token-budget continuous batching), on top of the above:
- `prefill_chunk_tokens > 0` switches step() from prefill-priority to a
  per-step TOKEN budget: every step dispatches the running slots' fused
  decode FIRST, then at most one prefill dispatch of at most that many
  prompt tokens — a long prompt advances one fixed-size chunk per step
  (each chunk rides the existing length-bucket jit cache; chunk k>0
  attends to the pages chunks 0..k-1 wrote through the same ctx-merge
  path prefix-cache hits use), so a 512-token arrival bounds a running
  request's inter-token gap by one chunk instead of one whole prompt.
- admission is page-budget- and prefix-aware: when the queue head does
  not fit the page headroom, requests further back whose prompt prefix
  is already cached may co-admit ahead of it (their cached pages make
  them nearly free), and preemption picks its victim by reclaimable
  page count (pages shared with other live requests free nothing).
- `spec_lookahead > 0` adds prompt-lookup speculative decoding: a
  greedy slot with no in-flight work drafts up to that many tokens from
  its own prompt+output n-grams, one prefill-shaped dispatch verifies
  the whole draft (argmax at every position), and the harvest accepts
  the longest prefix whose draft tokens match the model's own argmax —
  bit-exact vs plain greedy decode by construction. Draft page writes
  past the accepted prefix sit beyond the request's total and are
  rewritten by the next dispatch before they ever become visible.

A model's FAMILY says what its dispatches count and which options it
cannot be given (serve/llm/stage.py: `model_family`; refused at
construction, by name). Where its layers keep state a DECODE SLOT beside
the pages, a request's slot is assigned at admission, its prefill row
leaves its final state there, a decode dispatch leaves every slot it does
not decode bit for bit, and prefix reuse is off.

A prompt is prefilled in the PASSES that cost least (`plan_passes`), on
the default scheduler: full buckets, then the smallest bucket that holds
what is left, where that computes enough less padding than the one bucket
that holds the prompt to pay for a pass more; a prompt longer than the
largest bucket (a preempted request's folded prompt too) starts with
passes of the largest. Every pass is one row of `_dispatch_prefill_batch`
that starts at the request's `n_prefilled` mark, attends to the pages the
earlier passes wrote (the path prefix hits use) and, for a model with
per-slot state, continues from the slot; only the last pass samples. All
of a prompt's passes are enqueued in the step that admits it, and the
device runs them in order. A family whose prefill cannot resume
(`RESUMES_PREFILL` False: models/jamba.py) prefills every prompt whole and
still raises past its largest bucket.
"""

from __future__ import annotations

import atexit
import dataclasses
import functools
import math
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

import numpy as np

from ...util import tracing
from .cache import OutOfPages, PageAllocator, prefix_reuse_unsound
from .stage import (_MAX_TOP_K, StageCompute, model_family,
                    serve_model_config)

WAITING, RUNNING, FINISHED = "WAITING", "RUNNING", "FINISHED"
# where a step's nanoseconds go (indices into LLMEngine._phase_ns, in the
# order of the `engine.step` record's fields)
_INTAKE, _ADMIT, _DISPATCH_PREFILL, _DISPATCH_DECODE, _FETCH, _HARVEST = \
    range(6)


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0  # 0 => greedy
    top_k: int = 0            # 0 => full vocab; bounded by 64 (stage.py:
                              # _MAX_TOP_K, the width of the on-device
                              # sampler's top_k; add_request refuses more)
    stop_token_ids: tuple = ()
    seed: Optional[int] = None  # None => engine-level RNG
    # disaggregation: stop after the first token and stash the request's
    # KV blob for pop_extracted() (gathered inside step(), on the driver
    # thread, so no reader ever races the donated page buffers)
    prefill_only: bool = False


@dataclasses.dataclass
class Request:
    request_id: str
    prompt_ids: List[int]
    sampling: SamplingParams
    state: str = WAITING
    pages: List[int] = dataclasses.field(default_factory=list)
    n_cached: int = 0            # tokens restored from the prefix cache
    output_ids: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    last_page_hash: Optional[int] = None
    n_hashed: int = 0            # tokens already entered into prefix cache
    arrival_t: float = dataclasses.field(default_factory=time.monotonic)
    dispatched_t: Optional[float] = None  # first prefill dispatch (TTFT
                                          # queue/prefill split)
    # absolute expiry in the time.monotonic() domain (converted from the
    # wall-clock deadline at add_request); an expired WAITING entry is
    # PRUNED at batch admission instead of burning prefill compute on a
    # request whose client already gave up
    deadline_mono: Optional[float] = None
    slot: int = -1               # decode slot while RUNNING
    planned_out: int = 0         # tokens dispatched (>= len(output_ids))
    decode_ready: bool = False   # prefill harvested; slot may decode
    # prompt tokens whose KV is dispatched into pages (cache-restored +
    # prefilled chunks); < len(prompt_ids) while a chunked prefill is in
    # progress
    n_prefilled: int = 0
    # a speculative verify dispatch is in flight for this slot: the
    # device carry is not updated by verify, so no other decode dispatch
    # may touch the slot until the harvest resolves acceptance
    spec_inflight: bool = False
    # a model that generates by diffusion over blocks. `block_pending`: a
    # block of this request went out from its slot (the slot's carry holds
    # or will hold it: the next block's program opens over it);
    # `block_unsettled`: the newest block HARVESTED has no harvested
    # successor yet. Both end with the slot (`_release_slot`)
    block_pending: bool = False
    block_unsettled: bool = False
    # the request's timeline for its `engine.request` flight record, on
    # the recorder's clock (ray_tpu/util/tracing.py); arrival_t and the
    # deadline logic stay on time.monotonic()
    arrival_ns: int = dataclasses.field(default_factory=tracing.now_ns)
    admitted_ns: Optional[int] = None    # slot and pages granted
    dispatched_ns: Optional[int] = None  # first prefill dispatch; reset
                                         # by a preemption as dispatched_t
    first_token_ns: Optional[int] = None
    # the device's side of `first_token_ns - dispatched_ns`, from the
    # stamps of the programs that carried the prompt's passes (_harvest):
    # their device time, the last pass's end, and whether every stamp of
    # them was exact (else the host came late to one of them and the
    # parts are bounds); reset by a preemption with dispatched_ns
    prefill_device_ns: int = 0
    prefill_end_ns: Optional[int] = None
    parts_exact: bool = True
    preemptions: int = 0
    n_folded: int = 0            # output tokens a preemption folded into
                                 # prompt_ids
    n_passes: int = 0            # prefill rows dispatched since admission

    @property
    def total_len(self) -> int:
        return len(self.prompt_ids) + len(self.output_ids)


def _cap_total(req: Request, max_model_len: int) -> int:
    """Hard ceiling on a request's cache-visible length: in-jit clamps
    mask every write past it, so speculative decode chunks can run beyond
    the stop without corrupting pages or block-table indexing."""
    return min(len(req.prompt_ids) + req.sampling.max_tokens + 1,
               max_model_len)


@dataclasses.dataclass
class OutputDelta:
    request_id: str
    new_token_ids: List[int]
    finished: bool
    finish_reason: Optional[str] = None
    # a model that generates by diffusion over blocks: the denoising pass
    # that fixed each of `new_token_ids` (one block's tokens, in position
    # order). None for every other model.
    fixed_pass: Optional[List[int]] = None


@dataclasses.dataclass
class EngineConfig:
    model: str = "tiny"
    model_overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)
    page_size: int = 16
    num_pages: int = 256
    max_model_len: int = 512
    max_batch: int = 8
    prefill_buckets: tuple = (32, 64, 128, 256, 512)
    eos_token_id: Optional[int] = None
    seed: int = 0
    dtype: str = "bfloat16"
    # tensor-parallel degree: >1 shards params (megatron-style, by the
    # logical axis rules shared with training) and the paged KV cache's
    # Hkv axis over a tp mesh built from the first `tp` local devices
    # (serve/llm/sharding.py). 1 = single-device fast path. An explicit
    # mesh passed to LLMEngine(mesh=...) overrides this degree.
    tp: int = 1
    # decode steps fused into ONE device dispatch (lax.scan): amortizes
    # per-dispatch host overhead. Trade-off: token delivery is chunked
    # and a request may compute up to K-1 tokens past its stop condition.
    decode_steps_per_dispatch: int = 1
    # decode dispatches kept in flight ahead of the harvest point. Depth
    # d hides d-1 fetch syncs behind device compute;
    # tokens/pages computed past a stop are dropped at harvest. 1 =
    # fully synchronous (round-2 behavior).
    pipeline_depth: int = 2
    # rows per prefill dispatch AT MOST (and the single compiled row
    # count per length bucket: a dispatch of fewer requests runs the same
    # program over its real rows only). A burst larger than this
    # prefills in waves: the waves pipeline on-device, so total compute
    # is unchanged but the first wave's tokens surface after only its
    # own share. None => max_batch // 2.
    prefill_wave_size: Optional[int] = None
    # token-budget scheduling: >0 caps each step's prefill work at this
    # many prompt tokens (rounded up to a page multiple, clamped to the
    # largest bucket) and interleaves it AFTER the running slots' fused
    # decode — a long prompt prefills in fixed-size chunks across steps
    # instead of stalling every running request for one whole prompt.
    # Trades ~1 dispatch of pipeline depth for bounded inter-token gaps.
    # 0 = legacy prefill-priority scheduling (whole prompts first).
    prefill_chunk_tokens: int = 0
    # prompt-lookup speculative decoding: >0 drafts up to this many
    # tokens per idle greedy slot from the request's own prompt+output
    # n-grams (no draft model) and verifies the draft in ONE
    # prefill-shaped dispatch; the longest argmax-matching prefix is
    # accepted, so one dispatch can emit many tokens on repetitive
    # output. Greedy-only and bit-exact by construction. 0 = off.
    spec_lookahead: int = 0
    # pipeline parallelism (serve/llm/pp.py PipelinedEngine): >1 splits
    # the layer stack into `pp` stage engines, each its own worker
    # process on its own chip gang, chained rank->rank by compiled-DAG
    # channels. The scheduler (this class) runs on rank 0 unchanged;
    # only the three _compute_* seams and _fetch_tokens change. pp must
    # divide num_layers. Composes with tp INSIDE each stage (each stage
    # process shards its params/KV slice over its own tp-chip mesh).
    pp: int = 1
    # decode slot groups under pp — the microbatches that keep S stages
    # busy (a slot's next input token is the previous tick's output, so
    # consecutive ticks of ONE group can never overlap; groups of
    # DIFFERENT slots can). 0 => max(2, 2*(pp-1)), the classic
    # fill+drain bound. Ignored when pp == 1.
    pp_microbatches: int = 0
    # bound on one pipelined result fetch (harvest-side ref.get): a
    # stage rank that dies mid-flight writes no sentinel, so the fetch
    # times out — the engine then probes the gang and raises a TYPED
    # ActorDiedError/GetTimeoutError instead of hanging. Ignored when
    # pp == 1.
    pp_fetch_timeout_s: float = 60.0


def refuse(config: EngineConfig, model_cfg, mesh=None,
           handoff: bool = False) -> None:
    """An engine option that is set (`handoff`: the disaggregated prefill ->
    decode hand-off is asked for), and that the model's family lists in its
    `CANNOT_BE_GIVEN` (stage.py: model_family), is refused by name with the
    family's reason. How an option reads when it is set is written here."""
    block = getattr(model_cfg, "block_length", 0)
    what, why = getattr(model_family(config.model), "CANNOT_BE_GIVEN",
                        ("", {}))
    asked = {
        "max_model_len": block and config.max_model_len % block
        and f"max_model_len={config.max_model_len}",
        "spec_lookahead": config.spec_lookahead > 0
        and f"spec_lookahead={config.spec_lookahead}",
        "prefill_chunk_tokens": config.prefill_chunk_tokens > 0
        and f"prefill_chunk_tokens={config.prefill_chunk_tokens}",
        "tp": (config.tp > 1 or mesh is not None)
        and (f"tensor parallelism (tp={config.tp}, mesh="
             f"{'given' if mesh is not None else None})"),
        "pp": config.pp > 1 and f"pipeline parallelism (pp={config.pp})",
        "handoff": handoff
        and ("the disaggregated prefill/decode hand-off (prefill_only, "
             "extract_kv, inject_request)"),
    }
    for option, how in asked.items():
        if how and option in why:
            error = (ValueError if option == "max_model_len"
                     else NotImplementedError)
            raise error(f"model {config.model!r} {what.format(cfg=model_cfg)}"
                        f": {how} {why[option]}")


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds the largest bucket {buckets[-1]}")


# The chip's balance, operations a byte of memory traffic in bf16: 197e12 /
# 819e9 (TPU v5e; ops/grouped_matmul.py's BALANCE_ROWS is its power of two).
# It is also the TOKENS at which a prefill pass's products (2 operations a
# parameter and token) take as long as reading its weights (2 bytes a
# parameter): a pass of fewer tokens costs the weight read all the same.
_BALANCE_TOKENS = 240
# What a (query, key) pair that the flash forward VISITS costs, a layer and
# head, in parameters a token multiplies: its 4 x head_dim operations at the
# share of the matmul peak the kernel reaches on the blocks it runs. Timed
# (benchmarks/prefill_split_probe.py, PERF.md section 6, PR 39): 2.1-2.6e-6
# ms a pair over Mistral's 16 layers x 32 heads against 0.047 ms a token
# over its 3.76e9 parameters. Timed again on the forward PR 45 rewrote:
# 2.2-2.4e-6 fresh (2.5 before, same call), a resumed pass's 3.1-3.5e-6
# where 3.1-3.6: a twentieth, so the constant stands (and no prompt of the
# cells' cycles plans otherwise down to 0.7 of it).
_PAIR_PARAMS = 375


# a model's kinds of attention layer, (layers, window or None) each, and
# (layers, window or None, query heads) where the kinds' heads differ: one
# kind, whole contexts, unless the family says otherwise (`attention_kinds`)
_ONE_KIND = ((1, None),)


def _kind_shares(kinds: tuple) -> tuple:
    """((share, window or None), ...) for `PassCost`: each kind's share of
    the model's (layer, head) pairs, which is its share of the layers
    where the kinds have one head count."""
    weights = [layers * (heads[0] if heads else 1)
               for layers, _, *heads in kinds]
    total = sum(weights)
    return tuple((w / total, kind[1]) for w, kind in zip(weights, kinds))


def _attn_visits(bucket: int, width: int, real=None, ctx=None,
                 kinds: tuple = _ONE_KIND) -> tuple:
    """`ops.paged_attention.prefill_block_visits`: what a pass's flash
    calls visit (imported late: this module loads without JAX), summed
    over the layers of each kind (one layer of one kind: the call
    itself)."""
    from ...ops.paged_attention import prefill_block_visits

    return tuple(map(sum, zip(*(
        [layers * n for n in prefill_block_visits(
            bucket, width, real, ctx, window)]
        for layers, window, *_ in kinds))))


@dataclasses.dataclass(frozen=True)
class PassCost:
    """What one prefill pass of `bucket` tokens, `real` of them a prompt's
    and the rest padding, behind `ctx` tokens of context costs, in tokens'
    worth of the model's products (benchmarks/prefill_split_probe.py times
    every term on the chip; PERF.md section 6, PR 39):

    - `max(bucket, floor)`: its products, padding included, or the weight
      read under them. `floor` is the balance x the weights a pass reads
      over the parameters a token multiplies (1 for a dense model);
    - its attention, `pair` a (query, key) pair the flash kernel VISITS
      (ops/flash_attention.py), and that is real blocks only: the blocks of
      `real` queries against their own causal keys and against `ctx`
      columns of context, whatever the bucket pads and the block table's
      width. `pair` is the kernel's products a pair (one a layer and head)
      at the share of the matmul peak it reaches, over the parameters a
      token multiplies;
    - if it RESUMES (`ctx` > 0: it starts mid-prompt or behind a cached
      prefix): one `floor` more, the bar a split has to clear (the
      dispatch, a second weight read, the gather of the table's width).

    `kinds`: the model's kinds of attention layer as SHARES of its (layer,
    head) pairs, ((share, window or None), ...) (`_kind_shares`): a pair of
    a layer with a sliding window is not a pair of a full one (a pass
    behind 16k tokens of context makes a sixteenth of them there), so the
    pairs are each kind's own, and a kind with more query heads makes more
    of them a layer."""
    floor: float
    pair: float
    kinds: tuple = _ONE_KIND

    def __call__(self, bucket: int, real: int, ctx: int) -> float:
        # (any table width that holds the context: the lengths cut the rest)
        pairs = _attn_visits(bucket, ctx, real, ctx, self.kinds)[1]
        cost = max(bucket, self.floor) + self.pair * pairs
        return cost + self.floor if ctx else cost


def plan_passes(n_new: int, buckets, page: int, cost: PassCost,
                ctx: int = 0) -> List[int]:
    """The length bucket of each prefill pass for `n_new` prompt tokens
    behind `ctx` tokens already in pages (a cached prefix: the first pass
    resumes too): every pass but the last is FULL (it prefills exactly its
    bucket, so where it ends is page-aligned: the context-merge path's
    contract), the last is the smallest bucket that holds the rest.

    While the rest exceeds the largest bucket it takes passes of the
    largest. Of the ways to cover what is then left, the one `cost` says
    is cheapest, the single bucket where nothing is cheaper: one bucket
    is left for two only where that computes enough less padding to pay
    for a pass more and the context it attends. Never more tokens than the
    single bucket."""
    return list(_plan(n_new, tuple(buckets), page, cost, ctx))


@functools.lru_cache(maxsize=4096)
def _plan(n_new: int, buckets: tuple, page: int, cost: PassCost,
          ctx: int) -> tuple:
    """`plan_passes`, kept for the lengths a deployment sees again: the
    search asks `cost` some fifty times for a long prompt over five
    buckets, 0.2-0.5 ms of an admission's host time."""
    largest = buckets[-1]
    end = ctx + n_new
    lead = []
    while n_new > largest:
        lead.append(largest)
        n_new -= largest
    full = [b for b in buckets if b % page == 0]
    memo: Dict[tuple, tuple] = {}

    def cheapest(rest: int, top: int) -> tuple:
        """(cost, buckets) for the last `rest` tokens (so behind `end -
        rest` of context), full passes from `full[:top]`, largest first."""
        got = memo.get((rest, top))
        if got is None:
            last = _bucket(rest, buckets)
            got = (cost(last, rest, end - rest), (last,))
            for i in reversed(range(top)):
                if full[i] >= rest:
                    continue
                c, tail = cheapest(rest - full[i], i + 1)
                c += cost(full[i], full[i], end - rest)
                # (cheaper by more than rounding: of two orders of the
                # same passes the larger bucket goes first)
                if c + 1e-6 < got[0] and full[i] + sum(tail) <= last:
                    got = (c, (full[i],) + tail)
            memo[(rest, top)] = got
        return got

    return tuple(lead) + cheapest(n_new, len(full))[1]


def _settle(inflight: List[dict], wait) -> None:
    """What is left of `LLMEngine.close` when the engine itself is gone:
    `wait` for the tokens of every dispatch in `inflight`, oldest first,
    and forget them. A program returns its tokens and the donated pool
    together, so when the last tokens are on the host nothing is queued on
    the device and no copy to the host is pending. A process that ends
    otherwise can die in the TPU client's teardown (SIGSEGV in
    `xla::TpuClient::pending_event_logger()` under
    `TpuRawBuffer::CopyToLiteralAsync()`: a program ends and its copy
    starts on a client that is going; after the result line, in 2 of 17
    benchmark runs of PR 37's tree and 1 of 26 of PR 35's: PERF.md section
    6, PR 39)."""
    while inflight:
        try:
            wait(inflight.pop(0)["toks"])
        except Exception:  # noqa: BLE001  # rtpulint: ignore[RTPU006] — the engine is being left: a handle that cannot be fetched has no copy pending
            pass


class LLMEngine:
    """Single-process engine. Not thread-safe except `add_request`/`abort`
    (which only touch the locked intake queue); one driver thread calls
    `step()`."""

    def __init__(self, config: EngineConfig, params=None, mesh=None):
        self.config = config
        refuse(config, serve_model_config(config), mesh)
        self._build_compute(params, mesh)
        self.max_pages_per_seq = config.max_model_len // config.page_size

        self.allocator = PageAllocator(
            config.num_pages, config.page_size,
            shard_degree=(self.sharding.tp if self.sharding else 1))
        self._init_host_state()

    def _build_compute(self, params, mesh) -> None:
        """Device-state construction seam: one StageCompute over all
        layers, in this process. The pipelined engine (serve/llm/pp.py)
        overrides this to place each layer slice in its own stage worker
        process; every host-side scheduler structure built after it
        (allocator, queues, slots, prefix cache) is backend-agnostic and
        shared verbatim."""
        self.compute = c = StageCompute(self.config, mesh=mesh,
                                        params=params)
        self.model_cfg, self.model, self.sharding = (
            c.model_cfg, c.model, c.sharding)
        self._attention, self._device = c.attention, c.device
        c.step_seq = lambda: self._step_seq

    # the whole param tree and the pool, where callers have always found
    # them (None under pp: the stage workers hold them)
    compute: Optional[StageCompute] = None
    params = property(lambda self: self.compute and self.compute.params)
    kv_pages = property(lambda self: self.compute and self.compute.kv_pages)

    def _init_host_state(self) -> None:
        config = self.config
        self._intake: List[Request] = []
        self._intake_lock = threading.Lock()
        # a step from its first line to its last, against `close`
        self._step_lock = threading.Lock()
        self._aborted: set = set()
        self._injections: List[tuple] = []
        self.extracted: Dict[str, Dict[str, Any]] = {}
        # unclaimed prefill KV blobs are dropped after a TTL or past a
        # count cap — a decode caller that aborts between prefill_done
        # and pop_extracted must not leak dense KV on a long-lived replica
        self._extracted_order: List[tuple] = []  # (request_id, ts)
        self.extracted_ttl_s: float = 120.0
        self.extracted_max: int = 64
        self.waiting: List[Request] = []
        self.running: List[Request] = []
        self.requests: Dict[str, Request] = {}
        # WAITING entries pruned for an expired deadline (stats() key;
        # the Serve layer surfaces them as typed RequestExpiredError).
        # RUNNING slots whose deadline passes mid-decode count here too.
        self._expired_total = 0
        # scheduler counters (stats() keys, exported as rtpu_llm_* by
        # the serve layer): page-pressure preemptions and speculative
        # draft/accept volumes
        self._preempted_total = 0
        self._spec_drafted_total = 0
        self._spec_accepted_total = 0
        # (head request_id, times passed) — bounds prefix-aware
        # skip-ahead unfairness against one page-blocked queue head
        self._head_overtaken: tuple = (None, 0)
        self._pending_deltas: List[OutputDelta] = []
        # the single compiled prefill row count (the MOST rows a prefill
        # dispatch has; it computes its real rows only) — one expression,
        # used by dispatch, split and warmup
        self._wave_rb: int = (config.prefill_wave_size
                              or max(1, config.max_batch // 2))
        # slots: fixed decode row assignment while a request is RUNNING
        self._free_slots: List[int] = list(range(config.max_batch))
        self._slot_req: Dict[int, Request] = {}
        # pending-first-decode override: slot -> host-known pending token
        # (set after prefill harvest / injection / re-admission)
        self._slot_override: Dict[int, int] = {}
        # FIFO of in-flight dispatches awaiting harvest; each dict is the
        # dispatch's `engine.dispatch` flight record in the making
        self._inflight: List[dict] = []
        # flight recorder (ray_tpu/util/tracing.py): sequence numbers of
        # steps and dispatches, the running step's nanoseconds by phase,
        # and cumulative totals of the same facts for stats()
        self._step_seq = 0
        self._dispatch_seq = 0
        self._phase_ns = [0] * 6
        self._totals = dict.fromkeys((
            "steps_total", "prefill_dispatches_total",
            "decode_dispatches_total", "prefill_tokens_total",
            "prefill_padded_tokens_total", "decode_rows_total",
            "decode_ctx_tokens_total", "prefill_passes_total",
            "prefill_resumed_passes_total", "prefill_split_prompts_total",
            "prefill_attn_blocks_total",
            "prefill_attn_blocks_skipped_total",
            "prefill_attn_blocks_masked_total",
            "drawn_dispatches_total"), 0)
        cfg_m = self.model_cfg
        family = model_family(self.config.model)
        # a model that generates by diffusion over blocks: its block
        # length (0: one token a row and step), and where a prefill pass
        # may start (a page boundary that is a block boundary too)
        self._block = getattr(cfg_m, "block_length", 0)
        self._pass_align = math.lcm(config.page_size, self._block or 1)
        # layers keep state a decode slot: a prefill row is told its slot
        self._slot_state = getattr(cfg_m, "n_slot_state_layers", 0) > 0
        # why a page found by its hash may not be reused (None: it may)
        # (in the family's own words where it names `prefix_reuse` among
        # what it cannot be given; `stats()` then says so too)
        named = getattr(family, "CANNOT_BE_GIVEN", ("", {}))[1].get(
            "prefix_reuse")
        self._prefix_off = None
        if self._slot_state:
            self._prefix_off = named or ("a page found by its content hash "
                                         "carries no recurrent state")
        elif self._block:
            self._prefix_off = prefix_reuse_unsound(config.page_size,
                                                    self._block)
        self._prefix_why_shown = bool(self._block or named)
        if self._prefix_off:
            self._totals["prefix_reuse_refused_total"] = 0
        # what the family's dispatches count (stage.py: model_family): its
        # keys of `_totals`, and the record fields every dispatch has
        self.family_facts = family.dispatch_facts(cfg_m, config)
        self._rec_constant: Dict[str, Any] = {}
        for facts in self.family_facts:
            self._totals.update(
                (key, 0) for key in facts.STATS if key.endswith("_total"))
            self._rec_constant.update(getattr(facts, "constant", ()))
        # a block-sparse family's selection rule (None: every prefill
        # pass's attention is the flash kernel's, whose visits are counted)
        self._sparse = getattr(cfg_m, "sparse", None)
        # a prompt is prefilled in the passes that cost least
        # (`plan_passes`), by the chip's balance and the two ratios the
        # family answers for its model
        self._resumes = family.RESUMES_PREFILL
        self._pass_cost = None
        # the kinds of attention layer whose flash visits are counted
        self._attn_kinds = (family.attention_kinds(cfg_m) if hasattr(
            family, "attention_kinds") else _ONE_KIND)
        if self._resumes:
            weights, scores = family.pass_cost_ratios(cfg_m)
            self._pass_cost = PassCost(
                floor=_BALANCE_TOKENS * weights,
                pair=_PAIR_PARAMS * scores,
                kinds=_kind_shares(self._attn_kinds))
        self._head_at_gather = getattr(family, "HEAD_AT_GATHER", False)
        self._queue_wait_ns_total = 0
        # the device's timeline as the host can stamp it (_device_stamps):
        # the estimated end of the last program harvested and whether it
        # is exact, the running step's two facts, the cumulative three
        self._device_end_ns: Optional[int] = None
        self._device_end_exact = True
        self._step_fetch_blocked = 0
        self._step_device_idle_ns = 0
        self._device_busy_ns_total = 0
        self._device_idle_ns_total = 0
        self._harvests_late_total = 0
        # left without being asked (dropped, or the interpreter exits):
        # `close`'s drain all the same. Registered with atexit HERE, after
        # JAX's own handlers (the constructor has built the programs), so
        # that it runs before them, while the client is whole
        self._closed = False
        self._leave = weakref.finalize(self, _settle, self._inflight,
                                       self._await_handle)
        atexit.register(self._leave)

    # ----------------------------------------------------------- intake

    def add_request(self, request_id: str, prompt_ids: List[int],
                    sampling: Optional[SamplingParams] = None,
                    deadline: Optional[float] = None) -> None:
        """``deadline`` is the request's ABSOLUTE wall-clock expiry
        (time.time() domain, as propagated by the Serve admission
        plane); it is converted to the engine's monotonic domain here so
        queue-time pruning is immune to wall-clock steps."""
        sampling = sampling or SamplingParams()
        if sampling.prefill_only:
            self._refuse_handoff()
        if len(prompt_ids) + 1 > self.config.max_model_len:
            raise ValueError(
                f"prompt of {len(prompt_ids)} tokens exceeds max_model_len "
                f"{self.config.max_model_len}")
        if sampling.top_k > _MAX_TOP_K:
            raise ValueError(
                f"top_k={sampling.top_k} exceeds the on-device sampler "
                f"bound of {_MAX_TOP_K}")
        req = Request(request_id, list(prompt_ids), sampling)
        if deadline is not None:
            req.deadline_mono = time.monotonic() + (deadline - time.time())
        with self._intake_lock:
            self._intake.append(req)

    def abort(self, request_id: str) -> None:
        with self._intake_lock:
            self._aborted.add(request_id)
            # drop any unclaimed prefill KV for this request immediately
            # (same lock as the engine thread's bookkeeping: an append
            # racing an unlocked rebuild could strand a blob past the TTL)
            if self.extracted.pop(request_id, None) is not None:
                self._extracted_order[:] = [
                    e for e in self._extracted_order if e[0] != request_id]

    def _evict_extracted(self) -> None:
        now = time.monotonic()
        with self._intake_lock:
            while self._extracted_order and (
                    len(self._extracted_order) > self.extracted_max
                    or now - self._extracted_order[0][1] > self.extracted_ttl_s):
                rid, _ = self._extracted_order.pop(0)
                self.extracted.pop(rid, None)

    def has_work(self) -> bool:
        with self._intake_lock:
            if self._intake or self._injections:
                return True
        return bool(self.waiting or self.running or self._inflight
                    or self._pending_deltas)

    def close(self) -> None:
        """Leave the engine: no step dispatches any more (a running one
        ends first), the tokens of every dispatch in flight are fetched,
        so that no copy to the host is pending, and the pool is ready:
        nothing of this engine is queued on the device when the process
        goes on to exit (`_settle`). Open requests are not finished and
        get no delta: whoever closes has stopped listening. Idempotent;
        an engine that is dropped, or alive when the interpreter exits,
        drains the same way without being asked."""
        with self._step_lock:
            self._closed = True
            self._leave()
            if self.compute is not None:
                import jax

                jax.block_until_ready(self.kv_pages)

    # ------------------------------------------------------------- step

    def step(self) -> List[OutputDelta]:
        """One scheduler iteration. Two scheduling modes share the same
        dispatch/harvest machinery:

        - legacy (prefill_chunk_tokens == 0): admit + prefill prompts
          first, each to its end in the passes that cost least
          (prefill-priority, like vLLM's default), fill
          the pipeline with fused decode chunks, harvest the oldest
          in-flight dispatch (blocking only when its transfer has not
          landed yet).
        - token budget (prefill_chunk_tokens > 0): decode FIRST — the
          running slots' next tokens never queue behind a new prompt —
          then at most one prefill dispatch of at most the budgeted
          prompt tokens (long prompts advance one chunk per step), then
          harvest enough dispatches to keep the backlog under the
          pipeline depth, so a running slot's inter-token gap is one
          decode chunk + one prefill chunk instead of one whole prompt.

        Every step leaves one `engine.step` flight record: its start and
        end and the nanoseconds of each phase (admit, dispatch_prefill,
        fetch and harvest are timed where they happen, further down).
        """
        with self._step_lock:
            if self._closed:
                raise RuntimeError("step() on a closed engine")
            return self._step()

    def _step(self) -> List[OutputDelta]:
        """`step`, under its lock."""
        self._step_seq += 1
        self._totals["steps_total"] += 1
        phase = self._phase_ns
        phase[:] = (0, 0, 0, 0, 0, 0)
        self._step_fetch_blocked = self._step_device_idle_ns = 0
        with tracing.region("rtpu.engine.step") as whole:
            deltas: List[OutputDelta] = list(self._pending_deltas)
            self._pending_deltas.clear()
            with tracing.region("rtpu.engine.intake") as r:
                self._drain_intake(deltas)
                self._prune_expired_running(deltas)
                self._prune_expired_waiting(deltas)
                self._try_admit_injection(deltas)
            # an injection drains the pipeline first: that time is
            # already under fetch and harvest
            phase[_INTAKE] = r.ns - phase[_FETCH] - phase[_HARVEST]
            chunked = self.config.prefill_chunk_tokens > 0
            depth = max(1, int(self.config.pipeline_depth))
            if not chunked:
                self._dispatch_prefills()
            with tracing.region("rtpu.engine.dispatch_decode") as r:
                while (len(self._inflight) < depth
                       and (self._dispatch_spec()
                            or self._dispatch_decode_chunk())):
                    pass
            phase[_DISPATCH_DECODE] = r.ns
            if chunked:
                self._dispatch_prefill_chunks()
                if self._inflight:
                    self._harvest(self._inflight.pop(0), deltas)
                while len(self._inflight) >= depth:
                    self._harvest(self._inflight.pop(0), deltas)
            elif self._inflight:
                self._harvest(self._inflight.pop(0), deltas)
        tracing.record("engine.step", (
            self._step_seq, whole.start_ns, whole.end_ns, *phase,
            len(self.running), len(self.waiting), self._step_fetch_blocked,
            self._step_device_idle_ns))
        return deltas

    def _drain_pipeline(self, deltas: List[OutputDelta]) -> None:
        """Harvest every in-flight dispatch (no new dispatches). Needed
        before any eager read/write of the page buffers (extract/inject):
        an eager `.at[].set` forks the buffer, silently dropping writes
        from dispatches still in flight."""
        while self._inflight:
            self._harvest(self._inflight.pop(0), deltas)

    def _drain_intake(self, deltas: List[OutputDelta]) -> None:
        with self._intake_lock:
            intake, self._intake = self._intake, []
            aborted, self._aborted = self._aborted, set()
        self.waiting.extend(intake)
        for req in intake:
            self.requests[req.request_id] = req
        for rid in aborted:
            req = self.requests.get(rid)
            if req and req.state != FINISHED:
                self._finish(req, "aborted")
                deltas.append(OutputDelta(rid, [], True, "aborted"))

    @staticmethod
    def _count_engine_expired() -> None:
        try:  # serve metrics are advisory; the engine runs standalone
            # (batch workers, tests) without them
            from .. import admission

            admission.count_shed(admission.SHED_ENGINE_EXPIRED)
        except Exception:  # rtpulint: ignore[RTPU006] — metric registration may fail outside a serve process; pruning must not
            pass

    def _prune_expired_running(self, deltas: List[OutputDelta]) -> None:
        """Shed RUNNING requests whose propagated deadline has passed: a
        slot still decoding for a client that already gave up is pure
        dead work AND pins pages + a batch slot other requests need.
        Free both at step start and emit the typed "expired" delta (the
        Serve layer maps it to RequestExpiredError). Dispatches already
        in flight for the slot are discarded at harvest — the same
        mechanism abort uses — and their page writes land beyond any
        live request's visible range."""
        if not self.running:
            return
        now = time.monotonic()
        expired = [r for r in self.running
                   if r.deadline_mono is not None
                   and now >= r.deadline_mono]
        for req in expired:
            self._finish(req, "expired")
            self._expired_total += 1
            deltas.append(OutputDelta(req.request_id, [], True,
                                      "expired"))
            self._count_engine_expired()

    def _prune_expired_waiting(self, deltas: List[OutputDelta]) -> None:
        """Shed expired WAITING entries at batch admission: a request
        whose propagated deadline passed while it sat in the queue must
        never reach prefill — its client already gave up, and the pages
        plus compute belong to requests that can still meet their SLO.
        Touches only queue bookkeeping (WAITING entries hold no pages or
        slots), so it is unit-testable without a built model."""
        if not self.waiting:
            return
        now = time.monotonic()
        kept: List[Request] = []
        for req in self.waiting:
            if req.deadline_mono is not None and now >= req.deadline_mono:
                req.state = FINISHED
                req.finish_reason = "expired"
                self.requests.pop(req.request_id, None)
                self._record_request(req)
                self._expired_total += 1
                deltas.append(OutputDelta(req.request_id, [], True,
                                          "expired"))
                self._count_engine_expired()
            else:
                kept.append(req)
        self.waiting[:] = kept

    # bounded admission lookahead: how far past the head of the waiting
    # queue prefix-aware admission may scan when the head does not fit
    # the page budget (only cached-prefix requests may skip ahead)
    _ADMIT_LOOKAHEAD = 32
    # bounded unfairness: how many requests may pass ONE blocked head
    # before skip-ahead pauses (sustained prefix-sharing traffic would
    # otherwise absorb every freed page and starve the head forever)
    _HEAD_OVERTAKE_CAP = 32

    def _admit_one(self, burst_prefixes: set = None) -> Optional[Request]:
        """Admit one waiting request (slot + page budget permitting)
        WITHOUT prefilling; returns the request or None.

        FIFO first: the head of the queue is always tried. When the head
        does NOT fit the current page headroom, requests further back
        whose prompt prefix is already in the page cache may admit ahead
        of it (prefix-aware co-admission): their cached pages make them
        nearly free, and joining the wave that computed their prefix
        beats queueing behind a page-hungry stranger. At most
        _HEAD_OVERTAKE_CAP requests may pass one blocked head — past
        that, skip-ahead pauses until the head admits, so freed pages
        accumulate for it instead of being absorbed by an endless stream
        of cheap prefix-sharers. The lookahead is part of scheduler v2:
        with prefill_chunk_tokens == 0 admission is strict FIFO (head
        only), preserving the legacy scheduler's order exactly.

        A request whose leading page matches one already admitted THIS
        step is deferred: next step its prefix pages are computed and
        cached, so it shares them instead of prefilling the same content
        in parallel (in v2 mode a twin whose prefix is ALREADY cached
        co-admits instead of deferring)."""
        if not self.waiting or not self._free_slots:
            return None
        page = self.config.page_size
        legacy = self.config.prefill_chunk_tokens <= 0
        lookahead = 1 if legacy else self._ADMIT_LOOKAHEAD
        if self._prefix_off:
            # a page found by its content hash cannot be reused
            # (`_prefix_off` says why): no twin is deferred to share a
            # prefix, nothing is matched
            burst_prefixes = None
        head_id = self.waiting[0].request_id
        if self._head_overtaken[0] != head_id:
            self._head_overtaken = (head_id, 0)
        for qi in range(min(len(self.waiting), lookahead)):
            req = self.waiting[qi]
            if qi > 0 and self._head_overtaken[1] >= \
                    self._HEAD_OVERTAKE_CAP:
                return None  # head has been passed enough; let it age in
            first_hash = None
            if burst_prefixes is not None and len(req.prompt_ids) >= page:
                first_hash = self.allocator.chain_hash(
                    None, req.prompt_ids[:page])
                if first_hash in burst_prefixes:
                    continue  # wait one step; the prefix cache will hit
            if self._prefix_off:
                cached_pages, n_cached = [], 0
                self._totals["prefix_reuse_refused_total"] += 1
            else:
                cached_pages, n_cached = self.allocator.match_prefix(
                    req.prompt_ids)
            if qi > 0 and not cached_pages:
                continue  # only prefix-sharers may pass a blocked head
            # the prompt and the first token's place (a block model: its
            # first block's)
            first_end = (self._block_start(req) + self._block if self._block
                         else len(req.prompt_ids) + 1)
            need = -(-first_end // page) - len(cached_pages)
            if self.allocator.num_free() < need:
                self.allocator.release(cached_pages)
                self.allocator.stats["cache_hits"] -= len(cached_pages)
                continue  # page budget: scan on for a cached-prefix fit
            if first_hash is not None and (legacy or not cached_pages):
                # this admission will COMPUTE the prefix: defer same-
                # prefix twins one step so they share it from the cache.
                # v2 mode skips the mark when the prefix is already
                # cached (the twin co-admits); legacy mode always marks,
                # matching the pre-v2 scheduler's behavior exactly.
                burst_prefixes.add(first_hash)
            if qi > 0:
                self._head_overtaken = (head_id,
                                        self._head_overtaken[1] + 1)
            else:
                self._head_overtaken = (None, 0)
            self.waiting.pop(qi)
            self.allocator.note_prefix_lookup(len(req.prompt_ids),
                                              n_cached)
            new_pages = self.allocator.allocate(need)
            req.pages = cached_pages + new_pages
            req.n_cached = n_cached
            req.n_prefilled = n_cached
            req.n_hashed = n_cached
            req.last_page_hash = None
            if cached_pages:
                # Recompute the chain hash up to the cached boundary.
                h = None
                for i in range(len(cached_pages)):
                    h = self.allocator.chain_hash(
                        h, req.prompt_ids[i * page:(i + 1) * page])
                req.last_page_hash = h
            req.state = RUNNING
            req.slot = self._free_slots.pop(0)
            req.planned_out = 0
            req.n_passes = 0
            req.admitted_ns = tracing.now_ns()
            self._slot_req[req.slot] = req
            self.running.append(req)
            # nothing to prefill (a block model's prompt shorter than a
            # block, or cached to its last whole block): its first block
            # may go at once
            req.decode_ready = req.n_prefilled >= self._prefill_end(req)
            return req
        return None

    def _prefill_end(self, req: Request) -> int:
        """The prompt tokens a prefill writes keys for: all of them, and
        the last pass samples the first token; for a block model the
        prompt's WHOLE blocks, and no pass samples (the ragged tail opens
        the first block, `_dispatch_block`)."""
        n = len(req.prompt_ids)
        return n // self._block * self._block if self._block else n

    def _block_start(self, req: Request) -> int:
        """Where the next block of a block model's request begins: every
        token planned so far is settled or on its way."""
        return ((len(req.prompt_ids) + req.planned_out)
                // self._block * self._block)

    # ---------------------------------------------------------- compute

    # The compute seams + the harvest fetch: everything the scheduler
    # knows about the compute backend. The base engine enqueues its one
    # stage's programs (stage.py: OPERANDS names what each takes); the
    # pipelined engine (pp.py) overrides these to push frames through the
    # stage DAG and returns CompiledDAGRef handles instead of device
    # arrays.

    @staticmethod
    def _to_host_async(tokens):
        try:
            tokens.copy_to_host_async()
        except Exception:  # noqa: BLE001  # rtpulint: ignore[RTPU006] — optional D2H prefetch: CPU backends lack it; harvest blocks on the array either way
            pass
        return tokens

    def _compute_prefill(self, sb, rb, cp, n_rows, bt, total, ids,
                         positions, gather, temp, topk, keys, *slots):
        """One prefill dispatch over the first `n_rows` of the wave-sized
        arrays; returns the sampled-tokens handle the harvest will
        resolve via _fetch_tokens ([rb] int32). `slots` (a model with
        per-slot state, and only then): the decode slot of each row."""
        import jax.numpy as jnp

        return self._to_host_async(self.compute.run(
            "prefill", (sb, rb, cp), np.int32(n_rows), jnp.asarray(bt),
            jnp.asarray(total), jnp.asarray(ids), jnp.asarray(positions),
            jnp.asarray(gather), temp, topk, keys,
            *map(jnp.asarray, slots)))

    def _compute_verify(self, sb, rb, n_rows, bt, total, ids, positions):
        """One speculative verify dispatch over the first `n_rows`;
        returns the handle of every position's argmax ([rb, sb] int32)."""
        import jax.numpy as jnp

        return self._to_host_async(self.compute.run(
            "verify", (sb, rb), np.int32(n_rows), jnp.asarray(bt),
            jnp.asarray(total), jnp.asarray(ids), jnp.asarray(positions)))

    def _compute_decode(self, k_steps, mp, bt, total, caps, positions,
                        override_mask, override_ids, temp, topk,
                        keys_steps):
        """One fused K-step decode dispatch over the full slot set;
        returns the tokens handle ([K, S] int32 after _fetch_tokens)."""
        import jax.numpy as jnp

        return self._to_host_async(self.compute.run(
            "decode", (k_steps, mp), jnp.asarray(bt), jnp.asarray(total),
            jnp.asarray(caps), jnp.asarray(positions),
            jnp.asarray(override_mask), jnp.asarray(override_ids), temp,
            topk, jnp.asarray(keys_steps)))

    def _compute_block(self, key, bt, total, ids, masked, pending, temp,
                       topk, keys_steps):
        """One block program over the full slot set (stage.py:
        `_block_program`); returns the handle of its packed result."""
        import jax.numpy as jnp

        return self._to_host_async(self.compute.run(
            "block", key, jnp.asarray(bt), jnp.asarray(total),
            jnp.asarray(ids), jnp.asarray(masked), jnp.asarray(pending),
            temp, topk, jnp.asarray(keys_steps)))

    def _fetch_tokens(self, handle) -> np.ndarray:
        """Resolve a compute handle into host tokens (blocks until the
        async D2H copy lands; microseconds once it has)."""
        return np.asarray(handle)

    # the same wait with no engine left to take the tokens (`_settle`)
    _await_handle = staticmethod(np.asarray)

    @staticmethod
    def _handle_ready(handle) -> Optional[bool]:
        """Whether the program behind a compute handle has finished,
        asked just before its fetch (under a microsecond for a device
        array). None where the handle cannot say (pp.py), and the
        engine's records then carry no device stamps."""
        return handle.is_ready()

    def _dispatch_prefills(self) -> None:
        """Prefill-priority mode: admit as many waiting requests as
        slots/pages allow and prefill each in the passes `plan_passes`
        gives it. Every pass but a prompt's last is a dispatch of its own,
        ahead of the last passes, which go out in waves: one dispatch per
        length bucket (a dispatch per prompt would make TTFT linear in
        the queue), rows that resume apart from fresh ones (one resuming
        row gives a whole wave the program with a context part)."""
        admitted = []
        burst_prefixes: set = set()
        with tracing.region("rtpu.engine.admit") as r:
            while len(self.running) < self.config.max_batch:
                req = self._admit_one(burst_prefixes)
                if req is None:
                    break
                admitted.append(req)
        self._phase_ns[_ADMIT] += r.ns
        if not admitted:
            return
        wave = self._wave_rb
        buckets = self.config.prefill_buckets
        waves: Dict[tuple, List[tuple]] = {}
        for req in admitted:
            n_new = self._prefill_end(req) - req.n_prefilled
            if n_new <= 0:
                continue
            plan = (plan_passes(n_new, buckets, self._pass_align,
                                self._pass_cost, req.n_prefilled)
                    if self._resumes else [_bucket(n_new, buckets)])
            self._totals["prefill_split_prompts_total"] += (
                len(plan) > 1 and n_new <= buckets[-1])
            for sb in plan[:-1]:
                # a full pass, its own dispatch: the device runs them in
                # the order they are enqueued
                self._dispatch_prefill_batch(sb, [(req, sb)])
            waves.setdefault((plan[-1], req.n_prefilled > 0), []).append(
                (req, self._prefill_end(req) - req.n_prefilled))
        for (sb, _), group in waves.items():
            for i in range(0, len(group), wave):
                self._dispatch_prefill_batch(sb, group[i:i + wave])

    def _chunk_tokens(self) -> int:
        """prefill_chunk_tokens rounded UP to a page multiple (chunk
        boundaries stay page-aligned so every completed chunk's full
        pages enter the prefix cache) and clamped to the largest length
        bucket (a chunk must fit one compiled prefill shape)."""
        page = self._pass_align
        c = max(1, int(self.config.prefill_chunk_tokens))
        return max(page, min(-(-c // page) * page,
                             self.config.prefill_buckets[-1]))

    def _dispatch_prefill_chunks(self) -> None:
        """Token-budget mode: admit new requests and advance mid-prefill
        requests, together bounded by the per-step budget — ONE dispatch
        per step (rows share the chunk's length bucket), so the device
        work a step adds ahead of the next decode harvest is bounded by
        one prefill chunk.

        NEW admissions take the budget FIRST: a short prompt arriving
        while a long prompt is mid-prefill starts immediately inside
        this step's budget instead of waiting out the long prompt's
        remaining chunks — that ordering IS the head-of-line fix, and it
        cannot starve the long prompt because admissions stop once the
        batch is full while most steps see no arrivals at all. The
        leftover budget is split evenly across continuing mid-prefill
        requests (page-aligned shares) so concurrent long prompts
        advance together instead of strictly FIFO."""
        budget = self._chunk_tokens()
        page = self._pass_align

        def grant(req: Request, tokens: int) -> int:
            """Tokens this row may prefill now: a FINAL chunk takes its
            exact remainder; a non-final chunk rounds DOWN to a page
            multiple so every chunk boundary stays page-aligned (full
            pages enter the prefix cache; the ctx-merge path only ever
            sees the page-multiple starts prefix-cache hits produce)."""
            remaining = self._prefill_end(req) - req.n_prefilled
            if remaining <= tokens:
                return remaining
            return tokens // page * page

        rows: List[tuple] = []
        used = 0
        burst_prefixes: set = set()
        while (used < budget and len(rows) < self._wave_rb
               and len(self.running) < self.config.max_batch):
            with tracing.region("rtpu.engine.admit") as r:
                req = self._admit_one(burst_prefixes)
            self._phase_ns[_ADMIT] += r.ns
            if req is None:
                break
            n_new = grant(req, budget - used)
            if n_new > 0:
                rows.append((req, n_new))
                used += n_new
            # n_new == 0: admitted with < 1 page of budget left — it
            # holds its slot/pages and continues in the next step's wave
        continuing = [r for r in self.running
                      if r.state == RUNNING and not r.decode_ready
                      and 0 < self._prefill_end(r) - r.n_prefilled
                      and all(r is not q for q, _ in rows)]
        if continuing and used < budget:
            # even, page-aligned shares; the division remainder goes to
            # the FIRST continuing row so the full budget is dispatched
            share = max(page,
                        (budget - used) // len(continuing) // page * page)
            extra = max(0, (budget - used) - share * len(continuing))
            for idx, req in enumerate(continuing):
                if used >= budget or len(rows) >= self._wave_rb:
                    break
                n_new = grant(req, min(share + (extra if idx == 0 else 0),
                                       budget - used))
                if n_new <= 0:
                    continue
                rows.append((req, n_new))
                used += n_new
        if not rows:
            return
        sb = _bucket(max(n for _, n in rows), self.config.prefill_buckets)
        self._dispatch_prefill_batch(sb, rows)

    def _dispatch_prefill_batch(self, sb: int,
                                group: List[tuple]) -> None:
        """One prefill dispatch. ``group`` rows are (request, n_new):
        each row prefills n_new prompt tokens starting at the request's
        n_prefilled mark — one pass of its plan in prefill-priority mode
        (`plan_passes`: often the whole remaining prompt), one chunk in
        token-budget mode. Rows whose start is > 0 attend to
        their earlier pages through the same ctx-merge path prefix-cache
        hits use; only rows whose FINAL chunk this is sample a token."""
        # the arrays always come at the wave size: ONE compiled row count
        # per length bucket (per-size row buckets would multiply the
        # programs warmup() has to build, each about a second of tracing
        # on the host, and an unwarmed shape hit mid-traffic is a
        # multi-second TTFT spike). The program computes the group's rows
        # only (stage.py: row_pass), so the padding rows below cost an
        # upload and no compute; `computed` is what the records count
        with tracing.region("rtpu.engine.dispatch_prefill") as r:
            rb = self._wave_rb
            computed = len(group)
            ids = np.zeros((rb, sb), np.int32)
            positions = np.zeros((rb, sb), np.int32)
            bt = np.zeros((rb, self.max_pages_per_seq), np.int32)
            total = np.zeros((rb,), np.int32)
            gather = np.zeros((rb,), np.int32)
            slots = np.zeros((rb,), np.int32) if self._slot_state else None
            rows = []
            facts = []
            passes = []
            cp = (self.max_pages_per_seq
                  if any(req.n_prefilled for req, _ in group) else 0)
            # the passes whose attention is the flash kernel's: not a
            # block-sparse family's past its dense length or over pages
            flash = self._sparse is None or (
                cp == 0 and sb <= self._sparse.dense_len)
            # (query block, key block) visits a row of the program's
            # shapes would make; what a row's lengths cut is counted below
            width = cp * self.config.page_size
            kinds = self._attn_kinds
            shapes = _attn_visits(sb, width, kinds=kinds)[0]
            for i, (req, n_new) in enumerate(group):
                start = req.n_prefilled
                if slots is not None:
                    slots[i] = req.slot
                ids[i, :n_new] = req.prompt_ids[start:start + n_new]
                positions[i] = start + np.arange(sb, dtype=np.int32)
                bt[i, :len(req.pages)] = req.pages
                total[i] = start + n_new
                final = start + n_new >= self._prefill_end(req)
                # where the family's model computes the head at this
                # position only, a pass that is not the last asks for none
                gather[i] = (n_new - 1 if final or not self._head_at_gather
                             else -1)
                rows.append((req.request_id, req.slot, start + n_new,
                             final))
                facts.append((req.request_id, n_new, start + n_new))
                passes.append((req.n_passes, final))
                req.n_passes += 1
                self._totals["prefill_resumed_passes_total"] += start > 0
                if flash:
                    visited, _, masked = _attn_visits(sb, width, n_new,
                                                      start, kinds)
                    self._totals["prefill_attn_blocks_total"] += shapes
                    self._totals["prefill_attn_blocks_skipped_total"] += (
                        shapes - visited)
                    self._totals["prefill_attn_blocks_masked_total"] += masked
            now = time.monotonic()
            for req, _ in group:
                if req.dispatched_t is None:
                    req.dispatched_t = now
                    req.dispatched_ns = r.start_ns
            temp, topk, keys = self._sampling_arrays(
                [req for req, _ in group], rb)
            tokens = self._compute_prefill(
                sb, rb, cp, len(group), bt, total, ids, positions, gather,
                temp, topk, keys, *(() if slots is None else (slots,)))
            for req, n_new in group:
                req.n_prefilled += n_new
                if req.n_prefilled >= self._prefill_end(req):
                    req.planned_out = 0 if self._block else 1
                    if self._block:
                        # this prefill yields no token the first block
                        # would wait for, and programs run in dispatch
                        # order on one stream: the block may be enqueued
                        # right behind the pass that writes its context
                        req.decode_ready = True
            self._totals["prefill_dispatches_total"] += 1
            self._totals["prefill_tokens_total"] += sum(
                n_new for _, n_new in group)
            self._totals["prefill_padded_tokens_total"] += computed * sb
            self._totals["prefill_passes_total"] += computed
            self._enqueue(
                "prefill", tokens, r.start_ns, computed, computed * sb,
                facts, self._family_fields("prefill", facts, passes, cp),
                (sb, rb, cp), temp=temp, group=rows)
        self._phase_ns[_DISPATCH_PREFILL] += r.ns

    def _family_fields(self, site: str, *what) -> Dict[str, Any]:
        """The named record fields the family's facts add at one of the
        three places a record is made ("prefill", "decode", "harvest":
        stage.py: model_family), each moving its own keys of `_totals`."""
        fields: Dict[str, Any] = {}
        for facts in self.family_facts:
            if hasattr(facts, site):
                fields.update(getattr(facts, site)(self._totals, *what) or ())
        return fields

    def _enqueue(self, kind: str, toks, dispatch_ns: int, rows_padded: int,
                 tokens_padded: int, facts: List[tuple],
                 fields: Dict[str, Any], program_key: tuple, k: int = 1,
                 temp: Optional[np.ndarray] = None, **harvest_keys) -> None:
        """Queue one enqueued program for harvest. The dict is also its
        `engine.dispatch` flight record in the making, by the record's
        field names: `facts` (its `rows`) is one (request_id, q_tokens,
        ctx_tokens) per real row — the tokens the
        row computes and the tokens of KV it attends to, cached prefix
        included (for a k-step decode row: at its first step) —
        `rows_padded`/`tokens_padded` are what the program computes,
        `fields` the family's, `program_key` the shape key `compute.run`
        was given for it, `temp` its operand of that name (None: a
        program with no sampler): the record's `drawn` is whether it has a
        row above 0, the branch the program's sampler takes (stage.py:
        _device_sample). _harvest adds the fetch's timestamps."""
        self._dispatch_seq += 1
        drawn = None if temp is None else bool((temp > 0).any())
        if kind in ("prefill", "decode"):
            self._totals["drawn_dispatches_total"] += bool(drawn)
        elif kind == "block":
            self._totals["block_drawn_dispatches_total"] += bool(drawn)
        self._inflight.append({
            **self._rec_constant, **fields, "drawn": drawn,
            "program_key": program_key,
            "kind": kind, "toks": toks, "k": k, "seq": self._dispatch_seq,
            "step_dispatched": self._step_seq, "dispatch_ns": dispatch_ns,
            "enqueued_ns": tracing.now_ns(),
            "rows_padded": rows_padded, "tokens_padded": tokens_padded,
            "rows": tuple(facts), **harvest_keys})

    @staticmethod
    def _prompt_lookup_draft(req: Request, max_len: int) -> List[int]:
        """Prompt-lookup (n-gram) draft: find the most recent earlier
        occurrence of the sequence's trailing n-gram in prompt+output and
        propose the tokens that followed it. No draft model — the
        request's own text is the only source, which is exactly the
        regime speculation wins in (code, templated output, extraction,
        repetition). Longer (more precise) n-grams are tried first."""
        seq = req.prompt_ids + req.output_ids
        for n in (3, 2):
            if len(seq) < n + 1:
                continue
            tail = seq[-n:]
            # backwards: the MOST RECENT occurrence predicts best
            for i in range(len(seq) - n - 1, -1, -1):
                if seq[i:i + n] == tail:
                    return [int(t) for t in seq[i + n:i + n + max_len]]
        return []

    def _dispatch_spec(self) -> bool:
        """Prompt-lookup speculative decode: ONE prefill-shaped dispatch
        verifies each drafted continuation (inputs = pending token +
        draft; argmax at every position comes back); the harvest accepts
        the longest prefix whose draft tokens match the model's own
        argmax, emitting up to spec_lookahead+1 tokens per dispatch.
        Greedy-only (temperature == 0) and only for slots with no work
        in flight (drafting needs the host-known tail of the sequence).
        Returns False when no slot qualifies — the normal fused decode
        then covers everything."""
        cfg = self.config
        L = int(cfg.spec_lookahead)
        if L <= 0:
            return False
        L = min(L, cfg.prefill_buckets[-1] - 1)
        page = cfg.page_size
        rows: List[tuple] = []
        for req in self.running:
            if (req.slot < 0 or not req.decode_ready
                    or req.spec_inflight
                    or req.sampling.temperature > 0
                    or req.sampling.prefill_only
                    or req.planned_out != len(req.output_ids)
                    or req.planned_out >= req.sampling.max_tokens):
                continue
            cap = _cap_total(req, cfg.max_model_len)
            total = len(req.prompt_ids) + len(req.output_ids)
            if total >= cap:
                continue
            draft = self._prompt_lookup_draft(req, min(L, cap - total))
            if not draft:
                continue
            # page horizon for the draft writes (positions total-1 ..
            # total-1+len(draft), all < cap by the clamp above); a
            # shortfall skips speculation for this slot — the normal
            # decode path owns preemption
            last_pos = total - 1 + len(draft)
            required = min(last_pos // page + 1, self.max_pages_per_seq)
            if len(req.pages) < required:
                try:
                    req.pages.extend(self.allocator.allocate(
                        required - len(req.pages)))
                except OutOfPages:
                    continue
            rows.append((req, draft))
            if len(rows) >= self._wave_rb:
                break
        if not rows:
            return False
        # the arrays come at the wave size and the program computes the
        # real rows only, as a prefill's does
        rb = self._wave_rb
        sb = _bucket(L + 1, cfg.prefill_buckets)
        ids = np.zeros((rb, sb), np.int32)
        positions = np.zeros((rb, sb), np.int32)
        bt = np.zeros((rb, self.max_pages_per_seq), np.int32)
        total_arr = np.zeros((rb,), np.int32)
        recs = []
        facts = []
        for i, (req, draft) in enumerate(rows):
            total = len(req.prompt_ids) + len(req.output_ids)
            pending = (req.output_ids[-1] if req.output_ids
                       else req.prompt_ids[-1])
            n = len(draft)
            ids[i, 0] = pending
            ids[i, 1:1 + n] = draft
            positions[i] = (total - 1) + np.arange(sb, dtype=np.int32)
            bt[i, :len(req.pages)] = req.pages
            # pos-mask: writes beyond the pending token + draft are
            # dropped (padding columns), and the clamp above keeps every
            # draft write under the request's cap
            total_arr[i] = total + n
            recs.append((req.request_id, req.slot, len(req.output_ids),
                         list(draft)))
            facts.append((req.request_id, n + 1, total + n))
            req.planned_out += n + 1  # optimistic; rolled back at harvest
            req.spec_inflight = True
            self._spec_drafted_total += n
        dispatch_ns = tracing.now_ns()
        toks = self._compute_verify(sb, rb, len(rows), bt, total_arr, ids,
                                    positions)
        self._enqueue("spec", toks, dispatch_ns, len(rows), len(rows) * sb,
                      facts, {}, (sb, rb), drafts=recs)
        return True

    def _decode_eligible(self) -> List[Request]:
        """Slots safe to decode: RUNNING, prefill harvested
        (decode_ready), and not already dispatched through their whole
        token budget — chunks past max_tokens are 100% waste; chunks
        past an unpredictable EOS/stop-token are the speculative waste
        we accept."""
        cfg = self.config
        elig = []
        for req in self.running:
            if (req.slot < 0 or not req.decode_ready
                    or req.spec_inflight):
                # spec_inflight: a verify dispatch owns the slot — the
                # device carry is stale until its harvest resolves
                continue
            cap = _cap_total(req, cfg.max_model_len)
            if (req.planned_out >= req.sampling.max_tokens
                    or len(req.prompt_ids) + req.planned_out >= cap):
                continue
            elig.append(req)
        return elig

    def _reserve_decode_pages(self, elig: List[Request],
                              k_steps: int) -> Optional[List[Request]]:
        """Page horizon for one decode chunk: every eligible slot needs
        pages covering its planned writes through this chunk (clamped by
        its cap). Oldest first; on exhaustion with an empty pipeline,
        preempt the victim with the MOST reclaimable pages
        (sole-reference pages — prefix pages shared with other live
        requests free nothing), newest arrival breaking ties (vLLM's
        recompute-style preemption) — with work in flight, back off
        (returns None) and let the harvest free pages."""
        cfg = self.config
        page = cfg.page_size
        for req in sorted(elig, key=lambda r: r.arrival_t):
            cap = _cap_total(req, cfg.max_model_len)
            # last position this chunk writes: the pending token sits at
            # total-1 and each of the K steps advances one, clamped (a
            # block model: the end of the request's next block)
            last_pos = (self._block_start(req) + self._block - 1
                        if self._block else
                        min(len(req.prompt_ids) + req.planned_out - 1
                            + (k_steps - 1), cap - 1))
            required = min(last_pos // page + 1, self.max_pages_per_seq)
            # (the cheap tests first: `in` walks the list of a full slot
            # set, a dataclass comparison an entry, and a row needs a page
            # once in `page` steps)
            while (req.state == RUNNING and len(req.pages) < required
                   and req in self.running):
                try:
                    req.pages.extend(
                        self.allocator.allocate(required - len(req.pages)))
                except OutOfPages:
                    if self._inflight:
                        return None
                    victims = [r for r in self.running
                               if r is not req and r.planned_out
                               == len(r.output_ids)]
                    if not victims:
                        if req.planned_out == len(req.output_ids):
                            self._preempt(req)
                        break
                    self._preempt(max(
                        victims,
                        key=lambda r: (
                            self.allocator.reclaimable_pages(r.pages),
                            r.arrival_t)))
        running = {r.request_id for r in self.running}
        return [r for r in elig
                if r.state == RUNNING and r.request_id in running]

    def _dispatch_decode_chunk(self) -> bool:
        """Launch one fused K-step decode dispatch over the full slot set,
        reading last tokens from the device-resident carry. Returns False
        when there is nothing safe to decode (no eligible slot, or a page
        shortfall that needs the pipeline drained first)."""
        if self._block:
            return self._dispatch_block()
        cfg = self.config
        k_steps = self._decode_shape_key()[0]
        S = cfg.max_batch
        elig = self._decode_eligible()
        if not elig:
            return False
        elig = self._reserve_decode_pages(elig, k_steps)
        if not elig:
            return False

        # full-width block table, single compile shape: the decode kernel
        # streams only the pages covered by total_lens, so width is free
        mp = self.max_pages_per_seq
        bt = np.zeros((S, mp), np.int32)
        total = np.zeros((S,), np.int32)
        caps = np.ones((S,), np.int32)
        positions = np.zeros((S, 1), np.int32)
        override_mask = np.zeros((S,), bool)
        override_ids = np.zeros((S, 1), np.int32)
        chunk_slots = {}
        facts = []
        for req in elig:
            s = req.slot
            planned_total = len(req.prompt_ids) + req.planned_out
            bt[s, :len(req.pages)] = req.pages
            total[s] = planned_total
            caps[s] = _cap_total(req, cfg.max_model_len)
            positions[s, 0] = planned_total - 1
            if s in self._slot_override:
                override_mask[s] = True
                override_ids[s, 0] = self._slot_override.pop(s)
            chunk_slots[s] = (req.request_id, req.planned_out)
            facts.append((req.request_id, k_steps, planned_total))
        keys_steps = np.zeros((k_steps, S, 2), np.uint32)
        temp = np.zeros((S,), np.float32)
        topk = np.zeros((S,), np.int32)
        for k in range(k_steps):
            t_k, tk_k, keys_k = self._sampling_arrays(
                elig, S, counter_offset=k, slot_layout=True,
                base="planned")
            keys_steps[k] = keys_k
            if k == 0:
                temp, topk = t_k, tk_k
        for req in elig:
            req.planned_out += k_steps
        dispatch_ns = tracing.now_ns()
        toks = self._compute_decode(k_steps, mp, bt, total, caps,
                                    positions, override_mask,
                                    override_ids, temp, topk, keys_steps)
        self._enqueue_decode("decode", toks, dispatch_ns, (k_steps, mp),
                             k_steps, S * k_steps, facts, chunk_slots, temp)
        return True

    def _dispatch_block(self) -> bool:
        """A block model's generation step: ONE program that denoises the
        next block of every slot that has one to generate, in
        `denoising_steps` forwards at most (stage.py: `_block_program`).
        A request's first block opens with its prompt's ragged tail; every
        later one is all masks AND opens over the block before it, whose
        settled ids the device kept: `pending` says which slots have one
        (a block of the same request went out from the slot since its last
        prefill pass; the prompt's whole blocks, a refilled request's
        folded output among them, are final from the prefill). So the host
        needs no token of block n to dispatch block n + 1 (the pipeline
        runs ahead as a decode chain does; what a stop token makes stale
        is dropped at harvest), and a row that sits a round out finds its
        pending block in the carry when it next goes. Returns False when
        no slot has a block to generate or pages fall short."""
        cfg = self.config
        B, S = self._block, cfg.max_batch
        elig = [req for req in self.running
                if req.slot >= 0 and req.decode_ready
                and req.planned_out < req.sampling.max_tokens
                and self._block_start(req) + B <= cfg.max_model_len]
        if not elig:
            return False
        elig = self._reserve_decode_pages(elig, B)
        if not elig:
            return False
        with tracing.region("rtpu.engine.dispatch_block") as r:
            key = self._block_shape_key()
            steps = key[1]
            bt = np.zeros((S, self.max_pages_per_seq), np.int32)
            total = np.zeros((S,), np.int32)
            ids = np.full((S, B), self.model_cfg.mask_token_id, np.int32)
            masked = np.zeros((S, B), bool)
            pending = np.zeros((S,), bool)
            block_slots, facts = {}, []
            now = time.monotonic()
            for req in elig:
                s, start = req.slot, self._block_start(req)
                # the block's known tokens: a first block's prompt tail
                tail = req.prompt_ids[start:] if req.planned_out == 0 else []
                ids[s, :len(tail)] = tail
                masked[s, len(tail):] = True
                bt[s, :len(req.pages)] = req.pages
                total[s] = start + B
                pending[s] = req.block_pending
                block_slots[s] = (req.request_id, req.planned_out, len(tail),
                                  req.block_pending)
                facts.append((req.request_id, B, start + B))
                if req.dispatched_t is None:
                    # a prompt with no whole block had no prefill
                    req.dispatched_t, req.dispatched_ns = now, r.start_ns
            keys_steps = np.zeros((steps, S, 2), np.uint32)
            for k in range(steps):
                temp, topk, keys_steps[k] = self._sampling_arrays(
                    elig, S, counter_offset=k, slot_layout=True,
                    base="planned")
            for req in elig:
                req.planned_out += B - block_slots[req.slot][2]
                req.block_pending = True
            toks = self._compute_block(key, bt, total, ids, masked, pending,
                                       temp, topk, keys_steps)
            self._enqueue_decode("block", toks, r.start_ns, key, steps,
                                 S * B, facts, block_slots, temp)
        return True

    def _enqueue_decode(self, kind: str, toks, dispatch_ns: int,
                        program_key: tuple, k_steps: int, tokens_padded: int,
                        facts: List[tuple], slots: dict,
                        temp: np.ndarray) -> None:
        """_enqueue for a program over the full slot set (a decode chunk
        with the stats() totals it moves, a block program)."""
        if kind == "decode":
            self._totals["decode_dispatches_total"] += 1
            self._totals["decode_rows_total"] += len(facts)
            self._totals["decode_ctx_tokens_total"] += sum(
                ctx for _, _, ctx in facts)
        self._enqueue(
            kind, toks, dispatch_ns, self.config.max_batch, tokens_padded,
            facts, self._family_fields("decode", facts, k_steps),
            program_key, k=k_steps, temp=temp, slots=slots)

    # ---------------------------------------------------------- harvest

    def _device_stamps(self, rec: dict, ready: Optional[bool],
                       fetch_start_ns: int, fetch_end_ns: int) -> bool:
        """Writes the record's `device_start_ns`, `device_end_ns` and
        `end_exact` (None, and `enqueued_ns` with them, where the handle
        cannot say); returns whether start and end are both exact.
        Programs run in dispatch order on one stream and are
        harvested in that order. A program that had NOT finished when the
        host came to fetch it (`ready` False: the rule while the host
        runs ahead of the device) ended as the fetch returned. That is
        late by the fetch's own lag (the completion's way to the host
        and the copy of the tokens: 0.6-0.9 ms on a v5e, PERF.md section
        6, PR 37), the same for every program, so a duration between two
        such ends carries none of it and a wait that ends at one carries
        it once. A program that had finished ended at some time before
        the fetch began: the record says so, and `harvests_late_total`
        counts it (the host loop is behind the device). A program started
        when it was enqueued or when the program before it ended,
        whichever came later: exact unless that end is an upper bound
        which lies past the enqueue. The gap, where the device had
        nothing enqueued, is the step's and the engine's idle time."""
        if ready is None:
            rec.update(dict.fromkeys(("enqueued_ns", "device_start_ns",
                                      "device_end_ns", "end_exact")))
            return False
        start = rec["enqueued_ns"]
        prev = self._device_end_ns
        start_exact = True
        if prev is not None:
            if prev > start:
                start, start_exact = prev, self._device_end_exact
            else:
                self._step_device_idle_ns += start - prev
                self._device_idle_ns_total += start - prev
        end = fetch_start_ns if ready else fetch_end_ns
        self._device_end_ns, self._device_end_exact = end, not ready
        self._device_busy_ns_total += end - start
        self._step_fetch_blocked += not ready
        self._harvests_late_total += ready
        rec.update(device_start_ns=start, device_end_ns=end,
                   end_exact=not ready)
        return start_exact and not ready

    def _harvest(self, rec: dict, deltas: List[OutputDelta]) -> None:
        ready = self._handle_ready(rec["toks"])
        with tracing.region("rtpu.engine.fetch") as fetch:
            toks_np = self._fetch_tokens(rec["toks"])
        exact = self._device_stamps(rec, ready, fetch.start_ns, fetch.end_ns)
        device_end_ns = rec["device_end_ns"]
        with tracing.region("rtpu.engine.harvest") as r:
            toks_np, packed = self._split_packed(rec, toks_np)
            if rec["kind"] == "prefill":
                for i, (rid, slot, end, final) in enumerate(rec["group"]):
                    req = self.requests.get(rid)
                    if req is None or req.state != RUNNING or req.slot != slot:
                        continue  # aborted while in flight
                    if device_end_ns is not None:
                        # every row of a wave waited for the whole program
                        req.prefill_device_ns += (device_end_ns
                                                  - rec["device_start_ns"])
                        req.parts_exact &= exact
                        if final:
                            req.prefill_end_ns = device_end_ns
                    self._register_full_pages(req, upto=end)
                    if not final:
                        # intermediate chunk: pages are written; the sampled
                        # token (mid-prompt continuation) is meaningless
                        continue
                    if self._block:
                        # a block model's prefill yields no token: the
                        # prompt's whole blocks are in pages, its first
                        # block may go
                        req.decode_ready = True
                        self._totals["prefill_tokenless_total"] += 1
                        continue
                    token = int(toks_np[i])
                    # the decode chain reads this slot's first input from the
                    # host-side override (the prefill wrote pages, not the
                    # slot carry)
                    self._slot_override[slot] = token
                    req.decode_ready = True
                    self._append_token(req, token, deltas)
            elif rec["kind"] == "spec":
                # toks_np is [rb, sb]: g[j] = the model's argmax AFTER input
                # column j. Accept g[0] (computed from the true pending
                # token), then each g[j] while draft[j-1] == g[j-1] — the
                # draft token fed at column j was the model's own choice, so
                # everything before the first mismatch is exactly what plain
                # greedy decode would have produced.
                for i, (rid, slot, start, draft) in enumerate(rec["drafts"]):
                    req = self.requests.get(rid)
                    if req is None:
                        continue
                    req.spec_inflight = False
                    if (req.state != RUNNING or req.slot != slot
                            or len(req.output_ids) != start):
                        continue  # finished/aborted while in flight
                    g = toks_np[i]
                    emitted = [int(g[0])]
                    for j in range(1, len(draft) + 1):
                        if int(draft[j - 1]) != emitted[-1]:
                            break
                        emitted.append(int(g[j]))
                    self._spec_accepted_total += len(emitted) - 1
                    for tok in emitted:
                        if req.state != RUNNING:
                            break  # EOS/stop/length inside the accepted run
                        self._append_token(req, tok, deltas)
                    if req.state == RUNNING:
                        # roll the optimistic plan back to reality and feed
                        # the next dispatch the last ACCEPTED token (verify
                        # never touches the device carry); rejected draft
                        # writes sit beyond total and are rewritten before
                        # any live request's attention can reach them
                        req.planned_out = len(req.output_ids)
                        self._slot_override[req.slot] = req.output_ids[-1]
            elif rec["kind"] == "block":
                self._harvest_block(rec, toks_np, deltas, exact)
            else:
                # decode chunk: toks_np is [K, S]
                k_steps = rec["k"]
                for slot, (rid, start) in rec["slots"].items():
                    req = self.requests.get(rid)
                    if (req is None or req.state != RUNNING or req.slot != slot
                            or len(req.output_ids) != start):
                        continue  # finished/aborted/preempted while in flight
                    for k in range(k_steps):
                        if req.state != RUNNING:
                            break
                        self._append_token(req, int(toks_np[k, slot]), deltas)
            rec.update(self._family_fields("harvest", rec, packed))
        self._phase_ns[_FETCH] += fetch.ns
        self._phase_ns[_HARVEST] += r.ns
        rec.update(step_harvested=self._step_seq,
                   fetch_start_ns=fetch.start_ns, fetch_end_ns=fetch.end_ns)
        tracing.record("engine.dispatch", tuple(map(
            rec.get, tracing.FIELDS["engine.dispatch"])))

    def _harvest_block(self, rec: dict, fetched: np.ndarray,
                       deltas: List[OutputDelta], exact: bool) -> None:
        """A block program's tokens (stage.py: `_block_program`; [2, S, B]:
        the ids, and the pass that fixed each): each live row's tokens go
        out in position order as ONE delta with the pass that fixed each,
        and the record in the making is told how many (`emitted`). A row
        that opened over its pending block settled it: its keys are final."""
        ids, fixed_at = fetched
        device_end_ns = rec["device_end_ns"]
        emitted = 0
        for slot, (rid, start, known, pending) in rec["slots"].items():
            req = self.requests.get(rid)
            if (req is None or req.state != RUNNING or req.slot != slot
                    or len(req.output_ids) != start):
                continue  # finished/aborted/preempted while in flight
            if req.first_token_ns is None and device_end_ns is not None:
                # the first block's program is one of the request's OWN:
                # its device time is the first token's, not a wait
                req.prefill_device_ns += (device_end_ns
                                          - rec["device_start_ns"])
                req.parts_exact &= exact
                req.prefill_end_ns = device_end_ns
            self._totals["block_settles_folded_total"] += pending
            req.block_unsettled = True
            emitted += self._append_block(
                req, ids[slot, known:], fixed_at[slot, known:], deltas)
        rec["emitted"] = emitted

    def _append_block(self, req: Request, tokens, fixed_at,
                      deltas: List[OutputDelta]) -> int:
        """`_append_token` for a block's tokens, in position order: the
        first stop token ends the request and what lies right of it in
        the block is dropped, `max_tokens` cuts a last block. One delta."""
        new: List[int] = []
        stop = None
        for tok in tokens:
            new.append(int(tok))
            req.output_ids.append(new[-1])
            stop = self._stop_reason(req, new[-1])
            if stop:
                break
        if req.first_token_ns is None:
            req.first_token_ns = tracing.now_ns()
        if stop:
            self._finish(req, stop)
        deltas.append(OutputDelta(
            req.request_id, new, bool(stop), stop,
            fixed_pass=[int(p) for p in fixed_at[:len(new)]]))
        return len(new)

    def _split_packed(self, rec: dict, fetched: np.ndarray) -> tuple:
        """(the program's tokens in the shape its kind's harvest reads: a
        prefill's or a verify's for every row of the wave-sized arrays; what
        it packed behind them for the family's facts, stage.py: pack)."""
        S, rb = self.config.max_batch, self._wave_rb
        shape = {
            "prefill": (rb,),
            "spec": (rb, rec["tokens_padded"] // max(rec["rows_padded"], 1)),
            "decode": (rec["k"], S),
            "block": (2, S, self._block)}[rec["kind"]]
        flat = fetched.reshape(-1)
        n = math.prod(shape)
        return flat[:n].reshape(shape), flat[n:]

    def _preempt(self, req: Request) -> None:
        """Return a running request to the waiting queue, dropping its
        pages (its KV is recomputed on re-admission; generated tokens are
        folded into the prompt). Only called with an empty pipeline, so
        host bookkeeping is authoritative."""
        assert not self._inflight
        self._preempted_total += 1
        req.preemptions += 1
        req.n_folded += len(req.output_ids)
        self.running.remove(req)
        self._release_slot(req)
        self.allocator.release(req.pages)
        req.prompt_ids = req.prompt_ids + req.output_ids
        req.sampling.max_tokens -= len(req.output_ids)
        req.output_ids = []
        req.pages = []
        req.n_cached = 0
        req.n_prefilled = 0
        req.n_hashed = 0
        req.planned_out = 0
        req.decode_ready = False
        req.spec_inflight = False
        req.dispatched_t = None  # re-prefill measures its own queue wait
        req.dispatched_ns = None
        req.prefill_device_ns, req.prefill_end_ns = 0, None
        req.parts_exact = True
        req.state = WAITING
        self.waiting.insert(0, req)

    def _release_slot(self, req: Request) -> None:
        if req.block_unsettled:
            # a request's LAST block in a slot is never settled, and no
            # output depends on it: nothing reads its keys (prompt pages
            # alone enter the prefix cache, a preemption refills from the
            # tokens, a finish releases the pages, the hand-off is refused)
            self._totals["block_unsettled_dropped_total"] += 1
        req.block_pending = req.block_unsettled = False
        if req.slot >= 0:
            self._slot_req.pop(req.slot, None)
            self._slot_override.pop(req.slot, None)
            self._free_slots.append(req.slot)
            self._free_slots.sort()
            req.slot = -1

    # ---------------------------------------------------------- sampling

    def _sampling_arrays(self, batch, rb: int = None,
                         counter_offset: int = 0, slot_layout: bool = False,
                         base: str = "actual"):
        """Per-row sampling params + PRNG keys for the on-device sampler.
        Keys derive from (request seed, tokens-sampled-so-far) so results
        are independent of batch composition — sequential, batched, and
        speculatively-pipelined execution of the same requests sample
        identically. With slot_layout, rows are decode slots; `base`
        selects the token counter ('planned' for dispatch-ahead chunks,
        whose counts are deterministic)."""
        import hashlib as hashlib_mod

        rb = rb or len(batch)
        temp = np.zeros((rb,), np.float32)
        topk = np.zeros((rb,), np.int32)
        keys = np.zeros((rb, 2), np.uint32)
        for i, req in enumerate(batch):
            row = req.slot if slot_layout else i
            s = req.sampling
            temp[row] = s.temperature
            topk[row] = min(s.top_k, _MAX_TOP_K) if s.top_k else 0
            seed = s.seed if s.seed is not None else self.config.seed
            count = (req.planned_out if base == "planned"
                     else len(req.output_ids))
            digest = hashlib_mod.blake2b(
                f"{req.request_id}:{seed}:"
                f"{count + counter_offset}".encode(),
                digest_size=8).digest()
            keys[row, 0] = int.from_bytes(digest[:4], "little")
            keys[row, 1] = int.from_bytes(digest[4:], "little")
        return temp, topk, keys

    def _stop_reason(self, req: Request, token: int) -> Optional[str]:
        eos = self.config.eos_token_id
        if eos is not None and token == eos:
            return "stop"
        if token in req.sampling.stop_token_ids:
            return "stop"
        if len(req.output_ids) >= req.sampling.max_tokens:
            return "length"
        if req.total_len >= self.config.max_model_len:
            return "length"
        return None

    def _append_token(self, req: Request, token: int,
                      deltas: List[OutputDelta]) -> None:
        req.output_ids.append(token)
        if req.first_token_ns is None:
            req.first_token_ns = tracing.now_ns()
        stop = self._stop_reason(req, token)
        if req.sampling.prefill_only and stop is None:
            # gather-then-release inside the driver thread: the blob is
            # complete before the finished delta is observable. When the
            # first token already terminates (EOS/stop/length), fall
            # through to the normal finish instead — there is nothing
            # worth handing to a decode engine.
            blob = self._gather_kv(req)  # device gather OUTSIDE the lock
            with self._intake_lock:
                self.extracted[req.request_id] = blob
                self._extracted_order.append(
                    (req.request_id, time.monotonic()))
            self._evict_extracted()
            self._finish(req, "prefill_done")
            deltas.append(OutputDelta(req.request_id, [token], True,
                                      "prefill_done"))
            return
        if stop:
            self._finish(req, stop)
            deltas.append(OutputDelta(req.request_id, [token], True, stop))
        else:
            deltas.append(OutputDelta(req.request_id, [token], False))

    def _register_full_pages(self, req: Request,
                             upto: Optional[int] = None) -> None:
        """Enter any newly-FULL prompt pages into the prefix cache (only
        prompt tokens — generated text is rarely shared). ``upto`` bounds
        registration to tokens whose KV has actually been written (a
        chunked prefill registers chunk by chunk as dispatches land)."""
        if self._prefix_off:
            return  # a shared page could not be reused
        page = self.config.page_size
        n_prompt_full = len(req.prompt_ids) // page
        if upto is not None:
            n_prompt_full = min(n_prompt_full, upto // page)
        while req.n_hashed // page < n_prompt_full:
            i = req.n_hashed // page
            tokens = req.prompt_ids[i * page:(i + 1) * page]
            req.last_page_hash = self.allocator.register_full_page(
                req.pages[i], req.last_page_hash, tokens)
            req.n_hashed += page

    def _finish(self, req: Request, reason: str) -> None:
        if req.state == RUNNING and req in self.running:
            self.running.remove(req)
        elif req in self.waiting:
            self.waiting.remove(req)
        self._release_slot(req)
        req.state = FINISHED
        req.finish_reason = reason
        self.allocator.release(req.pages)
        req.pages = []
        # drop the bookkeeping entry: long-lived engines (batch workers,
        # serve replicas) would otherwise accumulate one Request per call
        self.requests.pop(req.request_id, None)
        self._record_request(req)

    def _record_request(self, req: Request) -> None:
        """The request's one `engine.request` flight record, written when
        it leaves the engine (finished, aborted, expired, transferred)."""
        if req.dispatched_ns is not None:
            self._queue_wait_ns_total += req.dispatched_ns - req.arrival_ns
        parts = (None, None, None, None)
        end = req.prefill_end_ns
        if (end is not None and req.first_token_ns is not None
                and req.dispatched_ns is not None):
            # they sum to first_token_ns - dispatched_ns by construction
            # (a preempted request keeps its first token and has a later
            # dispatch: the identity holds, the parts say nothing)
            parts = (end - req.dispatched_ns - req.prefill_device_ns,
                     req.prefill_device_ns, req.first_token_ns - end,
                     req.parts_exact)
        tracing.record("engine.request", (
            req.request_id, req.arrival_ns, req.admitted_ns,
            req.dispatched_ns, req.first_token_ns, tracing.now_ns(),
            len(req.prompt_ids) - req.n_folded, req.n_cached,
            len(req.output_ids) + req.n_folded, req.preemptions,
            req.finish_reason) + parts)

    # ------------------------------------------- prefill/decode handoff

    def _refuse_handoff(self) -> None:
        """The disaggregated prefill -> decode hand-off moves pages and one
        pending token (`_gather_kv`, kv_transfer.py)."""
        refuse(self.config, self.model_cfg, handoff=True)

    def _gather_kv(self, req: Request) -> Dict[str, Any]:
        now = time.monotonic()
        disp = req.dispatched_t if req.dispatched_t is not None \
            else req.arrival_t
        return {
            # [L, n_pages, Hkv, page, 2*D] — page axis 1 in the combined
            # page-major layout; both disagg engines must agree on it
            "kv": self.compute.read_pages(req.pages),
            "prompt_ids": list(req.prompt_ids),
            "output_ids": list(req.output_ids),
            # TTFT split for the disagg router: time queued before the
            # prefill dispatch vs prefill compute (handoff cost is the
            # caller's to measure — it happens after this gather)
            "queued_s": max(0.0, disp - req.arrival_t),
            "prefill_s": max(0.0, now - disp),
        }

    def extract_kv(self, request_id: str) -> Dict[str, Any]:
        """Gather a running request's KV pages + generation state into a
        host blob for disaggregated prefill→decode handoff (ref:
        llm/_internal/serve/deployments/prefill_decode_disagg/ — the
        reference moves KV between vLLM instances; here pages move
        between engines as dense arrays). Synchronous-driver use only;
        concurrent servers use SamplingParams(prefill_only=True) +
        pop_extracted, which gathers inside step()."""
        self._refuse_handoff()
        self._drain_pipeline(self._pending_deltas)
        req = self.requests.get(request_id)
        if req is None or req.state != RUNNING:
            # a speculative decode chunk drained above may have crossed
            # the request's stop condition and finished it (pages are
            # released then — there is nothing left to gather)
            raise KeyError(
                f"{request_id!r} is not running: it finished (possibly "
                "while speculative decode chunks drained) or was never "
                "added; extract_kv must be called before generation "
                "completes")
        return self._gather_kv(req)

    def pop_extracted(self, request_id: str) -> Dict[str, Any]:
        """Fetch (and drop) the KV blob of a prefill_only request that
        finished with reason 'prefill_done'."""
        with self._intake_lock:
            blob = self.extracted.pop(request_id, None)
            self._extracted_order[:] = [
                e for e in self._extracted_order if e[0] != request_id]
        if blob is None:
            raise KeyError(
                f"prefill KV for {request_id!r} is unavailable: the "
                "handoff expired (TTL/cap eviction), was aborted, or the "
                "request never finished prefill")
        return blob

    def release_request(self, request_id: str) -> None:
        """Drop a request after handoff (its pages return to the pool)."""
        req = self.requests.pop(request_id, None)
        if req is not None and req.state != FINISHED:
            self._finish(req, "transferred")

    def inject_request(self, request_id: str, handoff: Dict[str, Any],
                       sampling: Optional[SamplingParams] = None) -> None:
        """Adopt a prefilled request: queue it for admission; the next
        step() scatters its KV pages and resumes decoding from its
        pending token. Queued (not applied inline) so injections respect
        the same max_batch/page admission control as fresh prompts."""
        self._refuse_handoff()
        with self._intake_lock:
            self._injections.append(
                (request_id, handoff, sampling or SamplingParams()))

    def _try_admit_injection(self, deltas: List[OutputDelta]) -> bool:
        """Admit the oldest queued injection if batch slots + pages allow
        (called from step(), before fresh-prompt admission — transferred
        requests already paid for their prefill)."""
        with self._intake_lock:
            if not self._injections:
                return False
            if len(self.running) >= self.config.max_batch:
                return False
            if not self._free_slots:
                return False
            request_id, handoff, sampling = self._injections[0]
            n = handoff["kv"].shape[1]
            if self.allocator.num_free() < n:
                return False
            self._injections.pop(0)
        # the eager page scatter below forks the page buffers; anything
        # still in flight must land first or its writes are lost
        self._drain_pipeline(deltas)
        pages = self.allocator.allocate(n)
        self.compute.write_pages(pages, handoff["kv"])
        req = Request(request_id, list(handoff["prompt_ids"]), sampling)
        req.output_ids = list(handoff["output_ids"])
        req.pages = pages
        req.state = RUNNING
        # mark the whole transferred prompt as hashed so the decode
        # engine never re-registers pages it did not fill page-aligned
        page = self.config.page_size
        req.n_hashed = (len(req.prompt_ids) // page) * page
        req.n_cached = 0
        req.n_prefilled = len(req.prompt_ids)
        req.slot = self._free_slots.pop(0)
        req.planned_out = len(req.output_ids)
        req.decode_ready = True
        self._slot_req[req.slot] = req
        # pending token (sampled by the prefill engine, not yet written)
        pending = (req.output_ids[-1] if req.output_ids
                   else req.prompt_ids[-1])
        self._slot_override[req.slot] = pending
        self.requests[request_id] = req
        self.running.append(req)
        return True

    # ----------------------------------------------------------- warmup

    def _block_shape_key(self) -> tuple:
        return (self._block, self.model_cfg.denoising_steps,
                self.max_pages_per_seq)

    def _decode_shape_key(self) -> tuple:
        return (max(1, int(self.config.decode_steps_per_dispatch)),
                self.max_pages_per_seq)

    def program_text(self, kind: str, shape_key: tuple) -> str:
        """The lowered (StableHLO) text of one dispatch program."""
        return self.compute.program_text(kind, shape_key)

    def program_scopes(self, kind: str,
                       shape_key: tuple) -> Optional[Dict[str, str]]:
        """Instruction name -> scope path of one dispatch program
        (`StageCompute.program_scopes`), by a dispatch record's `kind`
        ("spec" is the verify program) and `program_key`; None where the
        programs are another process's (pp)."""
        if self.compute is None:
            return None
        return self.compute.program_scopes(
            "verify" if kind == "spec" else kind, shape_key)

    def _warmup_programs(self, prompt_buckets, include_decode) -> list:
        """(kind, shape key) of every dispatch shape traffic can hit: one
        prefill program per length bucket, with and without a prefix part
        (rows are no dimension of the set: a program takes the wave size
        and computes the rows a dispatch gives it, so every group size
        from one request to a full wave is already here) plus the
        decode-phase programs."""
        from itertools import product

        rb = self._wave_rb
        if prompt_buckets is None:
            prompt_buckets = self.config.prefill_buckets
        # the variant with a prefix part runs behind a cached prefix (a
        # model without per-slot state) and in every pass after a
        # prompt's first (a family whose prefill resumes); a model with
        # recurrent state that starts every row from zero never runs it
        prefix_parts = ((0, self.max_pages_per_seq) if self._resumes
                        else (0,))
        programs = [("prefill", (sb, rb, cp)) for sb, cp in product(
            prompt_buckets, prefix_parts)]
        if not include_decode:
            return programs
        if self.config.spec_lookahead > 0:
            # the speculative verify dispatch (decode-phase work) has ONE
            # shape: the bucket covering spec_lookahead+1 — padded rows
            # and columns handle shorter drafts
            sbv = _bucket(min(int(self.config.spec_lookahead),
                              self.config.prefill_buckets[-1] - 1) + 1,
                          self.config.prefill_buckets)
            programs.append(("verify", (sbv, rb)))
        if self._block:
            return programs + [("block", self._block_shape_key())]
        return programs + [("decode", self._decode_shape_key())]

    def warmup(self, prompt_buckets=None, include_decode=True) -> int:
        """Build every dispatch shape traffic can hit by running masked
        dummy dispatches (stage.py: dummy_operands; engine state is
        untouched; a prefill is given no real row, so its row loop makes
        no pass and building it costs the trace and the compile or cache
        fetch, no device time). Serve replicas call this before reporting
        READY: an unwarmed shape compiled under live traffic is a
        multi-second TTFT spike. prompt_buckets=() skips prefill shapes
        (decode-only replicas); include_decode=False skips the decode
        chunk (prefill-only replicas). Returns the number of shapes
        compiled. Must be called with an idle pipeline (no traffic yet)."""
        assert not self._inflight, "warmup requires an idle engine"
        return self.compute.warmup(
            self._warmup_programs(prompt_buckets, include_decode))

    # ------------------------------------------------------------ stats

    def stats(self) -> Dict[str, Any]:
        free = self.allocator.num_free()
        out = {
            "running": len(self.running),
            "waiting": len(self.waiting),
            "inflight": len(self._inflight),
            "expired_total": self._expired_total,
            "preempted_total": self._preempted_total,
            "spec_drafted_total": self._spec_drafted_total,
            "spec_accepted_total": self._spec_accepted_total,
            "free_pages": free,
            "pages_free": free,  # rtpu_llm_pages_free gauge key
            "attention": self._attention,
            "device": self._device,
            **self.allocator.stats,
            # the flight recorder's facts, cumulative (rtpu_llm_*_total)
            **self._totals,
            "programs_built_total": (self.compute.programs_built
                                     if self.compute else 0),
            "queue_wait_s_total": self._queue_wait_ns_total / 1e9,
            # the device's timeline by the engine's own stamps
            "device_busy_s_total": self._device_busy_ns_total / 1e9,
            "device_idle_s_total": self._device_idle_ns_total / 1e9,
            "harvests_late_total": self._harvests_late_total,
        }
        for facts in self.family_facts:
            if hasattr(facts, "sizes") and self.compute:
                out.update(facts.sizes(self.compute.pool_bytes()))
        if self._prefix_off and self._prefix_why_shown:
            out["prefix_reuse_refused_why"] = self._prefix_off
        if self.sharding is not None:
            out["sharding"] = self.sharding.page_accounting(
                self.config, self.model_cfg)
        return out
