"""Pipeline-parallel serving: multi-process stage engines over
compiled-graph channels.

Removes the repo's single-host model-size ceiling (serve/llm/sharding.py
tp_bundles rejects tp > CHIPS_PER_HOST because LLMEngine is one
process): the layer stack splits into ``pp`` stage engines, each its own
worker process on its own chip gang, holding its [L/pp]-layer param
slice and its layer-slice of the paged KV pool. Stages are chained
rank->rank by PR-8 compiled-DAG channels, so a steady-state decode tick
moves ONLY activations (per-microbatch hidden states + the sampling
carry) through shm/stream rings — never a control-plane RPC (asserted
in tests the way the cross-host DAG tests do, via rpc.transport_sends).

The PR-14 token-budget scheduler runs on rank 0 UNCHANGED — admission,
paged-KV allocation, prefix caching and preemption are host-side
bookkeeping over page ids, which are global (each stage holds its layer
slice of every page, so block tables replicate per stage exactly like
they replicate per tp shard). PipelinedEngine therefore subclasses
LLMEngine and overrides only the compute seams:

- ``_build_compute``: spawn stage workers, broadcast the checkpoint down
  the PR-16 replica ladder, compile the stage DAG;
- ``_compute_prefill`` / ``_dispatch_decode_chunk``: dispatch microbatch
  FRAMES down the DAG instead of local jits;
- ``_fetch_tokens``: resolve CompiledDAGRef results, converting a dead
  stage rank into a TYPED ActorDiedError/GetTimeoutError (a SIGKILLed
  rank writes no sentinel, so the fetch would otherwise be an untyped
  timeout).

Microbatching: chunked prefills already arrive as token-budget-sized
frames (prefill_chunk_tokens); decode slots partition into
``pp_microbatches`` groups by slot index. A slot's next input token is
the PREVIOUS tick's sampled output (there is no cross-frame device
carry — the sample lands on the last stage, the embed lookup needs it
on the first), so consecutive ticks of one group can never overlap;
groups of different slots can, and >= 2*(pp-1) of them keep every stage
busy once the pipeline fills. The bubble is measured, not modeled:
every stage's DAG loop counts reads whose input ring was empty at read
time (runtime/channel.py Channel.ready, dag/loop_runner.py), and
``pp_bubble_frac`` = starved reads / total reads over the window —
an event-based measure that stays meaningful on a timeshared CPU box
where wall-clock stage overlap does not exist.

Weight loading (PR-16 tie-in): rank 0 materializes the full param tree
once (bit-identical to the single-process engine's init), puts it in
the object store, and ``core.broadcast`` lands a replica on every
stage-hosting node down the staggered binomial ladder — one uplink per
round, O(log n) owner egress — before the stage workers slice their
layers out of the local replica.

Placement: ``pp_bundles(pp, tp)`` (sharding.py) emits one tp-chip
bundle per stage; SLICE_PACK orders the gang along an ICI-adjacent
snake path through the host grid (runtime/topology.py ici_path), so
stage k and stage k+1 are one ICI hop apart and each stage's tp mesh
stays inside one host (resolve_serve_mesh within the worker).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ... import exceptions
from ...runtime import faults
from ...runtime.channel import ChannelClosed
from ...util import tracing
from .engine import EngineConfig, LLMEngine, _device_sample
from .sharding import CHIPS_PER_HOST


def stage_params(full_params: Dict[str, Any], stage: int, pp: int,
                 num_layers: int) -> Dict[str, Any]:
    """One stage's slice of a full LlamaModel param tree: a
    [num_layers/pp]-length slice of every stacked "layers" leaf, plus
    the embed table on stage 0 and final_norm + lm_head on the last
    stage. Literal slices — no reshaping, no renaming — which is what
    makes the pipelined forward bit-exact against the single engine."""
    import jax

    per = num_layers // pp
    lo, hi = stage * per, (stage + 1) * per
    out: Dict[str, Any] = {
        "layers": jax.tree.map(lambda a: a[lo:hi], full_params["layers"])}
    if stage == 0:
        out["embed"] = full_params["embed"]
    if stage == pp - 1:
        out["final_norm"] = full_params["final_norm"]
        out["lm_head"] = full_params["lm_head"]
    return out


def broadcast_params(ref, nodes=None, fanout: int = 0) -> dict:
    """Land the checkpoint blob on the stage-hosting nodes down the
    PR-16 replica tree (core.broadcast; fanout=0 = the staggered
    binomial ladder, one uplink per round) so N stage workers resolve
    their params ObjectRef from a LOCAL pool replica instead of N
    point-pulls hammering the owner's uplink. Returns the broadcast
    report ({bytes, nodes, ok, failed, depth, seconds, ...})."""
    from ...runtime.core import get_core

    return get_core().broadcast(ref, nodes=nodes, fanout=fanout)


class _StageWorker:
    """One pipeline stage: an actor process owning a [L/pp]-layer param
    slice, the matching layer slice of the paged KV pool, and (tp > 1)
    its own single-host tp mesh. Driven through the compiled DAG —
    ``tick`` is the per-microbatch frame handler the DAG loop calls; the
    normal actor methods (ping/dag_stats) stay callable concurrently."""

    def __init__(self, config: EngineConfig, stage: int):
        import jax.numpy as jnp

        from ...models.llama import StageModel, get_config
        from ...util.compile_cache import enable_compile_cache
        from .sharding import resolve_serve_mesh

        enable_compile_cache()
        self.config = config
        self.stage = int(stage)
        self.pp = int(config.pp)
        dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
        self.dtype = dtype
        self.model_cfg = get_config(
            config.model, scan_layers=True, remat=False, dtype=dtype,
            param_dtype=dtype, max_seq_len=config.max_model_len,
            **config.model_overrides)
        self.n_layers = self.model_cfg.num_layers // self.pp
        self.first = self.stage == 0
        self.last = self.stage == self.pp - 1
        self.model = StageModel(self.model_cfg, n_layers=self.n_layers,
                                first=self.first, last=self.last)
        # tp INSIDE the stage: this worker's own process-local mesh
        self.sharding = resolve_serve_mesh(None, tp=config.tp)
        if self.sharding is not None:
            self.sharding.validate(self.model_cfg)
        from .engine import resolve_attention

        resolve_attention(self.model_cfg, config, self.sharding)
        shape = (self.n_layers, config.num_pages,
                 self.model_cfg.num_kv_heads, config.page_size,
                 2 * self.model_cfg.head_dim_)
        if self.sharding is not None:
            import jax

            self._kv_sharding = self.sharding.kv_pages_sharding()
            self._repl_sharding = self.sharding.replicated()
            self.kv_pages = jax.jit(
                lambda: jnp.zeros(shape, dtype),
                out_shardings=self._kv_sharding)()
        else:
            self.kv_pages = jnp.zeros(shape, dtype)
        self.params = None
        self._param_shardings = None
        self._jit_cache: Dict[tuple, Any] = {}
        self.max_pages_per_seq = config.max_model_len // config.page_size

    # ------------------------------------------------------------ setup

    def load_params(self, full_params) -> int:
        """Slice this stage's params out of the full tree (delivered as
        an ObjectRef arg, resolved from the node-local broadcast
        replica) and place them on this stage's devices."""
        import jax

        sliced = stage_params(full_params, self.stage, self.pp,
                              self.model_cfg.num_layers)
        cast = jax.tree.map(
            lambda a: np.asarray(a, dtype=self.dtype), sliced)
        if self.sharding is not None:
            self._param_shardings = self._stage_param_shardings()
            self.params = jax.tree.map(jax.device_put, cast,
                                       self._param_shardings)
        else:
            self.params = jax.tree.map(jax.numpy.asarray, cast)
        return self.stage

    def _stage_param_shardings(self):
        """NamedShardings for THIS stage's param slice, from the same
        logical-axis rule table the full engine uses (the stage module
        reuses the full model's param names/annotations, so the specs
        line up leaf-for-leaf with the slices)."""
        import jax.numpy as jnp

        cfg = self.model_cfg
        if self.first:
            x0 = jnp.zeros((1, 8), jnp.int32)
        else:
            x0 = jnp.zeros((1, 8, cfg.hidden_size), self.dtype)
        pos0 = jnp.zeros((1, 8), jnp.int32)
        return self.sharding.module_param_shardings(
            self.model, x0, pos0, None)

    # ---------------------------------------------------------- compute

    def _jit(self, kind: str, shape_key: tuple):
        import jax
        import jax.numpy as jnp

        from ...models.llama import PagedCache

        key = (kind,) + shape_key
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn
        model = self.model
        Ls = self.n_layers
        last = self.last
        ref_attn = self.sharding is not None
        cp = shape_key[2] if kind == "prefill" else 0

        def run(params, kv_pages, block_tables, total_lens, x, positions,
                gather_idx, temperature, top_k, rng_keys):
            pc = PagedCache(
                kv_pages=kv_pages,
                block_tables=jnp.broadcast_to(
                    block_tables, (Ls,) + block_tables.shape),
                total_lens=jnp.broadcast_to(total_lens,
                                            (Ls,) + total_lens.shape),
                ctx_pages=cp, ref_attention=ref_attn)
            out, new_pc = model.apply({"params": params}, x,
                                      positions, pc)
            if last:
                # sample ON the last stage: only int32 tokens ride the
                # return channel, exactly like the single engine's
                # device-side sampling keeps logits off the host
                b = out.shape[0]
                if kind == "prefill":
                    rows = out[jnp.arange(b), gather_idx]
                else:
                    rows = out[:, 0]
                out = _device_sample(rows.astype(jnp.float32),
                                     temperature, top_k, rng_keys)
            return out, new_pc.kv_pages

        if self.sharding is not None:
            repl = self._repl_sharding
            fn = jax.jit(
                run, donate_argnums=(1,),
                in_shardings=(self._param_shardings,
                              self._kv_sharding) + (repl,) * 8,
                out_shardings=(repl, self._kv_sharding))
        else:
            fn = jax.jit(run, donate_argnums=(1,))
        self._jit_cache[key] = fn
        return fn

    def tick(self, frame: dict) -> dict:
        """One microbatch through this stage. Prefill frames carry
        [rb, sb] token ids (stage 0) / hidden states (later stages);
        decode frames carry the full [S, 1] slot set with only the
        frame's slot group active (total == 0 rows never write). The
        last stage samples and returns a slim {kind, toks} frame."""
        faults.syncpoint("serve.pp_tick")
        import jax.numpy as jnp

        kind = frame["kind"]
        if kind == "prefill":
            shape_key = (frame["sb"], frame["rb"], frame["cp"])
        else:
            shape_key = (1, self.max_pages_per_seq, 0)
        fn = self._jit(kind, shape_key)
        x = frame.pop("ids") if self.first else frame.pop("x")
        out, self.kv_pages = fn(
            self.params, self.kv_pages, jnp.asarray(frame["bt"]),
            jnp.asarray(frame["total"]), jnp.asarray(x),
            jnp.asarray(frame["positions"]), jnp.asarray(frame["gather"]),
            jnp.asarray(frame["temp"]), jnp.asarray(frame["topk"]),
            jnp.asarray(frame["keys"]))
        if self.last:
            return {"kind": kind, "toks": np.asarray(out)}
        frame["x"] = np.asarray(out)
        return frame

    # -------------------------------------------------------- liveness

    def ping(self) -> int:
        return self.stage

    def dag_stats(self, reset: bool = False) -> dict:
        """Starved-read counters published by the DAG loop thread
        (dag/loop_runner.py) — the per-stage bubble measure. Callable
        WHILE the loop runs (actors serve normal calls concurrently)."""
        stats = getattr(self, "__rtpu_dag_stats__", None)
        if not isinstance(stats, dict):
            return {"reads": 0, "starved_reads": 0}
        out = {"reads": int(stats.get("reads", 0)),
               "starved_reads": int(stats.get("starved_reads", 0))}
        if reset:
            stats["reads"] = 0
            stats["starved_reads"] = 0
        return out

    def pid(self) -> int:
        import os

        return os.getpid()


class PipelinedEngine(LLMEngine):
    """LLMEngine whose compute plane is a gang of stage worker
    processes chained by compiled-DAG channels. The scheduler — every
    queue, the allocator, the prefix cache, preemption, harvest
    bookkeeping — is inherited verbatim from LLMEngine; this class only
    rebinds the compute seams, which is precisely why its greedy output
    is bit-exact against the single-process engine."""

    def __init__(self, config: EngineConfig, params=None, mesh=None):
        super().__init__(config, params=params, mesh=mesh)
        # page ids are global; each stage holds its layer slice of every
        # page, tp-sharded inside the stage — label the byte accounting
        # with the per-chip divisor (allocation semantics are unchanged)
        self.allocator.shard_degree = max(1, int(config.tp))
        self.allocator.stats["shard_degree"] = self.allocator.shard_degree

    def _build_compute(self, params, mesh) -> None:
        import jax
        import jax.numpy as jnp

        from ...models.llama import LlamaModel, get_config

        config = self.config
        pp = int(config.pp)
        if pp < 2:
            raise ValueError(
                f"PipelinedEngine needs pp >= 2 (got pp={pp}); use "
                f"LLMEngine for the single-process path")
        if config.spec_lookahead > 0:
            # PR-14 left this interaction implicit ("spec skips slots
            # with in-flight work, so spec and pipelined decode
            # alternate per slot"); under pp there is no device carry
            # for verify to leave stale, but spec's prefill-shaped
            # verify frames would serialize the pipeline per slot —
            # reject loudly instead of silently degrading
            raise ValueError(
                f"spec_lookahead={config.spec_lookahead} is not "
                f"supported with pp={pp}: prompt-lookup speculation "
                f"verifies against a slot-exclusive dispatch, which "
                f"would serialize the stage pipeline per slot. Set "
                f"spec_lookahead=0 (speculation remains a tp/single-"
                f"engine feature)")
        if mesh is not None:
            raise ValueError(
                "PipelinedEngine builds one mesh per stage worker from "
                "EngineConfig.tp; an explicit driver-side mesh= cannot "
                "span the stage processes")
        if config.tp > CHIPS_PER_HOST:
            raise ValueError(
                f"tp={config.tp} exceeds the {CHIPS_PER_HOST} chips one "
                f"host exposes; scale further with pp (stages multiply "
                f"tp, they do not widen it)")
        dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
        self.model_cfg = get_config(
            config.model, scan_layers=True, remat=False, dtype=dtype,
            param_dtype=dtype, max_seq_len=config.max_model_len,
            **config.model_overrides)
        L = self.model_cfg.num_layers
        if L % pp:
            raise ValueError(
                f"pp={pp} must divide num_layers={L} (ragged stage "
                f"splits are not supported)")
        if config.tp > 1:
            if self.model_cfg.num_kv_heads % config.tp \
                    or self.model_cfg.num_heads % config.tp:
                raise ValueError(
                    f"tp={config.tp} must divide num_kv_heads="
                    f"{self.model_cfg.num_kv_heads} and num_heads="
                    f"{self.model_cfg.num_heads}")
        self.model = LlamaModel(self.model_cfg)
        # the driver holds NO device state: stages own the params and
        # the KV pool; the scheduler's page ids are global bookkeeping
        self.sharding = None
        self._attention = {"decode": "per stage worker",
                           "prefill": "per stage worker"}
        self._device = None  # the scheduler process holds no device state
        self.kv_pages = None
        self.slot_ids = None
        self._pp = pp
        # decode slot groups = the microbatch supply that fills the
        # pipeline; 2(S-1) is the classic fill+drain bound
        self._pp_microbatches = int(config.pp_microbatches) \
            or max(2, 2 * (pp - 1))
        config.pipeline_depth = max(int(config.pipeline_depth),
                                    self._pp_microbatches, 2 * (pp - 1))
        self._pp_next_group = 0
        self._pp_ticks = 0

        # full-model init on rank 0, IDENTICAL to the single engine's
        # (same seed, same module) — the parity anchor. Kept as host
        # numpy only long enough to broadcast + slice.
        if params is None:
            import flax.linen as nn

            params = nn.meta.unbox(self.model.init(
                jax.random.PRNGKey(config.seed),
                jnp.zeros((1, 8), jnp.int32))["params"])
        params_np = jax.tree.map(np.asarray, params)
        self.params = None
        self._spawn_stages(params_np)
        self._build_dag()

    # ------------------------------------------------------------- gang

    def _spawn_stages(self, params_np) -> None:
        import ray_tpu

        config = self.config
        worker_cls = ray_tpu.remote(_StageWorker)
        self._stage_handles = [worker_cls.remote(config, s)
                               for s in range(self._pp)]
        ref = ray_tpu.put(params_np)
        # PR-16 replica ladder: land the blob near every stage worker
        # (one owner uplink per round) BEFORE they resolve the ref —
        # same-node workers then read the shm pool, remote workers their
        # node's replica, and nobody point-pulls the full tree
        self.broadcast_report = broadcast_params(ref)
        ray_tpu.get([h.load_params.remote(ref)
                     for h in self._stage_handles], timeout=300)

    def _build_dag(self) -> None:
        from ...dag import InputNode

        with InputNode() as inp:
            node = inp
            for h in self._stage_handles:
                node = h.tick.bind(node)
        # ring depth: the scheduler keeps up to pipeline_depth frames in
        # flight, +2 covers the harvest-side off-by-one while a prefill
        # chunk dispatches
        self._cdag = node.experimental_compile(
            max_inflight_executions=int(self.config.pipeline_depth) + 2)

    def shutdown(self) -> None:
        """Tear the stage DAG down and kill the gang (idempotent)."""
        import ray_tpu

        cdag = getattr(self, "_cdag", None)
        if cdag is not None:
            try:
                cdag.teardown()
            except Exception:  # rtpulint: ignore[RTPU006] — teardown after a dead rank: the sentinel drain can fail, the kills below still reap the gang
                pass
            self._cdag = None
        for h in getattr(self, "_stage_handles", []):
            try:
                ray_tpu.kill(h)
            except Exception:  # rtpulint: ignore[RTPU006] — already-dead rank (the chaos drill's whole point): kill is best-effort reaping
                pass
        self._stage_handles = []

    # ---------------------------------------------------------- compute

    def _dag_execute(self, frame: dict):
        self._pp_ticks += 1
        return self._cdag.execute(frame)

    def _prefill_rows(self, n: int) -> int:
        # the stage programs compute whole frames: a frame crosses the
        # stage channels at ONE shape per length bucket, padding rows
        # included. The single-process engine's row loop is not brought
        # here while pp has run on no chip (ROADMAP A5): the hidden
        # states between stages would have to skip the padding too
        return self._wave_rb

    def _compute_prefill(self, sb, rb, cp, n_rows, bt, total, ids,
                         positions, gather, temp, topk, keys):
        frame = {
            "kind": "prefill", "sb": sb, "rb": rb, "cp": cp,
            "ids": np.asarray(ids), "bt": np.asarray(bt),
            "total": np.asarray(total),
            "positions": np.asarray(positions),
            "gather": np.asarray(gather), "temp": np.asarray(temp),
            "topk": np.asarray(topk), "keys": np.asarray(keys),
        }
        return self._dag_execute(frame)

    def _dispatch_decode_chunk(self) -> bool:
        """Dispatch ONE decode microbatch frame: the next slot group
        (slot % pp_microbatches) with harvested-and-ready slots. A
        slot's next input token is the previous tick's output, so a
        slot is eligible only when nothing of its is in flight
        (planned_out == len(output_ids)); group rotation keeps up to
        pp_microbatches independent frames filling the stage pipeline.
        Frames carry the full [S] slot set (single compile shape, like
        the base engine) with only the group's slots active."""
        cfg = self.config
        S = cfg.max_batch
        elig = [r for r in self._decode_eligible()
                if r.planned_out == len(r.output_ids)]
        if not elig:
            return False
        elig = self._reserve_decode_pages(elig, 1)
        if not elig:
            return False
        M = self._pp_microbatches
        groups: Dict[int, List] = {}
        for r in elig:
            groups.setdefault(r.slot % M, []).append(r)
        for off in range(M):
            g = (self._pp_next_group + off) % M
            if g in groups:
                break
        else:
            return False
        self._pp_next_group = (g + 1) % M
        rows = groups[g]
        mp = self.max_pages_per_seq
        ids = np.zeros((S, 1), np.int32)
        bt = np.zeros((S, mp), np.int32)
        total = np.zeros((S,), np.int32)
        positions = np.zeros((S, 1), np.int32)
        chunk_slots = {}
        facts = []
        for req in rows:
            s = req.slot
            planned_total = len(req.prompt_ids) + req.planned_out
            bt[s, :len(req.pages)] = req.pages
            total[s] = planned_total
            positions[s, 0] = planned_total - 1
            # no cross-frame device carry under pp: EVERY tick feeds the
            # host-known last token (the base engine's override is the
            # first-decode special case; here it is the steady state)
            if s in self._slot_override:
                ids[s, 0] = self._slot_override.pop(s)
            else:
                ids[s, 0] = req.output_ids[-1]
            chunk_slots[s] = (req.request_id, req.planned_out)
            facts.append((req.request_id, 1, planned_total))
        temp, topk, keys = self._sampling_arrays(
            rows, S, slot_layout=True, base="planned")
        for req in rows:
            req.planned_out += 1
        frame = {
            "kind": "decode", "ids": ids, "bt": bt, "total": total,
            "positions": positions, "gather": np.zeros((S,), np.int32),
            "temp": temp, "topk": topk, "keys": keys,
        }
        dispatch_ns = tracing.now_ns()
        ref = self._dag_execute(frame)
        self._enqueue_decode(ref, dispatch_ns, 1, facts, chunk_slots)
        return True

    def _fetch_tokens(self, handle) -> np.ndarray:
        if isinstance(handle, np.ndarray):
            return handle
        try:
            frame = handle.get(timeout=self.config.pp_fetch_timeout_s)
        except exceptions.RtpuError:
            raise
        except (TimeoutError, ChannelClosed) as err:
            raise self._stage_failure(err) from err
        toks = frame["toks"]
        if frame["kind"] == "decode":
            # base harvest indexes [K, slot]
            return np.asarray(toks)[None, :]
        return np.asarray(toks)

    def _stage_failure(self, err) -> Exception:
        """Classify a wedged fetch into a TYPED error: probe each rank
        with a control-plane ping — a dead rank becomes ActorDiedError
        naming the rank; all-alive becomes GetTimeoutError (backpressure
        or a stalled stage, retryable by the caller)."""
        import ray_tpu

        from ...runtime.rpc import RpcError

        for rank, h in enumerate(self._stage_handles):
            try:
                ray_tpu.get(h.ping.remote(), timeout=10.0)
            except (exceptions.RtpuError, TimeoutError, RpcError,
                    OSError) as probe:
                return exceptions.ActorDiedError(
                    h.actor_id,
                    reason=(f"pipeline stage rank {rank}/{self._pp} died "
                            f"mid-flight ({type(probe).__name__}); the "
                            f"replica gang must be replaced"))
        return exceptions.GetTimeoutError(
            f"pipelined result not produced within pp_fetch_timeout_s="
            f"{self.config.pp_fetch_timeout_s}s but all {self._pp} stage "
            f"ranks answer pings ({type(err).__name__} on the result "
            f"channel)")

    # ----------------------------------------------------------- warmup

    def warmup(self, prompt_buckets=None, include_decode=True) -> int:
        """Compile every stage's dispatch shapes by pushing masked dummy
        frames (total_lens=0: no page write lands) through the DAG —
        the base engine's warmup touches self.params/self._jit, which a
        pipelined driver does not have. Serially: each frame is fetched
        before the next dispatch, so warmup never trips the in-flight
        bound."""
        assert not self._inflight, "warmup requires an idle engine"
        S = self.config.max_batch
        rb = self._wave_rb
        mp = self.max_pages_per_seq
        if prompt_buckets is None:
            prompt_buckets = self.config.prefill_buckets
        from itertools import product

        n = 0
        for sb, cp in product(prompt_buckets, (0, mp)):
            frame = {
                "kind": "prefill", "sb": sb, "rb": rb, "cp": cp,
                "ids": np.zeros((rb, sb), np.int32),
                "bt": np.zeros((rb, mp), np.int32),
                "total": np.zeros((rb,), np.int32),
                "positions": np.zeros((rb, sb), np.int32),
                "gather": np.zeros((rb,), np.int32),
                "temp": np.zeros((rb,), np.float32),
                "topk": np.zeros((rb,), np.int32),
                "keys": np.zeros((rb, 2), np.uint32),
            }
            self._dag_execute(frame).get(
                timeout=self.config.pp_fetch_timeout_s)
            n += 1
        if not include_decode:
            return n
        frame = {
            "kind": "decode",
            "ids": np.zeros((S, 1), np.int32),
            "bt": np.zeros((S, mp), np.int32),
            "total": np.zeros((S,), np.int32),
            "positions": np.zeros((S, 1), np.int32),
            "gather": np.zeros((S,), np.int32),
            "temp": np.zeros((S,), np.float32),
            "topk": np.zeros((S,), np.int32),
            "keys": np.zeros((S, 2), np.uint32),
        }
        self._dag_execute(frame).get(
            timeout=self.config.pp_fetch_timeout_s)
        return n + 1

    # ------------------------------------------------------------ stats

    def pp_stats(self, reset: bool = False) -> dict:
        """Measured pipeline occupancy: per-stage starved-read counters
        from every DAG loop plus the driver's tick count.
        ``pp_bubble_frac`` = starved reads / reads across all stages —
        the fraction of stage read-points that found an EMPTY input
        ring (the stage was about to idle). Control-plane calls; never
        used on the steady-state path."""
        import ray_tpu

        per_stage = ray_tpu.get(
            [h.dag_stats.remote(reset) for h in self._stage_handles],
            timeout=60)
        reads = sum(s["reads"] for s in per_stage)
        starved = sum(s["starved_reads"] for s in per_stage)
        return {
            "pp": self._pp,
            "pp_microbatches": self._pp_microbatches,
            "ticks": self._pp_ticks,
            "per_stage": per_stage,
            "reads": reads,
            "starved_reads": starved,
            "pp_bubble_frac": (starved / reads) if reads else 0.0,
        }

    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        out["pp"] = self._pp
        out["pp_microbatches"] = self._pp_microbatches
        out["pp_ticks"] = self._pp_ticks
        return out


def make_engine(config: EngineConfig, params=None,
                mesh=None) -> LLMEngine:
    """Engine factory keyed on EngineConfig.pp: the serve layer calls
    this so `pipeline_parallel_size` is one knob, not a class choice."""
    if int(getattr(config, "pp", 1) or 1) > 1:
        return PipelinedEngine(config, params=params)
    return LLMEngine(config, params=params, mesh=mesh)
