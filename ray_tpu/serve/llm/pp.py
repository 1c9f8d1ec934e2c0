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
- ``_compute_prefill`` / ``_compute_decode``: dispatch microbatch FRAMES
  down the DAG instead of enqueueing a local stage's programs (every
  stage worker runs the SAME programs as the single engine, stage.py),
  and ``_decode_eligible`` picks one slot group a frame;
- ``_fetch_tokens``: resolve CompiledDAGRef results, converting a dead
  stage rank into a TYPED ActorDiedError/GetTimeoutError (a SIGKILLed
  rank writes no sentinel, so the fetch would otherwise be an untyped
  timeout);
- ``_handle_ready``: None, so its flight records carry no device stamps.

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

from typing import Any, Dict, List

import numpy as np

from ... import exceptions
from ...runtime import faults
from ...runtime.channel import ChannelClosed
from .engine import EngineConfig, LLMEngine
from .sharding import CHIPS_PER_HOST, ServeSharding
from .stage import (OPERANDS, StageCompute, dummy_operands, init_params,
                    serve_model_config)


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
    """One pipeline stage: an actor process around the StageCompute of
    its [L/pp] layers (stage.py: param slice, the matching layer slice of
    the paged KV pool, and (tp > 1) its own single-host tp mesh). Driven
    through the compiled DAG — ``tick`` is the per-microbatch frame
    handler the DAG loop calls; the normal actor methods (ping/dag_stats)
    stay callable concurrently."""

    def __init__(self, config: EngineConfig, stage: int):
        self.stage = int(stage)
        per = serve_model_config(config).num_layers // int(config.pp)
        # tp INSIDE the stage: this worker's own process-local mesh
        self.compute = StageCompute(config, self.stage * per, per)

    def load_params(self, full_params) -> int:
        """`full_params` is delivered as an ObjectRef arg, resolved from
        the node-local broadcast replica."""
        self.compute.load(full_params)
        return self.stage

    def tick(self, frame: dict) -> dict:
        """One microbatch through this stage. A frame is the program's
        kind, shape key and operands by name (stage.py: OPERANDS), "x"
        being [rows, span] token ids into the first stage and hidden
        states after it; decode frames carry the full [S, 1] slot set
        with only the frame's slot group active (total == 0 rows never
        write). The last stage samples and returns a slim {kind, toks}
        frame; an expert model's routing counts ride the frame stage by
        stage and leave behind the tokens, in layer order."""
        faults.syncpoint("serve.pp_tick")
        kind = frame["kind"]
        out = self.compute.run(kind, frame["key"],
                               *(frame[name] for name in OPERANDS[kind]))
        counts = frame.get("counts", [])
        if self.compute.last:
            toks = np.asarray(out)
            if counts:
                own = (self.compute.n_layers
                       * self.compute.model_cfg.num_experts)
                toks = np.concatenate([toks[:-own], *counts, toks[-own:]])
            return {"kind": kind, "toks": toks}
        if self.compute.model_cfg.num_experts:
            out, own = out
            frame["counts"] = [*counts, np.asarray(own).reshape(-1)]
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

        from ...models.llama import LlamaModel

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
        self.model_cfg = serve_model_config(config)
        L = self.model_cfg.num_layers
        if L % pp:
            raise ValueError(
                f"pp={pp} must divide num_layers={L} (ragged stage "
                f"splits are not supported)")
        if config.tp > 1:
            ServeSharding(mesh=None, tp=config.tp).validate(self.model_cfg)
        self.model = LlamaModel(self.model_cfg)
        # the driver holds NO device state: stages own the params and
        # the KV pool; the scheduler's page ids are global bookkeeping
        self.sharding = None
        self._attention = {"decode": "per stage worker",
                           "prefill": "per stage worker"}
        self._device = None  # the scheduler process holds no device state
        self._pp = pp
        # decode slot groups = the microbatch supply that fills the
        # pipeline; 2(S-1) is the classic fill+drain bound
        self._pp_microbatches = int(config.pp_microbatches) \
            or max(2, 2 * (pp - 1))
        config.pipeline_depth = max(int(config.pipeline_depth),
                                    self._pp_microbatches, 2 * (pp - 1))
        # a slot's next id is sampled on the last stage and embedded on
        # the first: one step a dispatch
        config.decode_steps_per_dispatch = 1
        self._pp_next_group = 0
        self._pp_ticks = 0

        # full-model init on rank 0, IDENTICAL to the single engine's
        # (same seed, same module) — the parity anchor. Kept as host
        # numpy only long enough to broadcast + slice.
        if params is None:
            params = init_params(self.model, jnp.zeros((1, 8), jnp.int32),
                                 jax.random.PRNGKey(config.seed))
        self._spawn_stages(jax.tree.map(np.asarray, params))
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

    @staticmethod
    def _frame(kind: str, key: tuple, operands) -> dict:
        """A program's kind, shape key and operands by name, on the host:
        what crosses the stage channels."""
        return dict(zip(OPERANDS[kind], map(np.asarray, operands)),
                    kind=kind, key=key)

    def _compute_prefill(self, sb, rb, cp, *operands):
        return self._dag_execute(
            self._frame("prefill", (sb, rb, cp), operands))

    def _compute_decode(self, k_steps, mp, *operands):
        return self._dag_execute(
            self._frame("decode", (k_steps, mp), operands))

    def _decode_eligible(self) -> List:
        """The slots of ONE decode microbatch frame: the next slot group
        (slot % pp_microbatches) with harvested-and-ready slots. A
        slot's next input token is the previous tick's output, so a
        slot is eligible only when nothing of its is in flight
        (planned_out == len(output_ids)); group rotation keeps up to
        pp_microbatches independent frames filling the stage pipeline.
        Frames carry the full [S] slot set (single compile shape, like
        the base engine) with only the group's slots active. There is no
        cross-frame device carry under pp, so EVERY tick feeds the
        host-known last token: the base engine's first-decode override
        is the steady state here."""
        M = self._pp_microbatches
        groups: Dict[int, List] = {}
        for r in super()._decode_eligible():
            if r.planned_out == len(r.output_ids):
                groups.setdefault(r.slot % M, []).append(r)
        for off in range(M):
            g = (self._pp_next_group + off) % M
            if g in groups:
                break
        else:
            return []
        self._pp_next_group = (g + 1) % M
        for r in groups[g]:
            self._slot_override[r.slot] = (
                r.output_ids[-1] if r.output_ids else r.prompt_ids[-1])
        return groups[g]

    @staticmethod
    def _handle_ready(handle) -> None:
        """A frame's programs run in the stage workers: this process
        cannot say when they ended, and stamps no device timeline."""
        return None

    # (a frame's programs and copies are its stage workers': nothing of
    # this process is pending when the engine is left)
    _await_handle = staticmethod(lambda handle: None)

    def _fetch_tokens(self, handle) -> np.ndarray:
        if isinstance(handle, np.ndarray):
            return handle
        try:
            frame = handle.get(timeout=self.config.pp_fetch_timeout_s)
        except exceptions.RtpuError:
            raise
        except (TimeoutError, ChannelClosed) as err:
            raise self._stage_failure(err) from err
        return np.asarray(frame["toks"])

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
        frames (total_lens=0: no page write lands) through the DAG — the
        base engine's warmup runs its own stage's programs, which a
        pipelined driver does not have. Serially: each frame is fetched
        before the next dispatch, so warmup never trips the in-flight
        bound."""
        assert not self._inflight, "warmup requires an idle engine"
        programs = self._warmup_programs(prompt_buckets, include_decode)
        for kind, key in programs:
            self._dag_execute(self._frame(
                kind, key, dummy_operands(self.config, kind, key))).get(
                    timeout=self.config.pp_fetch_timeout_s)
        return len(programs)

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
