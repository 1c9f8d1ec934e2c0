"""Pipeline-parallel serving tests (serve/llm/pp.py).

Bit-exact greedy parity against the single-process engine on the virtual
CPU mesh (S=2 stages, tp=1 and tp=2, plus preemption-under-pp), zero
steady-state control RPCs over the stage DAG (rpc.transport_sends, like
the cross-host DAG tests), typed config guards (spec x pp), measured
bubble accounting, stage param slicing, gang bundles and the PR-16
broadcast wiring for weight loading. The stage-rank kill drill lives in
tests/test_chaos.py.
"""

import numpy as np
import pytest

from ray_tpu.serve.llm import (EngineConfig, LLMEngine, PipelinedEngine,
                               SamplingParams, make_engine, pp_bundles,
                               tp_bundles)
from ray_tpu.serve.llm.pp import broadcast_params
from ray_tpu.serve.llm.stage import stage_params

pytestmark = pytest.mark.pp

ENGINE_CFG = dict(
    model="tiny", page_size=8, num_pages=64, max_model_len=128,
    max_batch=4, prefill_buckets=(16, 32, 64, 128), dtype="float32",
    model_overrides={"vocab_size": 512},
)


def _collect(engine, want_ids, max_steps=600):
    done = {}
    for _ in range(max_steps):
        for delta in engine.step():
            rec = done.setdefault(delta.request_id,
                                  {"ids": [], "fin": None})
            rec["ids"].extend(delta.new_token_ids)
            if delta.finished:
                rec["fin"] = delta.finish_reason
        if all(done.get(r, {}).get("fin") for r in want_ids):
            break
    return done


def _ids(done):
    return {k: v["ids"] for k, v in done.items()}


# ------------------------------------------------------------ pure units

def test_pp_config_guards_are_typed():
    """Invalid pp configs fail at CONSTRUCTION with a ValueError that
    names the knob — before any stage process spawns."""
    with pytest.raises(ValueError, match="pp >= 2"):
        PipelinedEngine(EngineConfig(pp=1, **ENGINE_CFG))
    # the documented spec x pp exclusion (spec_lookahead would serialize
    # the stage pipeline per slot): rejected loudly, not auto-degraded
    with pytest.raises(ValueError, match="spec_lookahead"):
        PipelinedEngine(EngineConfig(pp=2, spec_lookahead=3,
                                     **ENGINE_CFG))
    # ragged layer splits: tiny has 2 layers
    with pytest.raises(ValueError, match="num_layers"):
        PipelinedEngine(EngineConfig(pp=4, **ENGINE_CFG))
    # a driver-side mesh cannot span the stage processes
    with pytest.raises(ValueError, match="mesh"):
        PipelinedEngine(EngineConfig(pp=2, **ENGINE_CFG), mesh=2)
    # per-stage tp keeps the single-host bound
    with pytest.raises(ValueError, match="chips"):
        PipelinedEngine(EngineConfig(pp=2, tp=8, **ENGINE_CFG))


def test_make_engine_dispatches_on_pp():
    engine = make_engine(EngineConfig(**ENGINE_CFG))
    assert type(engine) is LLMEngine
    with pytest.raises(ValueError, match="spec_lookahead"):
        make_engine(EngineConfig(pp=2, spec_lookahead=2, **ENGINE_CFG))


def test_pp_bundles_shapes_and_bounds():
    assert pp_bundles(3, 2) == [{"TPU": 2.0}] * 3
    assert pp_bundles(1, 4) == tp_bundles(4)
    with pytest.raises(ValueError, match="chips"):
        pp_bundles(2, 8)
    with pytest.raises(ValueError, match="pp"):
        pp_bundles(0, 1)
    # tp_bundles keeps its own single-host contract
    with pytest.raises(ValueError, match="span hosts"):
        tp_bundles(8)


def test_placement_options_pp_gang():
    from ray_tpu.serve.llm.server import LLMConfig, placement_options

    cfg = LLMConfig(engine=EngineConfig(pp=2, tp=2, **ENGINE_CFG),
                    reserve_tpu_bundle=True)
    opts = placement_options(cfg)
    assert opts["placement_strategy"] == "SLICE_PACK"
    assert opts["placement_bundles"] == [{"TPU": 2.0}] * 2
    cfg.reserve_tpu_bundle = False
    assert placement_options(cfg) == {}


def test_stage_params_are_literal_slices():
    """Stage trees reassemble bit-exactly into the full init: layer
    leaves are [L/pp] slices on axis 0, embed only on stage 0,
    final_norm + lm_head only on the last stage."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaModel, get_config

    cfg = get_config("tiny", scan_layers=True, remat=False,
                     max_seq_len=128, vocab_size=512)
    import flax.linen as nn

    full = nn.meta.unbox(LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    s0 = stage_params(full, 0, 1)
    s1 = stage_params(full, 1, 1)
    assert "embed" in s0 and "embed" not in s1
    assert "lm_head" in s1 and "lm_head" not in s0
    assert "final_norm" in s1 and "final_norm" not in s0
    for leaf_full, leaf0, leaf1 in zip(
            jax.tree.leaves(full["layers"]),
            jax.tree.leaves(s0["layers"]),
            jax.tree.leaves(s1["layers"])):
        np.testing.assert_array_equal(
            np.asarray(leaf_full),
            np.concatenate([np.asarray(leaf0), np.asarray(leaf1)], axis=0))


def test_weight_broadcast_ladder_one_uplink_per_round():
    """The weight-loading tree (broadcast_params -> core.broadcast
    fanout=0, the staggered binomial ladder) costs the checkpoint owner
    ONE uplink per round: the ranks that pull directly from rank 0 are
    exactly the powers of two, one new direct child as each round's
    population doubles."""
    from ray_tpu.runtime.tiering import binomial_parents

    for n in (2, 4, 7, 8, 12):  # stage/replica gang sizes
        parents = binomial_parents(n)
        owner_children = [i + 1 for i, p in enumerate(parents)
                          if p is None]
        # one-uplink-per-round: round r adds exactly one new owner
        # child, at rank 2^(r-1) — so the owner's direct children are
        # precisely the powers of two, one per round
        assert owner_children == [
            1 << k for k in range(n.bit_length()) if (1 << k) <= n]
        rounds = max(r.bit_length() for r in range(1, n + 1))
        assert len(owner_children) == rounds


# --------------------------------------------------------- cluster tier

@pytest.mark.slow
def test_pp_bit_exact_greedy_s2_and_broadcast_wiring(shared_cluster):
    """S=2, tp=1: token-identical greedy output vs the single-process
    engine, with the checkpoint landed via the PR-16 replica broadcast
    (spied: fanout=0 => the binomial ladder) before the stages slice."""
    rng = np.random.default_rng(0)
    prompts = {f"r{i}": list(rng.integers(0, 500, 11 + 7 * i))
               for i in range(3)}

    base = LLMEngine(EngineConfig(**ENGINE_CFG))
    for rid, p in prompts.items():
        base.add_request(rid, p, SamplingParams(max_tokens=6))
    ref = _collect(base, list(prompts))

    from ray_tpu.runtime.core import get_core

    core = get_core()
    orig, calls = core.broadcast, []

    def spy(ref_, nodes=None, *, fanout=None, timeout=120.0):
        calls.append({"fanout": fanout})
        return orig(ref_, nodes=nodes, fanout=fanout, timeout=timeout)

    core.broadcast = spy
    try:
        pp = PipelinedEngine(EngineConfig(pp=2, **ENGINE_CFG))
    finally:
        core.broadcast = orig
    try:
        assert calls and calls[0]["fanout"] == 0  # the ladder, not a tree
        assert pp.broadcast_report["failed"] == []
        for rid, p in prompts.items():
            pp.add_request(rid, p, SamplingParams(max_tokens=6))
        out = _collect(pp, list(prompts))
        assert _ids(out) == _ids(ref)
        assert all(v["fin"] == "length" for v in out.values())
        stats = pp.stats()
        assert stats["pp"] == 2 and stats["pp_ticks"] > 0
    finally:
        pp.shutdown()


@pytest.mark.slow
def test_pp_bit_exact_greedy_s2_tp2(shared_cluster):
    """S=2 stages, tp=2 INSIDE each stage (composed single-host TP):
    still token-identical vs the unsharded single-process engine."""
    rng = np.random.default_rng(1)
    prompts = {f"r{i}": list(rng.integers(0, 500, 9 + 5 * i))
               for i in range(2)}
    base = LLMEngine(EngineConfig(**ENGINE_CFG))
    for rid, p in prompts.items():
        base.add_request(rid, p, SamplingParams(max_tokens=5))
    ref = _collect(base, list(prompts))

    pp = PipelinedEngine(EngineConfig(pp=2, tp=2, **ENGINE_CFG))
    try:
        for rid, p in prompts.items():
            pp.add_request(rid, p, SamplingParams(max_tokens=5))
        out = _collect(pp, list(prompts))
        assert _ids(out) == _ids(ref)
        assert pp.allocator.stats["shard_degree"] == 2
    finally:
        pp.shutdown()


@pytest.mark.slow
def test_pp_expert_model_tokens_and_routing_counts(shared_cluster):
    """An expert model under pp: each stage counts its own layers' routing
    and the counts ride the frames, so the driver's `engine.dispatch`
    records (and greedy tokens) are the single engine's."""
    from ray_tpu.util import tracing

    cfg = dict(ENGINE_CFG, model="tiny-moe")
    rng = np.random.default_rng(2)
    prompts = {f"r{i}": list(rng.integers(0, 500, 11 + 3 * i))
               for i in range(3)}

    def run(engine):
        tracing.reset_ring()
        for rid, p in prompts.items():
            engine.add_request(rid, p, SamplingParams(max_tokens=5))
        out = _ids(_collect(engine, list(prompts)))
        fields = tracing.FIELDS["engine.dispatch"]
        moe = [(d["kind"], d["rows_padded"], d["moe_assignments"],
                d["moe_experts_touched"]) for d in (
                    dict(zip(fields, rec))
                    for rec in tracing.records("engine.dispatch"))
               if d["kind"] == "prefill"]
        assert engine.stats()["moe_assignments_total"] > sum(
            n for _, _, n, _ in moe)   # decode steps counted too
        return out, moe

    want = run(LLMEngine(EngineConfig(**cfg)))
    pp = PipelinedEngine(EngineConfig(pp=2, **cfg))
    try:
        assert run(pp) == want
    finally:
        pp.shutdown()


@pytest.mark.slow
def test_pp_preemption_token_identical(shared_cluster):
    """OutOfPages mid-decode under pp: preempt -> re-prefill ->
    continue, still token-identical to the uncontended single-engine
    run of each request alone (the host-side preemption machinery is
    the inherited PR-14 path; only the compute plane is staged)."""
    cfg = dict(ENGINE_CFG)
    cfg.update(num_pages=12, max_model_len=64, max_batch=2,
               prefill_buckets=(16, 32, 64))
    rng = np.random.default_rng(4)
    prompts = {f"p{i}": list(rng.integers(0, 500, 17)) for i in range(2)}

    solo = {}
    for rid, p in prompts.items():
        engine = LLMEngine(EngineConfig(**cfg))
        engine.add_request(rid, p, SamplingParams(max_tokens=40))
        solo.update(_collect(engine, [rid], max_steps=900))

    pp = PipelinedEngine(EngineConfig(pp=2, **cfg))
    try:
        for rid, p in prompts.items():
            pp.add_request(rid, p, SamplingParams(max_tokens=40))
        out = _collect(pp, list(prompts), max_steps=900)
        assert pp.stats()["preempted_total"] >= 1
        for rid in prompts:
            assert out[rid]["ids"] == solo[rid]["ids"], rid
        assert pp.allocator.num_free() == cfg["num_pages"] - 1
    finally:
        pp.shutdown()


def test_pp_zero_control_rpcs_and_bubble_accounting(shared_cluster,
                                                    dense_greedy):
    """Steady-state decode moves ONLY channel frames: across a window
    of pure-decode steps the process's RPC send counters stay flat
    (ambient liveness aside). The same window feeds the measured bubble
    counters: every stage counted reads, pp_bubble_frac in [0, 1], and
    reset zeroes the window. `engine.dispatch` prefill records carry
    `tokens_padded` = real rows x bucket. Run to the end, the stages' tokens (each
    stage's pool an [n_layers] slice carried through its own layer scan)
    are those of the whole model run densely with no cache."""
    from ray_tpu.runtime import rpc
    from ray_tpu.util import tracing

    tracing.reset_ring()
    cfg = EngineConfig(pp=2, pp_microbatches=4, **ENGINE_CFG)
    pp = PipelinedEngine(cfg)
    try:
        # depth raised to cover the fill+drain window
        assert cfg.pipeline_depth >= 4
        rng = np.random.default_rng(7)
        prompts = {f"r{i}": list(rng.integers(0, 500, 12))
                   for i in range(4)}
        got = {rid: [] for rid in prompts}

        def step():
            for delta in pp.step():
                got[delta.request_id].extend(delta.new_token_ids)

        for rid, prompt in prompts.items():
            pp.add_request(rid, prompt, SamplingParams(max_tokens=30))
        # enter steady state: every request prefilled and decoding
        for _ in range(200):
            step()
            if all(r.decode_ready for r in pp.running) \
                    and len(pp.running) == 4:
                break
        assert len(pp.running) == 4
        pp.pp_stats(reset=True)  # control-plane call OUTSIDE the window

        ambient = {"heartbeat", "report_metrics", "view_update"}
        before = rpc.transport_sends()
        for _ in range(12):
            step()
        after = rpc.transport_sends()
        delta = {k: after[k] - before.get(k, 0) for k in after
                 if after[k] != before.get(k, 0) and k not in ambient}
        assert not delta, f"steady-state pp decode issued RPCs: {delta}"

        stats = pp.pp_stats()
        assert stats["pp"] == 2 and stats["pp_microbatches"] == 4
        assert len(stats["per_stage"]) == 2
        assert stats["reads"] > 0
        assert 0.0 <= stats["pp_bubble_frac"] <= 1.0
        assert pp.pp_stats(reset=True)["reads"] >= 0

        for _ in range(600):
            if not (pp.running or pp.waiting):
                break
            step()
        whole = LLMEngine(EngineConfig(**ENGINE_CFG))  # same seed, unrun
        want = dense_greedy(whole.model, whole.params,
                            list(prompts.values()), 30)
        assert list(got.values()) == want
        # the stages ran the single engine's row loop: a prefill frame
        # computed its requests' rows (12 tokens: the bucket of 16) and
        # no padding row, and the records say so
        prefills = [dict(zip(tracing.FIELDS["engine.dispatch"], rec))
                    for rec in tracing.records("engine.dispatch")
                    if rec[1] == "prefill"]
        assert sum(len(d["rows"]) for d in prefills) == 4
        for d in prefills:
            assert d["rows_padded"] == len(d["rows"]) <= pp._wave_rb
            assert d["tokens_padded"] == len(d["rows"]) * 16
    finally:
        pp.shutdown()
