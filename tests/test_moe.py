"""Mixture-of-experts model family + expert parallelism.

The reference ships no in-repo MoE/EP implementation (SURVEY.md §2.4: EP is
"delegated to engines"), so this is greenfield TPU-native surface: Mixtral's
sparse FFN, dropless (sorted assignments through a grouped matmul) wherever
experts are not sharded, and the capacity-based grouped einsum dispatch
with expert weights sharded over the mesh's ep axis.

The serving tests compare the program in float32 with the plain reference
`chipbench/references/moe_decoder.py` (the benchmark's own file: one
source, no drift) on seeded random weights at tiny size.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaModel, get_config

from _engines import applied, jitted, tiny_engine


@pytest.fixture(scope="module")
def tiny_moe():
    cfg = get_config("tiny-moe")
    model = LlamaModel(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 32), dtype=np.int32))
    params = nn.meta.unbox(
        jitted(model.init)(jax.random.PRNGKey(0), ids)["params"])
    return cfg, model, params, ids


def test_moe_forward_and_fused_loss(tiny_moe):
    cfg, model, params, ids = tiny_moe
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 32, cfg.vocab_size)
    nll = model.apply({"params": params}, ids, targets=ids)
    assert nll.shape == (2, 32)
    assert np.isfinite(float(nll.mean()))
    # expert stacks exist: [L, E, h, 2f]
    gu = params["layers"]["layer"]["moe"]["experts_gate_up"]
    assert gu.shape == (cfg.num_layers, cfg.num_experts, cfg.hidden_size,
                        2 * cfg.intermediate_size)


def test_moe_aux_loss_sown_not_folded(tiny_moe):
    """Router load-balancing loss is sown into the 'losses' collection —
    the per-token nll stays pure cross-entropy — and the trainer adds the
    sown terms to its training loss."""
    cfg, model, params, ids = tiny_moe
    # plain apply: nll unchanged whether or not aux exists
    nll = model.apply({"params": params}, ids, targets=ids)
    nll2, variables = model.apply({"params": params}, ids, targets=ids,
                                  mutable=["losses"])
    np.testing.assert_allclose(np.asarray(nll), np.asarray(nll2))
    aux_total = sum(float(jnp.sum(leaf)) for leaf in
                    jax.tree_util.tree_leaves(variables["losses"]))
    # aux >= 1 per layer for any routing distribution (Cauchy-Schwarz,
    # equality at perfect balance), already scaled by the coefficient
    assert aux_total >= cfg.router_aux_loss_coef * cfg.num_layers * 0.99

    # the sharded trainer's loss includes the sown term: against an
    # identical model with the coefficient zeroed, the gap is exactly the
    # scaled aux total (same params + inputs -> same routing)
    import dataclasses

    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.train_lib import ShardedTrainer

    mesh = create_mesh(MeshConfig(dp=1, fsdp=1, sp=1, ep=1, tp=1),
                       devices=jax.devices("cpu")[:1])
    state = type("S", (), {"params": params})()
    loss = float(ShardedTrainer(model, mesh).eval_loss(
        state, {"input_ids": ids}))
    model0 = LlamaModel(dataclasses.replace(cfg,
                                            router_aux_loss_coef=0.0))
    loss0 = float(ShardedTrainer(model0, mesh).eval_loss(
        state, {"input_ids": ids}))
    assert loss > loss0
    np.testing.assert_allclose(loss - loss0, aux_total, rtol=1e-3)


def test_moe_capacity_drops_are_finite(tiny_moe):
    """The capacity dispatch, which runs where experts are sharded over
    ep: with a starved capacity factor most tokens overflow and are
    dropped (identity residual passes them through) — output must stay
    finite, not NaN, and it differs from the dropless layer's, which the
    same model computes without an ep axis."""
    cfg, _, params, ids = tiny_moe
    import dataclasses

    from ray_tpu.parallel.mesh import MeshConfig, active_mesh, create_mesh

    tight = LlamaModel(dataclasses.replace(cfg, capacity_factor=0.1))
    mesh = create_mesh(MeshConfig(dp=1, fsdp=1, sp=1, ep=2, tp=1),
                       devices=jax.devices("cpu")[:2])
    with active_mesh(mesh):
        dropped = np.asarray(tight.apply({"params": params}, ids),
                             np.float32)
    assert np.isfinite(dropped).all()
    dropless = np.asarray(tight.apply({"params": params}, ids), np.float32)
    assert np.abs(dropped - dropless).max() > 1e-3


@pytest.mark.slow
def test_moe_ep_sharded_training_matches_single_device(cpu_mesh_devices):
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.train_lib import (ShardedTrainer,
                                            default_optimizer)

    cfg = get_config("tiny-moe")
    model = LlamaModel(cfg)
    batch = {"input_ids": np.random.default_rng(1).integers(
        0, cfg.vocab_size, (4, 64), dtype=np.int32)}

    losses = {}
    for name, mesh_cfg, devs in [
        ("single", MeshConfig(dp=1, fsdp=1, sp=1, ep=1, tp=1),
         cpu_mesh_devices[:1]),
        ("ep_sharded", MeshConfig(dp=1, fsdp=2, sp=1, ep=2, tp=2),
         cpu_mesh_devices[:8]),
    ]:
        mesh = create_mesh(mesh_cfg, devices=devs)
        trainer = ShardedTrainer(model, mesh,
                                 optimizer=default_optimizer(lr=1e-3))
        state = trainer.init(jax.random.PRNGKey(0), batch)
        state, metrics = trainer.step(state, batch)
        losses[name] = float(metrics["loss"])
        if name == "ep_sharded":
            spec = state.params["layers"]["layer"]["moe"][
                "experts_gate_up"].sharding.spec
            assert "ep" in jax.tree_util.tree_leaves(tuple(spec)), spec
    np.testing.assert_allclose(losses["single"], losses["ep_sharded"],
                               rtol=2e-2)


@pytest.mark.slow
def test_moe_paged_decode_in_engine(shared_cluster):
    """The serving engine generates with an MoE model (paged KV + sparse
    FFN compose)."""
    from ray_tpu.serve.llm.engine import (EngineConfig, LLMEngine,
                                          SamplingParams)

    engine = LLMEngine(EngineConfig(model="tiny-moe", max_model_len=128,
                                    num_pages=32, prefill_buckets=(32,)))
    engine.add_request("r1", list(range(1, 9)),
                       SamplingParams(max_tokens=4))
    got = []
    while engine.has_work():
        for delta in engine.step():
            got.extend(delta.new_token_ids)
    assert len(got) == 4


# ------------------------------------------- the dropless serving path
# float32 program against the float32 reference. CPU matmuls are exact
# float32, so what is left is the order of summation: readings are 2e-6
# on logits whose rms is 1 (largest 3.6). The tolerance is ten times that
# and 500 times under what bfloat16 matmuls give (1e-2).
TOL = 2e-5


def _published(cfg):
    return dict(num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads,
                hidden_size=cfg.hidden_size, head_dim=cfg.head_dim_,
                intermediate_size=cfg.intermediate_size,
                num_experts_per_tok=cfg.num_experts_per_tok,
                rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta)


@pytest.fixture(scope="module")
def moe_f32():
    """tiny-moe in float32 with its parameters, and the reference's view
    of the same arrays."""
    from chipbench.references import moe_decoder

    cfg = get_config("tiny-moe", dtype=jnp.float32)
    model = LlamaModel(cfg)
    params = nn.meta.unbox(jitted(model.init)(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"])
    return (cfg, model, params, moe_decoder,
            moe_decoder.weights_from_program_tree(params), _published(cfg))


@pytest.mark.parametrize("shape", [(3, 48), (32, 128)])
def test_moe_forward_matches_reference(moe_f32, shape):
    """(a) LlamaModel's full forward against moe_decoder.forward; the
    larger batch is 8192 assignments, which pass the experts in two
    blocks with an expert's group across the boundary."""
    cfg, model, params, ref, weights, pub = moe_f32
    ids = jnp.asarray(np.random.default_rng(5).integers(
        0, cfg.vocab_size, shape, dtype=np.int32))
    want = np.asarray(ref.forward(weights, ids, pub))
    got = np.asarray(model.apply({"params": params}, ids))
    assert np.sqrt((want ** 2).mean()) > 0.5      # not a comparison of zeros
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def _tiny_engine(twin=None, **over):
    """The module's engine of this configuration, renewed."""
    cfg = dict(model="tiny-moe", dtype="float32", page_size=8, num_pages=64,
               max_model_len=128, max_batch=4,
               prefill_buckets=(16, 32, 64, 128), seed=3)
    cfg.update(over)
    return tiny_engine(**cfg, twin=twin)


def test_moe_paged_prefill_and_decode_match_reference():
    """(b) Prefill, then decode token by token through a PagedCache (the
    benchmark's own routine, which pads the prompts to one width and hands
    the model no mask), against the reference's full forward at every
    position."""
    from chipbench.references import moe_decoder as ref
    from chipbench.runners.engine import _paged_logits

    engine = _tiny_engine()
    pub = _published(engine.model_cfg)
    weights = ref.weights_from_program_tree(engine.params)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 256, n).tolist() for n in (5, 19, 40)]
    logits, fed = _paged_logits(engine, prompts, 6)
    for prompt, toks, got in zip(prompts, fed, logits):
        seq = prompt + toks[:-1]
        want = np.asarray(ref.forward(weights, jnp.asarray([seq]), pub))[0]
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_moe_paged_path_casts_the_expert_stack_once_whatever_its_type():
    """The paged path reads the scanned stack of experts in place
    (`_stacked_experts`) also where the parameters are kept in another
    type than the activations: bfloat16 parameters under float32
    activations (an exact cast) equal the reference on the same values."""
    import types

    from chipbench.references import moe_decoder as ref
    from chipbench.runners.engine import _paged_logits

    cfg = get_config("tiny-moe", dtype=jnp.float32, param_dtype=jnp.bfloat16)
    model = LlamaModel(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(4), jnp.zeros((1, 8), jnp.int32))["params"])
    engine = types.SimpleNamespace(
        model=model, params=params, model_cfg=cfg,
        config=types.SimpleNamespace(page_size=8),
        kv_pages=jnp.zeros((1,), jnp.float32))
    prompts = [np.random.default_rng(7).integers(0, 256, 21).tolist()]
    logits, fed = _paged_logits(engine, prompts, 4)
    want = np.asarray(ref.forward(
        ref.weights_from_program_tree(params),
        jnp.asarray([prompts[0] + fed[0][:-1]]), _published(cfg)))[0]
    np.testing.assert_allclose(logits[0], want, atol=TOL, rtol=0)


@pytest.mark.parametrize("capacity_factor", [0.1, 1.25])
@pytest.mark.parametrize("slot", [0, 15, 31])
def test_moe_row_is_independent_of_slot_and_padding(moe_f32, slot,
                                                   capacity_factor):
    """(c) A wave of 32 rows through a PagedCache, one real row of 11
    tokens in `slot` and token 0 everywhere else (31 padded rows, 117
    padded positions; the second block of its 8192 assignments is padding
    only and is skipped), masked as the engine masks it: the real row's logits are
    those of the row alone, unpadded. The capacity dispatch this replaces
    let identical padding tokens fill their two experts' capacity, so a
    row in a later slot lost its experts (at capacity_factor 0.1 nearly
    all of them)."""
    import dataclasses

    from ray_tpu.models.llama import PagedCache

    cfg, _, params, _, _, _ = moe_f32
    cfg = dataclasses.replace(cfg, capacity_factor=capacity_factor)
    model = LlamaModel(cfg)
    page, n, width, rows = 8, 11, 128, 32
    row = np.random.default_rng(7).integers(1, cfg.vocab_size, n)

    def run(ids, lens, mask):
        b, s = ids.shape
        mp = -(-s // page)
        pc = PagedCache(
            kv_pages=jnp.zeros((cfg.num_layers, 1 + b * mp, cfg.num_kv_heads,
                                page, 2 * cfg.head_dim_), jnp.float32),
            block_tables=jnp.broadcast_to(
                jnp.arange(1, 1 + b * mp).reshape(b, mp),
                (cfg.num_layers, b, mp)),
            total_lens=jnp.broadcast_to(lens, (cfg.num_layers, b)))
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        logits, _ = applied(
            model, params, jnp.asarray(ids), positions=positions,
            kv_caches=pc,
            token_mask=None if mask is None else positions < lens[:, None])
        return np.asarray(logits)

    alone = run(row[None], jnp.asarray([n]), None)[0]
    ids = np.zeros((rows, width), np.int32)
    ids[slot, :n] = row
    lens = jnp.zeros((rows,), jnp.int32).at[slot].set(n)
    padded = run(ids, lens, True)[slot, :n]
    np.testing.assert_allclose(padded, alone, atol=TOL, rtol=0)


@pytest.fixture(scope="module")
def moe_engine_run():
    """Every way the engine's programs touch the pool and the experts, in
    one run (the scenario of test_llm_serve's dense twin): fresh prefill,
    32 decode steps, a preemption and its re-prefill, a prefix-cache hit."""
    from ray_tpu.serve.llm.engine import SamplingParams
    from ray_tpu.util import tracing

    engine = _tiny_engine(num_pages=12, max_model_len=64, max_batch=2,
                          prefill_buckets=(16, 32, 64))
    rng = np.random.default_rng(4)
    prompts = {f"p{i}": rng.integers(0, 256, 17).tolist() for i in range(2)}
    prompts["hit"] = prompts["p0"][:16] + rng.integers(0, 256, 5).tolist()
    n = 32
    out = {rid: [] for rid in prompts}
    seen = tracing.appended("engine.dispatch")

    def drain(rids):
        for rid in rids:
            engine.add_request(rid, prompts[rid],
                               SamplingParams(max_tokens=n))
        for _ in range(900):
            if not engine.has_work():
                break
            for delta in engine.step():
                out[delta.request_id].extend(delta.new_token_ids)

    drain(["p0", "p1"])
    preempted = engine.stats()["preempted_total"]
    hits = engine.allocator.stats["cache_hits"]
    drain(["hit"])
    fields = tracing.FIELDS["engine.dispatch"]
    records = [dict(zip(fields, r))
               for r in tracing.records("engine.dispatch", seen)]
    return dict(engine=engine, prompts=prompts, out=out, n=n,
                preempted=preempted,
                prefix_hit=engine.allocator.stats["cache_hits"] > hits,
                records=records)


def test_moe_engine_matches_reference_greedy_through_prefix_hit_and_preemption(
        moe_engine_run):
    """(d) The engine's greedy tokens through add_request/step() are the
    reference's argmax, token for token, on each request's sequence."""
    from chipbench.references import moe_decoder as ref

    run = moe_engine_run
    assert run["preempted"] >= 1 and run["prefix_hit"]
    engine, n = run["engine"], run["n"]
    pub = _published(engine.model_cfg)
    weights = ref.weights_from_program_tree(engine.params)
    seqs = [list(p) for p in run["prompts"].values()]
    width = -(-(max(map(len, seqs)) + n) // 16) * 16
    rows_fn = jax.jit(lambda ids, rows: ref.forward_rows(
        weights, ids, rows, pub))
    for _ in range(n):
        ids = np.zeros((len(seqs), width), np.int32)
        for i, seq in enumerate(seqs):
            ids[i, :len(seq)] = seq
        last = np.asarray([[len(seq) - 1] for seq in seqs], np.int32)
        logits = np.asarray(rows_fn(jnp.asarray(ids), jnp.asarray(last)))
        for i, seq in enumerate(seqs):
            seq.append(int(logits[i, 0].argmax()))
    for (rid, prompt), seq in zip(run["prompts"].items(), seqs):
        assert run["out"][rid] == seq[len(prompt):], rid


def test_moe_dispatch_records_count_real_assignments(moe_engine_run):
    """(e) Every `engine.dispatch` record of that run: `moe_assignments` is
    real tokens x k x L, `moe_experts_touched` is what the REFERENCE's
    router keeps for those tokens (per fused step and layer, the number of
    distinct experts over the program's real tokens), and the fullest
    expert's count lies between the mean and all of a layer's tokens."""
    from chipbench.references import moe_decoder as ref
    from ray_tpu.models.llama import moe_tile_rows

    run = moe_engine_run
    engine = run["engine"]
    cfg = engine.model_cfg
    k, L = cfg.num_experts_per_tok, cfg.num_layers
    weights = ref.weights_from_program_tree(engine.params)
    chosen = {}     # request -> [L, S, k]: the reference's experts
    for rid, prompt in run["prompts"].items():
        seq = prompt + run["out"][rid]
        chosen[rid] = np.asarray(ref.routing(
            weights, jnp.asarray([seq]), _published(cfg)))[0]
    kinds = set()
    tile_rows = 0
    for rec in run["records"]:
        kinds.add(rec["kind"])
        tokens = sum(q for _, q, _ in rec["rows"])
        assert rec["moe_assignments"] == tokens * k * L, rec
        decode = rec["kind"] == "decode"
        steps = rec["k"] if decode else 1
        counts = np.zeros((steps, L, cfg.num_experts), np.int64)
        for j in range(steps):
            for layer in range(L):
                for rid, q, ctx in rec["rows"]:
                    at = (slice(ctx - 1 + j, ctx + j) if decode
                          else slice(ctx - q, ctx))
                    np.add.at(counts[j, layer],
                              chosen[rid][layer, at].ravel(), 1)
        assert rec["moe_experts_touched"] == (counts > 0).sum(), rec
        # a pass of the model: the slot set, or one row's length bucket
        tile_rows += moe_tile_rows(
            counts, rec["rows_padded"] if decode
            else rec["tokens_padded"] // rec["rows_padded"], cfg)
        assert (tokens * k / cfg.num_experts / steps
                <= rec["moe_expert_tokens_max"] <= tokens / steps), rec
    assert kinds == {"prefill", "decode"}
    stats = engine.stats()
    assert stats["moe_assignments_total"] == sum(
        r["moe_assignments"] for r in run["records"])
    assert stats["moe_experts_touched_total"] == sum(
        r["moe_experts_touched"] for r in run["records"])
    # the rows the grouped matmul multiplied for them: `tile_visits` x the
    # m-tile, on the same counts (a tiny pass fits one tile: every touched
    # expert is one visit of it)
    assert stats["moe_tile_rows_total"] == tile_rows
    assert tile_rows == 128 * stats["moe_experts_touched_total"]
    # and both totals reach /metrics as rtpu_llm_<key>
    from ray_tpu.serve.llm.server import EngineDriverMixin
    from ray_tpu.util import metrics

    driver = EngineDriverMixin()
    driver.engine = engine
    driver._init_driver()
    before = metrics.snapshot("rtpu_llm_")
    driver._publish_llm_metrics(stats)
    after = metrics.snapshot("rtpu_llm_")
    for key in ("moe_assignments_total", "moe_experts_touched_total",
                "moe_tile_rows_total"):
        name = f"rtpu_llm_{key}"
        assert after[name] - before.get(name, 0) == stats[key], name
    # what the tile rule made of the model's widths, once, as a gauge (a
    # tiny model's widths are no whole MXU edges: its tile is the weights)
    assert stats["moe_tile_kn_fill_pct"] == 100.0
    assert after["rtpu_llm_moe_tile_kn_fill_pct"] == 100.0


def test_moe_engine_prefill_with_groups_on_tile_boundaries():
    """A prompt long enough that `row_tile` aligns its pass's groups (100
    tokens in the 128 bucket: 256 assignments on 4 experts) through
    add_request/step(): the reference's greedy tokens, and tile rows that
    are whole tiles holding every assignment."""
    from chipbench.references import moe_decoder as ref
    from ray_tpu.models.llama import moe_row_layout
    from ray_tpu.serve.llm.engine import SamplingParams

    engine = _tiny_engine()
    cfg = engine.model_cfg
    assert moe_row_layout(128, cfg)[:2] == (128, True)
    prompt = np.random.default_rng(9).integers(0, 256, 100).tolist()
    engine.add_request("long", prompt, SamplingParams(max_tokens=4))
    out = []
    while engine.has_work():
        for delta in engine.step():
            out.extend(delta.new_token_ids)
    weights = ref.weights_from_program_tree(engine.params)
    seq = list(prompt)
    for _ in range(4):
        logits = np.asarray(ref.forward(weights, jnp.asarray([seq]),
                                        _published(cfg)))
        seq.append(int(logits[0, -1].argmax()))
    assert out == seq[len(prompt):]
    stats = engine.stats()
    assert stats["moe_tile_rows_total"] % 128 == 0
    assert stats["moe_assignments_total"] <= stats["moe_tile_rows_total"]


def test_dense_engine_records_carry_no_moe_fields():
    """A dense model's programs, records and stats() are what they were."""
    from ray_tpu.serve.llm.engine import (EngineConfig, LLMEngine,
                                          SamplingParams)
    from ray_tpu.util import tracing

    engine = LLMEngine(EngineConfig(
        model="tiny", dtype="float32", page_size=8, num_pages=32,
        max_model_len=64, max_batch=2, prefill_buckets=(16,)))
    seen = tracing.appended("engine.dispatch")
    engine.add_request("d", [1, 2, 3, 4, 5], SamplingParams(max_tokens=3))
    while engine.has_work():
        engine.step()
    fields = tracing.FIELDS["engine.dispatch"]
    records = tracing.records("engine.dispatch", seen)
    assert records and all(
        set(r[fields.index("moe_assignments"):fields.index("enqueued_ns")])
        == {None} for r in records)
    assert not any(key.startswith("moe_") for key in engine.stats())


# (M, E, K, N) -> the m-tile: every call `mixtral-chat`'s programs make
# (a decode step's 32 slots x 2; a prompt's [1 x bucket] pass at the five
# buckets; both products), a model of 64 small experts, the short calls, and
# the widths the other three expert cells run: Mellum2's decode step, its
# shortest bucket and a 4096 pass on 64 experts; SDAR's block step, its
# opening pass and a prefill wave on 128; Kimi's share of a 4096 pass on 12
MIXTRAL_GU, MIXTRAL_DN = (4096, 28672), (14336, 4096)
MELLUM_GU, MELLUM_DN = (2304, 1792), (896, 2304)
SDAR_GU, SDAR_DN = (2048, 1536), (768, 2048)
KIMI_GU, KIMI_DN = (7168, 4096), (2048, 7168)
TILE_CASES = [
    *((m, 8, *kn, tm) for kn in (MIXTRAL_GU, MIXTRAL_DN)
      for m, tm in ((64, 128), (256, 128), (512, 128), (1024, 256),
                    (2048, 256), (4096, 256))),
    *((m, 64, 2048, 2048, tm) for m, tm in (
        (64, 128), (128, 128), (256, 128), (4096, 128), (8192, 256))),
    *((m, 64, *kn, tm) for kn in (MELLUM_GU, MELLUM_DN)
      for m, tm in ((512, 128), (2048, 128), (32768, 256))),
    *((m, 128, *kn, tm) for kn in (SDAR_GU, SDAR_DN)
      for m, tm in ((2048, 128), (4096, 128), (16384, 256))),
    *((1024, 12, *kn, 256) for kn in (KIMI_GU, KIMI_DN)),
]
# what the fit gives the widths that `tk` 1024 / `tn` 8 * tm do not divide
FITTED = {
    (128, *MELLUM_GU): (2304, 896), (256, *MELLUM_GU): (2304, 896),
    (128, *MELLUM_DN): (896, 2304), (256, *MELLUM_DN): (896, 2304),
    (128, *SDAR_GU): (2048, 1536), (256, *KIMI_DN): (2048, 1024),
}


@pytest.mark.parametrize("m, e, k, n, tm", TILE_CASES)
def test_grouped_matmul_tile_follows_the_shape(m, e, k, n, tm):
    """`tile_for`: a shape in, a tile out. It is the tile it was (`tk` 1024,
    `tn` 8 * tm: decode's kernel and every Mixtral call are unchanged)
    wherever that tile divides the weights, and one that divides them
    everywhere else (`tile_fit` 1.0: no visit multiplies columns the weights
    do not have, no k-tile is masked); the kernel's double-buffered blocks
    and accumulator fit the 16 MiB of VMEM it gets unasked; the backward's
    two products have tiles of their own that divide and fit as well."""
    from ray_tpu.ops import grouped_matmul as gm

    tile = gm.tile_for(m, e, k, n)
    assert tile[0] == tm == gm.row_tile(m, e)[0]
    was = (tm, min(1024, k), min(8 * tm, n))
    if k % was[1] == 0 and n % was[2] == 0:
        assert tile == was and (tm, k, n) not in FITTED
    else:
        assert gm.tile_fit(k, n, was) < 1.0
        assert tile[1:] == FITTED[tm, k, n]
    if (k, n) in (MIXTRAL_GU, MIXTRAL_DN):
        assert tile == (tm, 1024, 1024 if tm == 128 else 2048)
    if m <= 256:
        assert not gm.row_tile(m, e)[1]
    # groups start on tile boundaries where an expert's share of the call
    # is half the smallest tile or more, and the tile holds that share
    assert gm.row_tile(m, e)[1] == (m // e >= 64)
    assert tm >= min(m // e, 256)
    assert gm.tile_vmem_bytes(tile) <= 16 * 2 ** 20
    assert k % tile[1] == 0 and n % tile[2] == 0
    assert gm.tile_fit(k, n, tile) == 1.0
    assert all(t % 128 == 0 for t in tile)
    # d lhs contracts over N; tgmm's accumulator is a whole [tk, tn]
    d_lhs, d_rhs = gm._tile(tm, n, k), gm._tile(tm, k, n, gm.tgmm_vmem_bytes)
    assert gm.tile_fit(n, k, d_lhs) == 1.0 == gm.tile_fit(k, n, d_rhs)
    assert gm.tile_vmem_bytes(d_lhs) <= 16 * 2 ** 20
    assert gm.tgmm_vmem_bytes(d_rhs) <= 16 * 2 ** 20


def test_tile_fit_is_the_share_of_a_visit_the_weights_have():
    """`tile_fit` at the tiles the rule gave Mellum2's and SDAR's widths
    before it fitted them (the issue's table: a visit multiplied 1.52 /
    1.33 / 1.78 times the real K x N), and what an engine says of its
    model once (`moe_tile_kn_fill_pct`, from the shapes alone): 100 for
    all four expert configurations."""
    from ray_tpu.models import kimi, mellum, sdar
    from ray_tpu.models.llama import moe_tile_kn_fill_pct
    from ray_tpu.ops import grouped_matmul as gm

    for (k, n), tile, padded in (
            (MELLUM_GU, (128, 1024, 1024), 1.52),
            (MELLUM_GU, (256, 1024, 1792), 1.33),
            (MELLUM_DN, (128, 896, 1024), 1.33),
            (MELLUM_DN, (256, 896, 2048), 1.78),
            (SDAR_GU, (128, 1024, 1024), 1.33),
            (KIMI_DN, (256, 1024, 2048), 1.14),
            (MIXTRAL_GU, (256, 1024, 2048), 1.00)):
        assert round(1 / gm.tile_fit(k, n, tile), 2) == padded
    # (decode step's tokens, largest bucket) as the cells run them
    for cfg, passes in (
            (get_config("mixtral-8x7b"), (32, 2048)),
            (mellum.get_config("mellum2-12b-a2.5b"), (64, 4096)),
            (sdar.get_config("sdar-30b-a3b"), (64 * 4, 2048)),
            (kimi.get_config("kimi-k2.5", num_experts=12,
                             n_routed_experts=384), (24, 4096))):
        assert moe_tile_kn_fill_pct(passes, cfg) == 100.0


@pytest.mark.parametrize("seed", range(4))
def test_tile_visits_counts_what_the_kernel_multiplies(seed):
    """`tile_visits` against a count by brute force (every (tile, group)
    pair that shares a row) and against the kernel's own metadata
    (`make_group_metadata`: the grid's middle axis), over random group
    sizes with empty groups, the [L x E] stacked form and a tail in no
    group; leading axes are calls of their own."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)
    from ray_tpu.ops.grouped_matmul import tile_visits

    rng = np.random.default_rng(seed)
    calls = []
    for tm in (128, 256, 512):
        m = tm * int(rng.integers(1, 9))
        sizes = rng.multinomial(int(m * rng.uniform(0.3, 1.0)),
                                rng.dirichlet(np.ones(8)))
        sizes[rng.integers(0, 8)] = 0
        stacked = np.zeros((24,), np.int64)
        stacked[8:16] = sizes
        owner = np.repeat(np.arange(24), stacked)       # row -> group
        brute = len({(row // tm, g) for row, g in enumerate(owner)})
        assert tile_visits(stacked, tm) == brute == tile_visits(sizes, tm)
        _, num_tiles = make_group_metadata(
            group_sizes=jnp.asarray(stacked, jnp.int32), m=m, tm=tm,
            start_group=jnp.int32(0), num_nonzero_groups=24,
            visit_empty_groups=False)
        assert int(num_tiles) == brute
        assert m // tm - 1 <= brute <= m // tm + 7
        calls.append((sizes, tm, brute))
    tm = 128
    both = np.stack([c[0] for c in calls[:2]])
    assert tile_visits(both, tm) == sum(
        tile_visits(c[0], tm) for c in calls[:2])


@pytest.mark.parametrize("impl", ["ragged_dot", "megablox_interpret"])
def test_dropless_layer_is_the_same_with_groups_on_tile_boundaries(
        monkeypatch, impl):
    """`MoEMLP._dropless` where `row_tile` asks for aligned groups (160
    tokens x 2 on 4 experts: every expert's rows start on a 128-row tile,
    padded with repeated rows) against the same layer with its rows packed:
    the output and the gradients to the input and to every weight, with a
    third of the tokens masked out as a length bucket's padding is."""
    from ray_tpu.models.llama import MoEMLP, moe_row_layout
    from ray_tpu.ops import grouped_matmul as gm

    cfg = get_config("tiny-moe", dtype=jnp.float32, param_dtype=jnp.float32)
    tokens = 160
    assert moe_row_layout(tokens, cfg) == (128, True, 384 + 4 * 128, 896)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, tokens, cfg.hidden_size)),
                    jnp.float32)
    mask = jnp.asarray(np.arange(tokens) < 107)[None]
    layer = MoEMLP(cfg)
    params = nn.meta.unbox(
        jitted(layer.init)(jax.random.PRNGKey(1), x)["params"])
    monkeypatch.setattr(gm, "_impl", lambda: impl)

    def loss(params, x):
        out = layer.apply({"params": params}, x, token_mask=mask)
        return (out ** 2).sum(), out

    def run():
        with jax.default_matmul_precision("highest"):
            return jitted(jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True))(params, x)

    (_, out), grads = run()
    monkeypatch.setattr(gm, "row_tile", lambda m, e: (128, False))
    assert moe_row_layout(tokens, cfg) == (128, False, 320, 320)
    (_, packed), g_packed = run()
    assert not np.asarray(out[0, 107:]).any()
    np.testing.assert_allclose(out, packed, atol=1e-5, rtol=1e-5)
    for got, want in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(g_packed)):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


# the m-tile the rule gives the call -> its rows and groups (uneven, one
# empty, ends inside tiles, and a tail of rows in no group) and [K, N]; and
# two pairs of widths the rule fits a tile to, none a power of two.
# "mellum-gate-up": the forward's is (128, 2304, 896), two n-tiles; d lhs's
# (128, 1792, 1152); tgmm's (128, 768, 1792), three k-steps.
# "k-steps": (128, 2304, 896) over K 4608, two k-steps a visit; d lhs's
# (128, 896, 2304), two n-tiles; tgmm's (128, 1536, 896)
KERNEL_CASES = {128: (300, [70, 0, 130, 50], 256, 384),
                256: (700, [300, 0, 45, 255], 256, 384),
                "mellum-gate-up": (300, [70, 0, 130, 50], 2304, 1792),
                "k-steps": (300, [70, 0, 130, 50], 4608, 896)}
KERNEL_TILES = {
    "mellum-gate-up": ((2304, 896), (1792, 1152), (768, 1792)),
    "k-steps": ((2304, 896), (896, 2304), (1536, 896))}


@pytest.mark.parametrize("stacked", [False, True], ids=["experts", "stack"])
@pytest.mark.parametrize("tm", list(KERNEL_CASES))
def test_grouped_matmul_kernel_matches_plain_path_forward_and_backward(
        monkeypatch, tm, stacked):
    """The Pallas grouped matmul (interpret mode here; the compiled kernel
    on a TPU) against `jax.lax.ragged_dot`, which the CPU tests above run,
    at every m-tile the rule returns and at widths it fits a tile to:
    uneven groups that cross tile boundaries, an empty one and a tail of
    rows in no group (undefined out of the kernel, with a zero gradient);
    the experts alone or a whole [L, E, K, N] stack read by layer; and the
    gradients the one-chip trainer takes through the kernel's own backward
    (gmm and tgmm, each at the tile of its own widths)."""
    from ray_tpu.ops import grouped_matmul as gm

    rng = np.random.default_rng(8)
    (m, sizes, kdim, n), experts = KERNEL_CASES[tm], 4
    if tm in KERNEL_TILES:
        tiles, tm = KERNEL_TILES[tm], 128
        assert tiles == tuple(t[1:] for t in (
            gm.tile_for(m, experts, kdim, n), gm._tile(tm, n, kdim),
            gm._tile(tm, kdim, n, gm.tgmm_vmem_bytes)))
    assert gm.tile_for(m, experts, kdim, n)[0] == tm
    lhs = jnp.asarray(rng.normal(size=(m, kdim)), jnp.float32)
    stack = jnp.asarray(rng.normal(size=(2, experts, kdim, n)), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    real = int(sizes.sum())                                 # rows over
    assert real < m and gm.tile_visits(sizes, tm) > -(-real // tm)

    def loss(lhs, rhs, layer, impl):
        monkeypatch.setattr(gm, "_impl", lambda: impl)
        out = gm.grouped_matmul(lhs, rhs, sizes, layer)[:real]
        return (out ** 2).sum(), out

    rhs, layer = (stack, jnp.int32(1)) if stacked else (stack[1], None)
    with jax.default_matmul_precision("highest"):
        both = jitted(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))
        (_, plain), g_plain = both(lhs, stack[1], None, "ragged_dot")
        (_, out), grads = both(lhs, rhs, layer, "megablox_interpret")

    def close(got, want, atol, rtol=0):
        # float32's summation order, at the size the widths give the sums
        # (outputs reach 70 and gradients 1e3 at K 256, three times that
        # and more at 2304)
        np.testing.assert_allclose(
            got, want, rtol=rtol, atol=atol * float(np.abs(want).max()))

    close(out, plain, 1.5e-6)
    assert not np.asarray(grads[0][real:]).any()
    d_rhs = grads[1][1] if stacked else grads[1]
    close(grads[0], g_plain[0], 3e-6, rtol=1e-5)
    close(d_rhs, g_plain[1], 3e-6, rtol=1e-5)
    if stacked:                 # the other layer's experts: untouched
        assert not np.asarray(grads[1][0]).any()


# ---- the dropless layer's row layout and its cut into calls (PR 49) ----

# the four expert configurations' (E, k, R) at tiny widths
LAYOUT_CONFIGS = {"mixtral": (8, 2, 8), "mellum": (64, 8, 64),
                  "sdar": (128, 8, 128), "kimi-share": (12, 8, 384)}
ROUTINGS = ("uniform", "empty-experts", "one-takes-all", "trailing-group",
            "padded-tail")


def _routed(routing, E, k, R, tokens, seed=0):
    """[tokens * k] int: the group each assignment joins (E: the trailing
    one, a share's absent experts and a `token_mask`'s padding)."""
    rng = np.random.default_rng(seed)
    if routing == "one-takes-all":
        chosen = np.full((tokens, k), 3 % E)
    elif routing == "empty-experts":
        # every second expert gets nothing, and the last none
        chosen = 2 * rng.integers(0, max((E - 1) // 2, 1), (tokens, k))
    else:
        # k distinct experts of the R routed a token, this share's first
        chosen = np.stack([rng.permutation(R)[:k] for _ in range(tokens)])
    expert = np.where(chosen < E, chosen, E).reshape(-1)
    if routing == "trailing-group":
        # most assignments to an expert the share does not hold
        expert = np.where(rng.random(expert.shape) < 0.7, E, expert)
    if routing == "padded-tail":
        expert[(tokens * 5 // 8) * k:] = E
    return expert.astype(np.int32)


def _plain_layout(expert, E, tm, aligned, rows):
    """The layout as the layer computed it before PR 49, in numpy: a
    scatter-add for the counts, a search a row for its owner, a gather a
    row, a gather an assignment for its shift."""
    M = len(expert)
    order = np.argsort(expert, kind="stable")
    counts = np.zeros(E + 1, np.int64)
    np.add.at(counts, expert, 1)
    counts = counts[:E]
    row_of = np.argsort(order)
    if not aligned:
        return (counts, np.cumsum(counts),
                order[np.clip(np.arange(rows), 0, M - 1)], row_of)
    sizes = -(-counts // tm) * tm
    shift = np.concatenate([[0], np.cumsum(sizes - counts)])
    ends = np.cumsum(sizes)
    owner = np.searchsorted(ends, np.arange(rows), side="right")
    at = order[np.clip(np.arange(rows) - shift[owner], 0, M - 1)]
    return counts, ends, at, row_of + shift[expert]


@pytest.mark.parametrize("aligned", [False, True], ids=["packed", "aligned"])
@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("config", list(LAYOUT_CONFIGS))
def test_layout_arrays_hold_what_a_search_a_row_gave(config, routing,
                                                     aligned):
    """`dropless_layout` (counts from a compare, the aligned rows from a
    table a tile) against the plain formulation it replaced, array by
    array: the four configurations' experts, top-k and share, every
    routing a table could get wrong, rows packed and on tile boundaries,
    in one call's rows and in the rows of a pass cut into blocks."""
    from ray_tpu.models.llama import dropless_layout
    from ray_tpu.ops.grouped_matmul import aligned_rows

    E, k, R = LAYOUT_CONFIGS[config]
    tokens, tm = 48, 128 if E > 8 else 256
    expert = _routed(routing, E, k, R, tokens)
    M = tokens * k
    need = aligned_rows(M, E, tm) if aligned else M
    # a pass cut into blocks has more rows than its layout needs
    for rows in (need, -(-need // (3 * tm)) * 3 * tm)[:1 + aligned]:
        got = jax.jit(dropless_layout, static_argnums=(1, 2, 3, 4))(
            jnp.asarray(expert), E, tm, aligned, rows)
        want = _plain_layout(expert, E, tm, aligned, rows)
        for name, a, b in zip(("counts", "ends", "at", "row_of"), got,
                              want):
            np.testing.assert_array_equal(np.asarray(a), b, err_msg=name)
        counts, ends, at, row_of = (np.asarray(a) for a in got)
        # every real assignment is read back from a row that holds it
        real = expert < E
        assert (at[row_of[real]] == np.arange(M)[real]).all()
        assert ends[-1] <= rows and counts.sum() == real.sum()


def _layer_out(cfg, x, mask, params=None, seed=1):
    """(output, counts, params) of one expert layer as a serving program
    runs it: its experts read by index from a stack (of two layers, this
    one the second)."""
    from ray_tpu.models.llama import MoEMLP

    layer = MoEMLP(cfg)
    if params is None:
        params = nn.meta.unbox(
            jitted(layer.init)(jax.random.PRNGKey(seed), x)["params"])

    def stack(w):
        return jnp.stack([jnp.zeros_like(w), w])

    def apply(p, x):
        return layer.apply(
            {"params": p}, x, token_mask=mask, mutable=["routing"],
            stacked=(stack(p["experts_gate_up"]), stack(p["experts_down"]),
                     jnp.int32(1)))
    out, sown = jax.jit(apply)(params, x)
    return np.asarray(out), np.asarray(jax.tree.leaves(sown)[0]), params


@pytest.mark.parametrize("impl", ["ragged_dot", "megablox_interpret"])
@pytest.mark.parametrize("case", ["empty-tail", "no-tail", "share"])
def test_loop_over_blocks_is_one_call(monkeypatch, case, impl):
    """A pass cut into blocks (`_MOE_ROWS` set so that its rows are 3 or
    more calls' worth) against the same pass in ONE call: the same output
    and counts, bit for bit on the plain path. `empty-tail`: two thirds of
    the tokens are padding, so the last blocks hold no group and are
    skipped; `no-tail`: every token real, every block visited; `share`: a
    layer that holds 4 of 16 routed experts, whose rows hold every
    assignment and whose blocks past the held experts' are skipped."""
    from ray_tpu.models import llama
    from ray_tpu.ops import grouped_matmul as gm

    share = case == "share"
    cfg = get_config(
        "tiny-moe", dtype=jnp.float32, param_dtype=jnp.float32,
        moe_intermediate_size=32, num_experts_per_tok=4 if share else 2,
        n_routed_experts=16 if share else None, expert_first=8)
    # `no-tail`: 1120 assignments, 257-512 an expert, fill 8 of the 9
    # tiles the layout has for the worst routing: all 3 blocks of 3
    tokens, tiles = {"empty-tail": (160, 1), "no-tail": (560, 3),
                     "share": (512, 2)}[case]
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(1, tokens, cfg.hidden_size)), jnp.float32)
    real = tokens if case == "no-tail" else tokens // 3
    mask = jnp.asarray(np.arange(tokens) < real)[None]
    monkeypatch.setattr(gm, "_impl", lambda: impl)
    tm, aligned, rows, block = llama.moe_row_layout(tokens, cfg)
    assert aligned and block == rows
    whole, counts, params = _layer_out(cfg, x, mask)
    monkeypatch.setattr(llama, "_MOE_ROWS", tiles * tm)
    cut = llama.moe_row_layout(tokens, cfg)
    assert cut[:2] == (tm, True) and cut[2] // cut[3] >= 3, cut
    calls, handed = llama.moe_gmm_calls(counts, tokens, cfg)
    assert (calls < cut[2] // cut[3]) == (case != "no-tail")
    assert handed == calls * cut[3]
    blocks, counts_cut, _ = _layer_out(cfg, x, mask, params)
    np.testing.assert_array_equal(counts_cut, counts)
    assert not blocks[0, real:].any()
    if impl == "ragged_dot":
        np.testing.assert_array_equal(blocks, whole)
    else:
        np.testing.assert_allclose(blocks, whole, atol=1e-6, rtol=1e-6)


def _brute_calls(counts, tokens, cfg):
    """(calls of one product, rows handed) of ONE pass, row by row: lay the
    groups out, and count the blocks that hold a row of a group."""
    from ray_tpu.models.llama import moe_row_layout

    tm, aligned, rows, block = moe_row_layout(tokens, cfg)
    owners = []
    for e, c in enumerate(counts):
        owners += [e] * int(-(-c // tm) * tm if aligned else c)
    assert len(owners) <= rows
    calls = 1 if block == rows else len(
        {p // block for p in range(len(owners))})
    return calls, calls * block


@pytest.mark.parametrize("config", list(LAYOUT_CONFIGS))
def test_gmm_calls_and_layout_rows_are_a_brute_force_count(monkeypatch,
                                                           config):
    """`moe_gmm_calls` (host arithmetic on [steps, L, E] counts) against a
    walk over every row of every pass's layout, at each configuration's
    experts, for passes in one call and passes cut into blocks, full and
    mostly padding."""
    from ray_tpu.models import llama

    E, k, R = LAYOUT_CONFIGS[config]
    cfg = get_config("tiny-moe", num_experts=E, num_experts_per_tok=k,
                     n_routed_experts=R if R != E else None,
                     moe_intermediate_size=32)
    rng = np.random.default_rng(11)
    for block in (llama._MOE_ROWS, 512):
        monkeypatch.setattr(llama, "_MOE_ROWS", block)
        for tokens in (4, 64, 512):
            counts = np.stack([np.bincount(
                _routed(routing, E, k, R, tokens, seed)[
                    :int(tokens * k * fill)], minlength=E + 1)[:E]
                for seed, (routing, fill) in enumerate(
                    (r, f) for r in ROUTINGS[:4] for f in (1.0, 0.3))])
            counts = counts.reshape(2, 4, E)            # [steps, L, E]
            brute = [_brute_calls(c, tokens, cfg)
                     for c in counts.reshape(-1, E)]
            assert llama.moe_gmm_calls(counts, tokens, cfg) == (
                sum(b[0] for b in brute), sum(b[1] for b in brute))
    blocked = llama.moe_row_layout(512, cfg)
    assert blocked[3] < blocked[2]          # the loop above cut a pass


def test_moe_engine_counts_the_calls_of_a_pass_cut_into_blocks(monkeypatch):
    """The engine with `_MOE_ROWS` set so that the 128 bucket's pass
    (768 rows) is three calls' worth: a 100-token prompt through
    add_request/step() gives the reference's greedy tokens through the
    loop over blocks, and `stats()`'s `moe_gmm_calls_total` /
    `moe_layout_rows_total` are a brute-force count over the records (the
    reference's routing laid out row by row), exported as `rtpu_llm_*`."""
    from chipbench.references import moe_decoder as ref
    from ray_tpu.models import llama
    from ray_tpu.serve.llm.engine import SamplingParams
    from ray_tpu.serve.llm.server import EngineDriverMixin
    from ray_tpu.util import metrics, tracing

    monkeypatch.setattr(llama, "_MOE_ROWS", 256)
    engine = _tiny_engine(twin="_MOE_ROWS=256")   # traced under the patch
    cfg = engine.model_cfg
    assert llama.moe_row_layout(128, cfg) == (128, True, 768, 256)
    k, L, E = cfg.num_experts_per_tok, cfg.num_layers, cfg.num_experts
    seen = tracing.appended("engine.dispatch")
    prompt = np.random.default_rng(9).integers(0, 256, 100).tolist()
    engine.add_request("long", prompt, SamplingParams(max_tokens=4))
    out = []
    while engine.has_work():
        for delta in engine.step():
            out.extend(delta.new_token_ids)
    weights = ref.weights_from_program_tree(engine.params)
    seq = list(prompt)
    for _ in range(4):
        logits = np.asarray(ref.forward(weights, jnp.asarray([seq]),
                                        _published(cfg)))
        seq.append(int(logits[0, -1].argmax()))
    assert out == seq[len(prompt):]
    chosen = np.asarray(ref.routing(weights, jnp.asarray([seq]),
                                    _published(cfg)))[0]      # [L, S, k]
    calls = rows = 0
    fields = tracing.FIELDS["engine.dispatch"]
    records = [rec for rec in (
        dict(zip(fields, r))
        for r in tracing.records("engine.dispatch", seen)) if rec["rows"]]
    for rec in records:
        decode = rec["kind"] == "decode"
        (_, q, ctx), = rec["rows"]
        for j in range(rec["k"] if decode else 1):
            at = (slice(ctx - 1 + j, ctx + j) if decode
                  else slice(ctx - q, ctx))
            for layer in range(L):
                c, r = _brute_calls(
                    np.bincount(chosen[layer, at].ravel(), minlength=E),
                    rec["rows_padded"] if decode
                    else rec["tokens_padded"] // rec["rows_padded"], cfg)
                calls, rows = calls + c, rows + r
    assert {r["kind"] for r in records} == {"prefill", "decode"}
    stats = engine.stats()
    assert stats["moe_gmm_calls_total"] == calls
    assert stats["moe_layout_rows_total"] == rows
    # the pass of 100 tokens: 200 assignments on 4 experts in tiles of 128,
    # so two or three of its three blocks held rows, in each layer
    prefill_calls = calls - L * sum(
        r["k"] for r in records if r["kind"] == "decode")
    assert 2 * L <= prefill_calls <= 3 * L
    driver = EngineDriverMixin()
    driver.engine = engine
    driver._init_driver()
    before = metrics.snapshot("rtpu_llm_")
    driver._publish_llm_metrics(stats)
    after = metrics.snapshot("rtpu_llm_")
    for key in ("moe_gmm_calls_total", "moe_layout_rows_total"):
        name = f"rtpu_llm_{key}"
        assert after[name] - before.get(name, 0) == stats[key], name
    engine.close()
