"""Kimi-K2.5's family (models/kimi.py) on the CPU at a tiny size: latent
attention in its two forms, one chip's share of sigmoid-routed experts,
YaRN's rotation, and the engine's normal path, each against the plain
reference of the benchmark (chipbench/references/mla_moe_decoder.py) or
against the same mathematics written another way. Logits, not tokens,
wherever a number can be compared.
"""

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import mla_moe_decoder as ref
from ray_tpu.models import kimi
from ray_tpu.models.llama import LlamaConfig, MoEMLP
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.rotary import yarn_inv_freq, yarn_mscale
from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.util import tracing

from _engines import (applied, fresh_params, jitted, new_engine, scarce,
                      tiny_engine)

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
CFG = dict(model="tiny-kimi", dtype="float32", page_size=16, num_pages=64,
           max_model_len=256, max_batch=4, prefill_buckets=(32, 64))
# the tiny preset as the reference reads a configuration
PUB = dict(num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=16,
           v_head_dim=16, kv_lora_rank=32, q_lora_rank=32, rms_norm_eps=1e-5,
           rope_theta=50000.0, num_experts_per_tok=4,
           routed_scaling_factor=2.827, norm_topk_prob=True, expert_first=4,
           rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                             mscale_all_dim=1, type="yarn",
                             original_max_position_embeddings=64))


def _seeded(params, seed=2):
    """Norm scales off one, a bias that changes choices, a router whose
    scores spread: a reference that forgot one of them would disagree."""
    key = jax.random.PRNGKey(seed)

    def one(path, a):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        k = jax.random.fold_in(key, sum(map(ord, name)))
        if name.endswith("scale"):
            return 1 + 0.1 * jax.random.normal(k, a.shape)
        if name.endswith("router_bias"):
            return 0.05 * jax.random.normal(k, a.shape)
        if name.endswith("router"):
            return a * 20
        return a

    return jax.tree_util.tree_map_with_path(one, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = kimi.get_config("tiny-kimi", **F32)
    model = kimi.KimiModel(cfg)
    params = fresh_params(model, 1, _seeded)
    return cfg, model, params


def _ids(shape, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, 256)


@jitted
def _reference(params, ids):
    return ref.forward(ref.weights_from_program_tree(params), ids, PUB)


MP = 12     # block-table columns of the tests' own pool: 192 tokens


@functools.lru_cache(maxsize=None)
def _step_fn(model, cfg, ctx_pages: int):
    """One pass of the model through a LatentCache, jitted a shape."""
    def fn(params, pool, bt, total, ids, positions):
        cache = kimi.serving_cache(cfg, pool, bt, total, ctx_pages=ctx_pages)
        logits, new = model.apply({"params": params}, ids,
                                  positions=positions, kv_caches=cache)
        return logits[0], new.pool

    return jax.jit(fn)


def _paged(cfg, model, params, seq, passes, decode=0, pool=None, page=16):
    """Prefill `seq` in `passes` (lengths), then `decode` more tokens one
    at a time (teacher-forced from `seq`'s tail), through a LatentCache:
    -> logits at every position [len, V], the pool."""
    if pool is None:
        shape, dtype = kimi.pool_spec(cfg, cfg.num_layers, 1 + MP, page, 1)
        pool = jnp.zeros(shape, dtype)
    bt = jnp.arange(1, 1 + MP, dtype=jnp.int32)[None]
    out, start = [], 0
    steps = [(n, True) for n in passes] + [(1, False)] * decode
    for n, prefill in steps:
        logits, pool = _step_fn(model, cfg,
                                MP if (prefill and start) else 0)(
            params, pool, bt, jnp.asarray([start + n], jnp.int32),
            jnp.asarray(seq[start:start + n])[None],
            (start + jnp.arange(n))[None])
        out.append(logits)
        start += n
    return jnp.concatenate(out), pool


# ------------------------------------------------ (a) against the reference
def test_the_full_forward_is_the_references(tiny):
    _, model, params = tiny
    ids = _ids((2, 100))
    with jax.default_matmul_precision("highest"):
        got = applied(model, params, ids)
    want = _reference(params, ids)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert float(jnp.sqrt((want ** 2).mean())) > 0.3


def test_prefill_then_decode_through_a_cache_is_the_references(tiny):
    cfg, model, params = tiny
    seq = np.asarray(_ids((90,), seed=4))
    with jax.default_matmul_precision("highest"):
        got, _ = _paged(cfg, model, params, seq, (64,), decode=26)
    want = _reference(params, jnp.asarray(seq)[None])[0]
    assert float(jnp.abs(got - want).max()) < 1e-4


# ----------------------------- (b) absorbed decode = materialised attention
def test_absorbed_decode_is_materialised_attention_at_the_same_positions(
        tiny):
    """The last 8 positions once as decode steps (absorbed: the latent row
    is the key) and once as one prefill pass behind the same pages
    (materialised per-head keys and values): the same logits."""
    cfg, model, params = tiny
    seq = np.asarray(_ids((72,), seed=5))
    with jax.default_matmul_precision("highest"):
        dec, _ = _paged(cfg, model, params, seq, (64,), decode=8)
        pre, _ = _paged(cfg, model, params, seq, (64, 8))
    assert float(jnp.abs(dec[64:] - pre[64:]).max()) < 1e-4


# ------------- (c) one pass = resumed passes over several chunks = a prefix
@pytest.mark.parametrize("passes", [(32, 32, 32, 32), (64, 64), (96, 32)])
def test_one_pass_is_resumed_passes_over_several_context_chunks(tiny,
                                                                passes):
    """`ctx_chunk_tokens` is 32 at this size (two pages): the last pass of
    (32, 32, 32, 32) walks three chunks of the table's six, the rest run
    nothing."""
    cfg, model, params = tiny
    seq = np.asarray(_ids((128,), seed=6))
    with jax.default_matmul_precision("highest"):
        one, _ = _paged(cfg, model, params, seq, (128,))
        many, _ = _paged(cfg, model, params, seq, passes)
    assert float(jnp.abs(one - many).max()) < 1e-4
    assert len(pa.ctx_chunks(MP, 16, cfg.ctx_chunk_tokens)) == 6


def test_a_chunk_past_every_rows_context_runs_nothing(tiny):
    """The chunks are `cond`s on the row's context: the jaxpr of a resumed
    pass holds one a chunk, and a NaN-filled page past the context (what
    an unwritten page may hold) moves nothing."""
    cfg, model, params = tiny
    seq = np.asarray(_ids((80,), seed=7))
    _, pool = _paged(cfg, model, params, seq, (64,))
    dirty = pool.at[:, 6:].set(jnp.nan)       # pages 6.. hold no token yet
    bt = jnp.arange(1, 1 + MP, dtype=jnp.int32)[None]

    @jax.jit
    def resumed(pool):
        cache = kimi.serving_cache(cfg, pool, bt, jnp.asarray([80]),
                                   ctx_pages=MP)
        return applied(model, params, jnp.asarray(seq[64:])[None],
                       positions=(64 + jnp.arange(16))[None],
                       kv_caches=cache)[0]

    assert bool(jnp.isfinite(resumed(dirty)).all())
    assert float(jnp.abs(resumed(dirty) - resumed(pool)).max()) == 0.0
    text = str(jax.make_jaxpr(resumed)(pool))
    # one `cond` a chunk of the table, in each of the two runs' scan bodies
    assert text.count("cond[") >= 2 * 6


# ------------------------------------------------ (d) the shares add up
def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """16 routed experts over 4 shares of 4: the routed parts of the four
    shares, plus the shared expert once, are the layer that holds all 16
    (and the reference's, share by share)."""
    base = dict(hidden_size=32, intermediate_size=64, num_layers=1,
                num_heads=2, num_kv_heads=2, num_experts_per_tok=4,
                moe_intermediate_size=16, moe_scoring="sigmoid",
                routed_scaling_factor=2.827, n_shared_experts=1, **F32)
    whole_cfg = LlamaConfig(num_experts=16, **base)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    whole = MoEMLP(whole_cfg)
    wp = jax.tree.map(np.asarray, nn.meta.unbox(
        whole.init(jax.random.PRNGKey(1), x)["params"]))
    wp["router"] = wp["router"] * 30
    wp["router_bias"] = 0.05 * np.asarray(
        jax.random.normal(jax.random.PRNGKey(2), (16,)))
    want = applied(whole, wp, x)
    shared = jnp.asarray(
        jax.nn.silu(x @ wp["shared"]["gate_up_proj"]["kernel"][:, :16])
        * (x @ wp["shared"]["gate_up_proj"]["kernel"][:, 16:])
    ) @ wp["shared"]["down_proj"]["kernel"]
    total = 0
    for first in (0, 4, 8, 12):
        cfg = LlamaConfig(num_experts=4, n_routed_experts=16,
                          expert_first=first, **base)
        sp = dict(wp, experts_gate_up=wp["experts_gate_up"][first:first + 4],
                  experts_down=wp["experts_down"][first:first + 4])
        got, sown = MoEMLP(cfg).apply({"params": sp}, x,
                                      mutable=["routing"])
        total = total + (got - shared)
        # the reference's share, an expert at a time
        m = x.reshape(-1, 32)
        w = {"router": wp["router"], "router_bias": wp["router_bias"],
             "gate_up": sp["experts_gate_up"], "down": sp["experts_down"],
             "shared_gate_up": wp["shared"]["gate_up_proj"]["kernel"],
             "shared_down": wp["shared"]["down_proj"]["kernel"]}
        ref_share, chosen = ref._expert_layer(
            m, w, dict(PUB, expert_first=first), "float32")
        assert float(jnp.abs(got.reshape(-1, 32) - ref_share).max()) < 1e-5
        counts = jax.tree.leaves(sown["routing"])[0]
        assert counts.tolist() == chosen.sum(0).tolist()
    assert float(jnp.abs(total + shared - want).max()) < 1e-5


# ---------------------------------------------------------- (e) the router
def _share(**over):
    base = dict(hidden_size=32, intermediate_size=64, num_layers=1,
                num_heads=2, num_kv_heads=2, num_experts=4,
                n_routed_experts=16, expert_first=4, num_experts_per_tok=4,
                moe_intermediate_size=16, moe_scoring="sigmoid",
                routed_scaling_factor=2.827, n_shared_experts=0, **F32)
    base.update(over)
    cfg = LlamaConfig(**base)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 32))
    layer = MoEMLP(cfg)
    p = jax.tree.map(np.asarray, nn.meta.unbox(
        layer.init(jax.random.PRNGKey(4), x)["params"]))
    p["router"] = p["router"] * 30
    return cfg, layer, p, x


def test_the_bias_moves_a_choice_and_no_weight():
    cfg, layer, p, x = _share()
    m = x.reshape(-1, 32)
    w0, chosen0 = ref.route(m, p["router"], p["router_bias"], PUB)
    bias = np.zeros(16, np.float32)
    bias[5] = 10.0          # expert 5 (held) is chosen by every token now
    w1, chosen1 = ref.route(m, p["router"], bias, PUB)
    assert bool(chosen1[:, 5].all()) and not bool(chosen0[:, 5].all())
    # weights sum to the scale over the k chosen, bias or none
    for w in (w0, w1):
        assert np.allclose(np.asarray(w.sum(-1)), 2.827, rtol=1e-5)
    # a weight is the score's, never the bias's: where the choice is the
    # same set, the weights are the same
    same = np.asarray((chosen0 == chosen1).all(-1))
    assert np.allclose(np.asarray(w0)[same], np.asarray(w1)[same])
    _, sown = layer.apply({"params": dict(p, router_bias=bias)}, x,
                          mutable=["routing"])
    assert int(jax.tree.leaves(sown["routing"])[0][1]) == 40


def test_an_absent_expert_costs_no_row_and_padding_costs_none():
    cfg, layer, p, x = _share()
    _, chosen = ref.route(x.reshape(-1, 32), p["router"], p["router_bias"],
                          PUB)
    held = np.asarray(chosen)[:, 4:8]
    _, sown = applied(layer, p, x, mutable=["routing"])
    counts = np.asarray(jax.tree.leaves(sown["routing"])[0])
    assert counts.tolist() == held.sum(0).tolist()
    assert counts.sum() < 40 * 4          # the absent ones are not rows
    mask = jnp.arange(40)[None] < 25
    out, sown = applied(layer, p, x, mask, mutable=["routing"])
    counts = np.asarray(jax.tree.leaves(sown["routing"])[0])
    assert counts.tolist() == held[:25].sum(0).tolist()
    assert float(jnp.abs(out[0, 25:]).max()) == 0.0
    full = applied(layer, p, x)
    assert float(jnp.abs(out[0, :25] - full[0, :25]).max()) < 1e-6


def test_no_assignment_to_a_held_expert_is_dropped_whatever_the_routing():
    cfg, layer, p, x = _share()
    bias = np.full(16, -10.0, np.float32)
    bias[4:8] = 10.0        # every token chooses exactly the four held
    _, sown = layer.apply({"params": dict(p, router_bias=bias)}, x,
                          mutable=["routing"])
    assert np.asarray(jax.tree.leaves(sown["routing"])[0]).tolist() == [40] * 4


# ------------------------------------------------------------- (f) YaRN
def test_yarns_table_is_the_formula_and_the_scale_is_the_published_one():
    inv = yarn_inv_freq(64, 50000.0, 64.0, 4096, 32.0, 1.0)

    def dim(r):
        return 64 * math.log(4096 / (2 * math.pi * r)) / (
            2 * math.log(50000.0))

    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), 63)
    for i in range(32):
        f = 50000.0 ** (-2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        assert math.isclose(inv[i], f / 64 * ramp + f * (1 - ramp),
                            rel_tol=1e-6)
    assert inv[0] == 1.0 and math.isclose(inv[31] * 64,
                                          50000.0 ** (-62 / 64),
                                          rel_tol=1e-6)
    cfg = kimi.get_config("kimi-k2.5")
    assert np.allclose(cfg.rope_inv_freq, inv)
    m = yarn_mscale(64.0, 1.0)
    assert math.isclose(m, 1.41589, rel_tol=1e-5)
    assert math.isclose(cfg.softmax_scale, 0.14468, rel_tol=1e-4)
    # positions far past the original context turn by the table's angle
    from ray_tpu.ops.rotary import rotate

    x = jnp.ones((1, 4, 1, 64))
    pos = jnp.asarray([[0, 4095, 4096, 262143]])
    got = rotate(x, pos, inv)[0, :, 0]
    ang = np.asarray(pos[0], np.float64)[:, None] * np.asarray(inv,
                                                               np.float64)
    # a float32 angle of 262143 x 1.0 is exact; cos/sin to float32
    want = np.concatenate([np.cos(ang) - np.sin(ang),
                           np.cos(ang) + np.sin(ang)], -1)
    assert np.allclose(np.asarray(got), want, atol=2e-2)
    assert np.allclose(np.asarray(got[0]), 1.0)


# ------------------------------------------- (g) the kernels, interpreted
def test_the_latent_decode_kernel_in_interpret_mode():
    """Lanes 640 (576 real), values 512, 64 query heads on one key row:
    against the gather path, rows of several lengths, one inactive."""
    page, mp, b, h = 64, 6, 3, 64
    key = jax.random.PRNGKey(0)
    pool = jax.random.normal(key, (2, 1 + b * mp, 1, page, 640))
    pool = pool.at[..., 576:].set(0.0)
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, h, 576)) * 0.2
    bt = (1 + jnp.arange(b * mp, dtype=jnp.int32)).reshape(b, mp)
    lengths = jnp.asarray([300, 0, 65], jnp.int32)
    want = pa.latent_attention_reference(q, pool, bt, lengths, v_width=512,
                                         scale=0.14, layer=1)
    got = pa.latent_attention_decode(q, pool, bt, lengths, v_width=512,
                                     scale=0.14, layer=1, interpret=True,
                                     pages_per_chunk=2)
    assert got.shape == (b, h, 512)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(got[1]).max()) == 0.0
    with pytest.raises(ValueError, match="a latent pool"):
        pa.latent_attention_decode(q[..., :512], pool, bt, lengths,
                                   v_width=512, scale=0.14, layer=1)


@pytest.mark.parametrize("causal", [True, False])
def test_the_flash_forward_at_keys_wider_than_values(causal):
    """d_qk 192, d_v 128, a row's true lengths: interpret mode against
    the jnp path."""
    b, sq, sk, h = 2, 128, 128 if causal else 256, 4
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (b, sq, h, 192)) * 0.3
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, sk, h, 192)) * 0.3
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, sk, h, 128))
    q_lens = jnp.asarray([100, 128])
    kv_lens = None if causal else jnp.asarray([130, 256])
    o1, l1 = pa._attn_lse(q, k, v, causal=causal, scale=0.1, q_lens=q_lens,
                          kv_lens=kv_lens, impl="flash")
    o2, l2 = pa._attn_lse(q, k, v, causal=causal, scale=0.1, q_lens=q_lens,
                          kv_lens=kv_lens, impl="reference")
    assert o1.shape == (b, sq, h, 128)
    real = (jnp.arange(sq)[None] < q_lens[:, None])[..., None, None]
    assert float(jnp.abs(jnp.where(real, o1 - o2, 0)).max()) < 2e-5
    assert float(jnp.abs(jnp.where(real[..., 0], l1 - l2, 0)).max()) < 2e-5


def test_values_narrower_than_keys_are_the_forward_only_paths():
    from ray_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 128, 2, 192))
    with pytest.raises(ValueError, match="forward-only"):
        flash_attention(q, q, jnp.zeros((1, 128, 2, 128)), interpret=True)


# ----------------------------------------------------- (h) through the engine
def _run(engine):
    out = {}
    while engine.has_work():
        for d in engine.step():
            out.setdefault(d.request_id, []).extend(d.new_token_ids)
    return out


def _judge(engine, prompt, tokens, tie=1e-3):
    """Greedy tokens against the reference's argmax on the engine's own
    sequence, where its top two logits are not near-tied."""
    seq = list(prompt) + list(tokens[:-1])
    logits = np.asarray(_reference(engine.params, jnp.asarray([seq]))[0])
    judged = 0
    for j, tok in enumerate(tokens):
        row = logits[len(prompt) - 1 + j]
        top = np.sort(row)[-2:]
        if top[1] - top[0] > tie:
            assert int(row.argmax()) == tok, (j, tok)
            judged += 1
    return judged


@pytest.fixture(scope="module")
def engine():
    return tiny_engine(**CFG)


def _prompts(lens, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lens]


def test_the_engine_emits_the_references_tokens_and_its_records_say_how(
        engine):
    tracing.reset_ring()
    prompts = _prompts((20, 70, 130), 0)
    for i, p in enumerate(prompts):
        engine.add_request(f"r{i}", p, SamplingParams(max_tokens=8))
    got = _run(engine)
    assert sum(_judge(engine, p, got[f"r{i}"])
               for i, p in enumerate(prompts)) >= 20
    fields = tracing.FIELDS["engine.dispatch"]
    recs = [dict(zip(fields, r)) for r in tracing.records("engine.dispatch")]
    assert all(len(r) == len(fields)
               for r in tracing.records("engine.dispatch"))
    cfg = engine.model_cfg
    # the family's four sit behind every other family's, before the stamps
    assert fields.index("enqueued_ns") == fields.index(
        "moe_assignments_routed") + 1 == fields.index("mla_layers") + 4
    for r in recs:
        assert r["mla_layers"] == 3
        assert r["block_passes"] is None and r["ssm_layers"] is None
        assert r["enqueued_ns"] <= r["device_start_ns"] <= r["device_end_ns"]
        assert r["latent_bytes_token"] == 3 * 128 * 4     # float32 here
        real = sum(q for _, q, _ in r["rows"])
        assert r["moe_assignments_routed"] == real * 4 * cfg.n_expert_layers
        assert 0 <= r["moe_assignments"] <= r["moe_assignments_routed"]
        assert r["moe_experts_touched"] <= cfg.n_expert_layers * 4 * r["k"]
        if r["kind"] == "prefill":
            # a chunk is 32 tokens: a row that resumes behind c tokens
            # materialised ceil(c / 32) of them
            assert r["mla_ctx_chunks"] == tuple(
                -(-(end - q) // 32) for _, q, end in r["rows"])
        else:
            assert r["mla_ctx_chunks"] is None
    assert any(r["mla_ctx_chunks"] and max(r["mla_ctx_chunks"]) >= 2
               for r in recs if r["kind"] == "prefill")
    st = engine.stats()
    assert st["mla_prefill_ctx_chunks_total"] == sum(
        sum(r["mla_ctx_chunks"]) for r in recs if r["kind"] == "prefill")
    assert st["mla_prefill_ctx_tokens_materialised_total"] == 32 * st[
        "mla_prefill_ctx_chunks_total"]
    assert st["moe_assignments_routed_total"] == sum(
        r["moe_assignments_routed"] for r in recs)
    assert st["mla_decode_ctx_tokens_total"] == sum(
        c for r in recs if r["kind"] == "decode" for _, _, c in r["rows"])
    assert st["latent_pool_bytes"] == 3 * 64 * 16 * 128 * 4
    assert st["attention"]["decode"].startswith("reference")
    # the share the held experts got: 4 of 16 under even routing
    assert 0.1 < st["moe_assignments_total"] / st[
        "moe_assignments_routed_total"] < 0.45


def test_a_prefix_hit_and_a_preemption_that_refills_keep_the_tokens():
    with scarce(tiny_engine(**CFG), 8) as eng:
        shared = _prompts((48,), 11)[0]
        tails = _prompts((5, 7), 12)
        for i, tail in enumerate(tails):
            eng.add_request(f"p{i}", shared + tail,
                            SamplingParams(max_tokens=6))
            got = _run(eng)[f"p{i}"]
            assert _judge(eng, shared + tail, got) >= 4
        assert eng.stats()["prefix_token_hits"] == 48      # three pages of 16
        assert "prefix_reuse_refused_total" not in eng.stats()
        prompts = _prompts((30, 33), 5)
        for i, p in enumerate(prompts):
            eng.add_request(f"q{i}", p, SamplingParams(max_tokens=50))
        got = _run(eng)
        assert eng.stats()["preempted_total"] >= 1
        for i, p in enumerate(prompts):
            assert len(got[f"q{i}"]) == 50
            assert _judge(eng, p, got[f"q{i}"]) >= 35


def test_chunked_prefill_is_resumed_passes_too():
    eng = tiny_engine(**{**CFG, "prefill_chunk_tokens": 32})
    prompt = _prompts((100,), 9)[0]
    eng.add_request("c", prompt, SamplingParams(max_tokens=6))
    got = _run(eng)["c"]
    assert eng.stats()["prefill_resumed_passes_total"] >= 2
    assert _judge(eng, prompt, got) >= 4


def test_no_program_is_built_under_traffic_after_warmup():
    """(An engine of its own: what a first use builds is the claim.)"""
    eng = new_engine(**CFG)
    n = eng.warmup()
    assert n == 2 * 2 + 1
    assert set(eng.compute.programs) == {
        ("prefill", sb, eng._wave_rb, cp) for sb in (32, 64)
        for cp in (0, eng.max_pages_per_seq)} | {
            ("decode",) + eng._decode_shape_key()}
    tracing.reset_ring()
    for round_ in range(2):             # the second round hits the prefix
        for i, p in enumerate(_prompts((20, 70, 130, 33), 21)):
            eng.add_request(f"w{round_}{i}", p, SamplingParams(max_tokens=4))
        _run(eng)
    assert eng.stats()["prefix_token_hits"] > 0
    assert not tracing.records("engine.program_built")
    assert eng.stats()["programs_built_total"] == n
    eng.close()


def test_the_family_is_found_by_its_presets_and_serves_through_openai():
    from ray_tpu.serve.llm.stage import model_family

    assert model_family("kimi-k2.5") is kimi
    assert model_family("tiny-kimi") is kimi
    full = kimi.get_config("kimi-k2.5")
    # 1.026 T parameters whole; 4.173 B in the benchmark's cut
    assert abs(full.num_params() / 1.026e12 - 1) < 0.005
    cut = kimi.get_config("kimi-k2.5", num_layers=6, num_experts=12,
                          n_routed_experts=384, vocab_size=20480)
    assert abs(cut.num_params() / 4.173e9 - 1) < 0.005
    assert cut.latent_lanes == 640 and cut.latent_width == 576
    assert kimi.pool_spec(cut, 6, 8192, 64, 24)[0] == (6, 8192, 1, 64, 640)
    weights, pair = kimi.pass_cost_ratios(cut)
    assert 2.5 < weights < 3.5 and pair > 0


# ------------------------------------------------------ (i) each refusal
@pytest.mark.parametrize("over, what", [
    (dict(tp=2), "tensor parallelism"),
    (dict(pp=3), "pipeline parallelism"),
    (dict(spec_lookahead=4), "spec_lookahead=4")])
def test_what_a_latent_pool_cannot_be_given_is_refused_by_name(over, what):
    with pytest.raises(NotImplementedError, match=what) as e:
        LLMEngine(EngineConfig(**{**CFG, **over}))
    assert "one latent row a token for all heads" in str(e.value)


def test_a_slice_of_the_stack_is_refused():
    with pytest.raises(NotImplementedError, match="a dense run"):
        kimi.serving_model(kimi.get_config("tiny-kimi"), 1, True, False)
