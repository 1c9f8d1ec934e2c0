"""GigaChat3.5's family (models/gigachat.py) on the CPU at a tiny size:
gated-delta-net layers beside latent attention in one stack, three kinds of
state in one pool, one chip's share of sigmoid-routed experts with clamped
FFNs, and the engine's normal path, each against the plain reference of the
benchmark (chipbench/references/gdn_mla_moe_decoder.py). Logits, not
tokens, wherever a number can be compared.
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import gdn_mla_moe_decoder as ref
from ray_tpu.models import gigachat
from ray_tpu.models.llama import LlamaConfig, MoEMLP
from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.serve.llm.stage import model_family
from ray_tpu.util import tracing

from _engines import (applied, fresh_params, jitted, new_engine, scarce,
                      tiny_engine)

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
CFG = dict(model="tiny-gigachat", dtype="float32", page_size=16,
           num_pages=64, max_model_len=256, max_batch=4,
           prefill_buckets=(32, 64))
# the tiny preset as the reference reads a configuration
PUB = dict(num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=16,
           v_head_dim=16, kv_lora_rank=32, q_lora_rank=32, rms_norm_eps=1e-6,
           rope_theta=100000.0, num_experts_per_tok=4,
           routed_scaling_factor=2.5, norm_topk_prob=True, expert_first=4,
           linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_conv_kernel_dim=4,
           linear_attn_o_norm_eps=1e-6, linear_sigmoid_gate_scale=2.0,
           layernorm_gating_weight=2.0, swiglu_limit=10.0,
           rope_scaling=dict(beta_fast=32, beta_slow=1, factor=8, mscale=1,
                             mscale_all_dim=1, type="yarn",
                             original_max_position_embeddings=64))


def _seeded(params, seed=2):
    """Norm weights off their centre, a bias that changes choices, a router
    whose scores spread, decays and gates off their defaults, FFN inputs
    large enough for the clamp to bite: a reference that forgot one of them
    would disagree."""
    key = jax.random.PRNGKey(seed)

    def one(path, a):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        k = jax.random.fold_in(key, sum(map(ord, name)))
        if name.endswith("scale"):
            return 1 + 0.1 * jax.random.normal(k, a.shape)
        if name.endswith(("zc_weight", "o_norm", "dt_bias")):
            return 0.3 * jax.random.normal(k, a.shape)
        if name.endswith("A_log"):
            return jax.random.uniform(k, a.shape, minval=-3.0, maxval=0.5)
        if name.endswith("router_bias"):
            return 0.05 * jax.random.normal(k, a.shape)
        if name.endswith("router"):
            return a * 20
        if name.endswith("gate_up_proj/kernel") or name.endswith(
                "experts_gate_up"):
            return a * 12
        return a

    return jax.tree_util.tree_map_with_path(one, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = gigachat.get_config("tiny-gigachat", **F32)
    model = gigachat.GigaChatModel(cfg)
    params = fresh_params(model, 1, _seeded)
    return cfg, model, params


def _ids(shape, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, 256)


@jitted
def _reference(params, ids):
    return ref.forward(ref.weights_from_program_tree(params), ids, PUB)


MP = 12     # block-table columns of the tests' own pool: 192 tokens


@functools.lru_cache(maxsize=None)
def _step_fn(model, cfg, ctx_pages: int, prefill: bool):
    """One pass of the model through a GdnLatentCache, jitted a shape."""
    def fn(params, pool, bt, total, ids, positions):
        cache = gigachat.serving_cache(
            cfg, pool, bt, total,
            jnp.zeros((1,), jnp.int32) if prefill else None,
            ctx_pages=ctx_pages)
        logits, new = model.apply({"params": params}, ids,
                                  positions=positions, kv_caches=cache)
        return logits[0], new.pool

    return jax.jit(fn)


def _paged(cfg, model, params, seq, passes, decode=0, page=16):
    """Prefill `seq` in `passes` (lengths), then `decode` more tokens one
    at a time (teacher-forced from `seq`'s tail), through a cache of one
    slot: -> logits at every position [len, V], the pool."""
    pool = {k: jnp.zeros(*sd) for k, sd in gigachat.pool_spec(
        cfg, cfg.num_layers, 1 + MP, page, 1).items()}
    bt = jnp.arange(1, 1 + MP, dtype=jnp.int32)[None]
    out, start = [], 0
    steps = [(n, True) for n in passes] + [(1, False)] * decode
    for n, prefill in steps:
        logits, pool = _step_fn(model, cfg, MP if (prefill and start) else 0,
                                prefill)(
            params, pool, bt, jnp.asarray([start + n], jnp.int32),
            jnp.asarray(seq[start:start + n])[None],
            (start + jnp.arange(n))[None])
        out.append(logits)
        start += n
    return jnp.concatenate(out), pool


# ------------------------------------------------ (a) against the reference
def test_the_layer_list_is_the_published_one():
    full = gigachat.get_config("gigachat3.5-432b-a28b")
    runs = full.runs
    assert runs[0] == ((gigachat.GDN, True), 3)
    assert runs[1:3] == (((gigachat.MLA, False), 1),
                         ((gigachat.GDN, False), 3))
    assert (full.n_mla_layers, full.n_gdn_layers, full.n_expert_layers) == (
        10, 30, 37)
    # about 430 B whole (the published 432B counts two prediction modules)
    assert abs(full.num_params() / 430e9 - 1) < 0.01
    cut = gigachat.get_config(
        "gigachat3.5-432b-a28b", num_layers=5, kept_layers=(2, 3, 4, 5, 6),
        num_experts=16, n_routed_experts=256, vocab_size=16032)
    assert cut.runs == (((gigachat.GDN, True), 1), ((gigachat.MLA, False), 1),
                        ((gigachat.GDN, False), 3))
    assert abs(cut.num_params() / 4.73e9 - 1) < 0.005
    spec = gigachat.pool_spec(cut, 5, 12288, 64, 96)
    assert spec["latent_pages"][0] == (1, 12288, 1, 64, 640)
    assert spec["gdn_state"] == ((4, 96, 64, 128, 128), jnp.float32)
    assert spec["gdn_conv"][0] == (4, 3, 96, 16384)
    assert cut.gdn_state_bytes_row() == 4 * (64 * 128 * 128 * 4
                                             + 3 * 16384 * 2)
    weights, pair = gigachat.pass_cost_ratios(cut)
    assert 1.5 < weights < 3.5 and pair > 0
    assert abs(cut.softmax_scale - 0.105304) < 1e-6


def test_the_full_forward_is_the_references(tiny):
    _, model, params = tiny
    ids = _ids((2, 100))
    with jax.default_matmul_precision("highest"):
        got = applied(model, params, ids)
    want = _reference(params, ids)
    assert float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(got - want).max()) < 2e-4


def test_the_clamp_and_the_gates_are_live_in_the_comparison(tiny):
    """The reference with a reading switched off is NOT the program's: the
    seeded weights make the clamp and the norm's gain move the logits. (The
    constant 2 of the GDN output gate does not: the post-norm behind every
    mixer divides it out again, down to its eps.)"""
    _, model, params = tiny
    ids = _ids((1, 64))
    with jax.default_matmul_precision("highest"):
        got = applied(model, params, ids)
    w = ref.weights_from_program_tree(params)
    for over in (dict(swiglu_limit=None), dict(layernorm_gating_weight=1.0)):
        other = ref.forward(w, ids, {**PUB, **over})
        assert float(jnp.abs(got - other).max()) > 1e-2, over
    same = ref.forward(w, ids, {**PUB, "linear_sigmoid_gate_scale": 1.0})
    assert float(jnp.abs(got - same).max()) < 1e-3


@pytest.mark.parametrize("passes", [(80,), (32, 32, 16), (64, 16)])
def test_prefill_then_decode_through_a_cache_is_the_references(tiny, passes):
    """One pass or three resumed ones (state, conv tail and a latent
    context chunk carried), then the update and the absorbed decode."""
    cfg, model, params = tiny
    seq = _ids((90,), seed=5)
    with jax.default_matmul_precision("highest"):
        got, _ = _paged(cfg, model, params, seq, passes, decode=10)
    want = _reference(params, seq[None])[0]
    assert float(jnp.abs(got - want).max()) < 2e-4


def test_an_idle_slot_keeps_all_three_kinds_bit_for_bit(tiny):
    cfg, model, params = tiny
    rng = np.random.default_rng(0)
    pool = {k: jnp.asarray(rng.normal(size=sd[0]), sd[1])
            for k, sd in gigachat.pool_spec(cfg, cfg.num_layers, 9, 16,
                                            2).items()}
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    cache = gigachat.serving_cache(cfg, pool, bt,
                                   jnp.asarray([20, 0], jnp.int32))
    _, new = applied(model, params, _ids((2, 1)),
                     positions=jnp.asarray([[19], [0]]), kv_caches=cache)
    for name, axis in (("gdn_state", 1), ("gdn_conv", 2)):
        was, now = (jnp.moveaxis(p[name], axis, 0) for p in (pool, new.pool))
        assert bool((now[1] == was[1]).all()), name
        assert not bool((now[0] == was[0]).all())
    pages = np.asarray(new.pool["latent_pages"] != pool["latent_pages"])
    assert pages[:, 2].any() and not pages[:, 5:].any()


# ------------------------------------------------ (b) the shares add up
def test_the_shares_of_sixteen_chips_add_up_to_the_uncut_layer():
    """32 routed experts over 16 shares of 2, every FFN clamped: the routed
    parts of the shares, plus the shared expert once, are the reference's
    layer that holds all 32."""
    base = dict(hidden_size=32, intermediate_size=64, num_layers=1,
                num_heads=2, num_kv_heads=2, num_experts_per_tok=4,
                moe_intermediate_size=16, moe_scoring="sigmoid",
                routed_scaling_factor=2.5, n_shared_experts=1,
                swiglu_limit=1.5, **F32)
    x = 3 * jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    whole = MoEMLP(LlamaConfig(num_experts=32, **base))
    wp = jax.tree.map(np.asarray, nn.meta.unbox(
        whole.init(jax.random.PRNGKey(1), x)["params"]))
    wp["router"] = wp["router"] * 30
    wp["router_bias"] = 0.05 * np.asarray(
        jax.random.normal(jax.random.PRNGKey(2), (32,)))
    cfg = dict(PUB, swiglu_limit=1.5)
    m = x.reshape(-1, 32)

    def ref_w(first, n):
        return {"router": wp["router"], "router_bias": wp["router_bias"],
                "gate_up": wp["experts_gate_up"][first:first + n],
                "down": wp["experts_down"][first:first + n],
                "shared_gate_up": wp["shared"]["gate_up_proj"]["kernel"],
                "shared_down": wp["shared"]["down_proj"]["kernel"]}

    want, _ = ref._expert_layer(m, ref_w(0, 32), dict(cfg, expert_first=0),
                                "float32")
    assert float(jnp.abs(applied(whole, wp, x).reshape(-1, 32)
                         - want).max()) < 1e-5
    # the clamp bites: the same layer without it is another layer
    loose, _ = ref._expert_layer(m, ref_w(0, 32), dict(
        cfg, expert_first=0, swiglu_limit=None), "float32")
    assert float(jnp.abs(loose - want).max()) > 1e-2
    shared = ref._ffn(m, wp["shared"]["gate_up_proj"]["kernel"],
                      wp["shared"]["down_proj"]["kernel"], cfg, "float32")
    total = 0
    for first in range(0, 32, 2):
        share_cfg = LlamaConfig(num_experts=2, n_routed_experts=32,
                                expert_first=first, **base)
        sp = dict(wp, experts_gate_up=wp["experts_gate_up"][first:first + 2],
                  experts_down=wp["experts_down"][first:first + 2])
        got = MoEMLP(share_cfg).apply({"params": sp}, x).reshape(-1, 32)
        ref_share, _ = ref._expert_layer(
            m, ref_w(first, 2), dict(cfg, expert_first=first), "float32")
        assert float(jnp.abs(got - ref_share).max()) < 1e-5
        total = total + (got - shared)
    assert float(jnp.abs(total + shared - want).max()) < 2e-5


def test_a_vocabulary_slice_is_a_smaller_vocabulary(tiny):
    """Rows [0, 128) of the embedding and the head: the sliced model's
    logits are the whole model's over the slice, for ids of the slice."""
    cfg, model, params = tiny
    cut = gigachat.GigaChatModel(gigachat.get_config(
        "tiny-gigachat", vocab_size=128, **F32))
    sliced = dict(params, embed=params["embed"][:128],
                  lm_head=params["lm_head"][:, :128])
    ids = _ids((1, 40)) % 128
    with jax.default_matmul_precision("highest"):
        got = applied(cut, sliced, ids)
        whole = applied(model, params, ids)
    assert got.shape[-1] == 128
    assert float(jnp.abs(got - whole[..., :128]).max()) < 1e-5
    assert float(jnp.abs(got - _reference(sliced, ids)).max()) < 2e-4


# ----------------------------------------------------- (c) through the engine
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


def _engine(**over):
    """The module's engine of this configuration, renewed. (A fresh
    engine's norms, gates and decays sit at their centres: `_seeded` moves
    them, as `tiny` does.)"""
    return tiny_engine(**{**CFG, **over}, params=_seeded)


@pytest.fixture(scope="module")
def engine():
    return _engine()


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
    cfg = engine.model_cfg
    # the family's two come behind the stamps (the next family's two and
    # the engine's own behind them): hand-made records of four families'
    # tests hold every earlier field to its place
    assert fields[-6:] == ("gdn_layers", "gdn_state_bytes_row", "cca_layers",
                           "cca_tail_bytes_row", "drawn", "program_key")
    row_bytes = 5 * (4 * 16 * 16 * 4 + 3 * 128 * 4)      # float32 here
    for r in recs:
        assert len(r) == len(fields)
        assert (r["gdn_layers"], r["mla_layers"]) == (5, 1)
        assert r["gdn_state_bytes_row"] == row_bytes
        assert r["latent_bytes_token"] == 1 * 128 * 4
        assert r["lin_layers"] is None and r["ssm_layers"] is None
        real = sum(q for _, q, _ in r["rows"])
        assert r["moe_assignments_routed"] == real * 4 * cfg.n_expert_layers
        assert 0 <= r["moe_assignments"] <= r["moe_assignments_routed"]
    st = engine.stats()
    pre = [r for r in recs if r["kind"] == "prefill"]
    dec = [r for r in recs if r["kind"] == "decode"]
    assert st["gdn_prefill_tokens_total"] == 5 * sum(
        q for r in pre for _, q, _ in r["rows"]) == 5 * 220
    assert st["gdn_prefill_chunks_total"] == 5 * sum(
        -(-q // 64) for r in pre for _, q, _ in r["rows"])
    assert st["gdn_state_updates_total"] == 5 * sum(
        len(r["rows"]) * r["k"] for r in dec)
    assert st["mla_decode_ctx_tokens_total"] == sum(
        c for r in dec for _, _, c in r["rows"])
    assert st["prefill_resumed_passes_total"] >= 2   # 130 = 64 + 64 + 2
    assert st["latent_pool_bytes"] == 1 * 64 * 16 * 128 * 4
    assert st["gdn_state_pool_bytes"] == CFG["max_batch"] * row_bytes
    # no page is matched by its hash, and stats() says why
    assert st["prefix_reuse_refused_total"] >= 3
    assert "delta-rule state" in st["prefix_reuse_refused_why"]


def test_a_prompt_seen_before_is_prefilled_again_not_matched(engine):
    prompt = _prompts((48,), 11)[0]
    for i in range(2):
        engine.add_request(f"p{i}", prompt + [i], SamplingParams(max_tokens=6))
        assert _judge(engine, prompt + [i], _run(engine)[f"p{i}"]) >= 4
    st = engine.stats()
    assert st["prefix_token_hits"] == 0
    assert st["prefix_reuse_refused_total"] >= 1


def test_a_preemption_that_refills_keeps_the_tokens():
    """Out of pages mid-decode: the victim's slot and pages go, and it is
    refilled by prefilling prompt + tokens so far again (B-M6 (a): no
    state is saved)."""
    with scarce(_engine(), 8) as eng:
        prompts = _prompts((30, 33), 5)
        for i, p in enumerate(prompts):
            eng.add_request(f"q{i}", p, SamplingParams(max_tokens=50))
        got = _run(eng)
        assert eng.stats()["preempted_total"] >= 1
        for i, p in enumerate(prompts):
            assert len(got[f"q{i}"]) == 50
            assert _judge(eng, p, got[f"q{i}"]) >= 35


def test_no_program_is_built_under_traffic_after_warmup():
    """(An engine of its own: what a first use builds is the claim.)"""
    eng = new_engine(**CFG)
    n = eng.warmup()
    assert n == 2 * 2 + 1
    tracing.reset_ring()
    for i, p in enumerate(_prompts((20, 70, 130, 33), 21)):
        eng.add_request(f"w{i}", p, SamplingParams(max_tokens=4))
    _run(eng)
    assert not tracing.records("engine.program_built")
    assert eng.stats()["programs_built_total"] == n
    # every program carries its three-kind pool in place
    text = eng.program_text("decode", eng._decode_shape_key())
    assert text.count("tf.aliasing_output") >= 4
    eng.close()


def test_the_family_is_found_by_its_presets():
    assert model_family("gigachat3.5-432b-a28b") is gigachat
    assert model_family("tiny-gigachat") is gigachat


# ------------------------------------------------------ (d) each refusal
@pytest.mark.parametrize("over, what", [
    (dict(tp=2), "tensor parallelism"),
    (dict(pp=3), "pipeline parallelism"),
    (dict(spec_lookahead=4), "spec_lookahead=4")])
def test_what_this_family_cannot_be_given_is_refused_by_name(over, what):
    with pytest.raises(NotImplementedError, match=what) as e:
        LLMEngine(EngineConfig(**{**CFG, **over}))
    assert "a matrix state and a conv tail a decode slot" in str(e.value)


def test_the_handoff_is_refused_by_name(engine):
    with pytest.raises(NotImplementedError, match="hand-off") as e:
        engine.add_request("h", [1, 2, 3], SamplingParams(
            max_tokens=2, prefill_only=True))
    assert "conv tail would be left behind" in str(e.value)


def test_a_slice_of_the_stack_is_refused():
    with pytest.raises(NotImplementedError, match="two mixers"):
        gigachat.serving_model(gigachat.get_config("tiny-gigachat"), 1, True,
                               False)
