"""SDAR (models/sdar.py): the Llama family's layers under a block mask,
generation by diffusion over blocks through the paged engine, against the
plain reference (chipbench/references/sdar_decoder.py): seeded weights in
float32, tiny sizes, on the CPU. The tiny preset has 16 experts of which 4
a token, an expert width that is not the dense one's, head-dim norms on q
and k, blocks of 4."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.references import sdar_decoder as reference
from ray_tpu.models import sdar
from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops import head_argmax as head_op
from ray_tpu.ops.paged_attention import (paged_attention_block,
                                         paged_attention_reference)
from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.serve.llm.stage import init_params, model_family
from ray_tpu.util import tracing

from _engines import new_engine, scarce, tiny_engine

B = 4
CFG = dict(model="tiny-sdar", page_size=16, num_pages=64, max_model_len=256,
           max_batch=4, prefill_buckets=(32, 64, 128), dtype="float32")
RULES = ("low_confidence_static", "sequential", "low_confidence_dynamic")
# remainders 3, 0, 1, 2, 3 by blocks of 4; the first is shorter than a block
PROMPT_LENS = (3, 8, 41, 42, 43)


def _model_cfg(**over):
    return sdar.get_config("tiny-sdar", scan_layers=True, remat=False,
                           dtype=jnp.float32, param_dtype=jnp.float32, **over)


@functools.cache
def _params(seed=7, head_gain=1.0):
    """The benchmark's seeded weights in the tiny preset's tree. The head
    times `head_gain`: at 200 every position's softmax is peaked (a
    "confident" model)."""
    cfg = _model_cfg()
    probe = jax.eval_shape(lambda: init_params(
        sdar.serving_model(cfg), jnp.zeros((1, 8), jnp.int32),
        jax.random.PRNGKey(0)))
    params = weights.make_params(probe, seed)
    params["lm_head"]["kernel"] = params["lm_head"]["kernel"] * head_gain
    return params


def _ref_cfg(model_cfg):
    c = model_cfg
    return dict(
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        head_dim=c.head_dim_, hidden_size=c.hidden_size,
        moe_intermediate_size=c.moe_intermediate_size,
        num_experts_per_tok=c.num_experts_per_tok,
        rms_norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta,
        block_length=c.block_length, denoising_steps=c.denoising_steps,
        remasking=c.remasking, confidence_threshold=c.confidence_threshold,
        mask_token_id=c.mask_token_id)


def _engine(rule="low_confidence_static", head_gain=1.0, own=False,
            **over):
    """The module's engine of this configuration and head, renewed; or one
    of the caller's `own`, that no other case renews or has used: a
    fixture's, whose pool its tests read, or one whose first builds are
    counted."""
    cfg = {**CFG, **over}
    cfg["model_overrides"] = {"remasking": rule,
                              **over.get("model_overrides", {})}
    if own:
        return new_engine(**cfg, params=_params(head_gain=head_gain))
    return tiny_engine(**cfg, params=_params(head_gain=head_gain),
                       twin=head_gain)


def _run(engine, max_steps=2000):
    deltas = []
    for _ in range(max_steps):
        if not engine.has_work():
            return deltas
        deltas.extend(engine.step())
    raise AssertionError("the engine did not finish")


def _by_request(deltas):
    out = {}
    for d in deltas:
        got = out.setdefault(d.request_id, dict(tokens=[], passes=[],
                                                blocks=[], reason=None))
        if d.new_token_ids:
            got["tokens"] += d.new_token_ids
            got["passes"] += d.fixed_pass
            got["blocks"].append(list(d.new_token_ids))
        if d.finished:
            got["reason"] = d.finish_reason
    return out


def _prompts(lens=PROMPT_LENS, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 255, n).tolist() for n in lens]


def _reference_generate(engine, prompt, max_tokens, stop_ids=()):
    w = reference.weights_from_program_tree(engine.params)
    cfg = _ref_cfg(engine.model_cfg)
    return reference.generate(w, prompt, cfg, max_tokens, stop_ids)


# ------------------------------------------------------------------ model
@pytest.mark.parametrize("s", [16, 50])
def test_forward_under_the_block_mask_is_the_references(s):
    cfg, params = _model_cfg(), _params()
    ids = jnp.asarray(np.random.default_rng(s).integers(0, 255, (2, s)),
                      jnp.int32)
    got = sdar.serving_model(cfg).apply({"params": params}, ids)
    want = reference.forward(reference.weights_from_program_tree(params),
                             ids, _ref_cfg(cfg))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # and it is not the causal function: a block's first token sees its
    # last
    causal = sdar.serving_model(_model_cfg(block_length=1)).apply(
        {"params": params}, ids)
    assert float(jnp.abs(causal - got).max()) > 1e-2


def test_the_family_is_found_by_its_presets_and_keeps_llamas_layers():
    assert model_family("tiny-sdar") is sdar
    assert model_family("sdar-30b-a3b") is sdar
    cfg = sdar.get_config("sdar-30b-a3b")
    assert (cfg.block_causal, cfg.expert_width, cfg.qk_norm) == (4, 768,
                                                                 True)
    # 48 layers of 128 x 3 x 2048 x 768 experts: 30.5 B, of which 3.35 B a
    # token
    assert round(cfg.num_params() / 1e9, 1) == 30.5
    assert round(cfg.active_params() / 1e9, 2) == 3.35
    assert sdar.transfer_schedule(4, 4) == (1, 1, 1, 1)
    assert sdar.transfer_schedule(8, 3) == (3, 3, 2)
    with pytest.raises(ValueError, match="remasking"):
        sdar.get_config("tiny-sdar", remasking="random")


# ----------------------------------------------------------------- kernels
def _qkv(sq, sk, seed=0, b=2, hq=4, hkv=2, d=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, sq, hq, d), jnp.float32),
            jax.random.normal(ks[1], (b, sk, hkv, d), jnp.float32),
            jax.random.normal(ks[2], (b, sk, hkv, d), jnp.float32))


@pytest.mark.parametrize("sq, sk, block, lens", [
    (128, 128, 4, None), (256, 256, 16, None), (128, 384, 4, None),
    (256, 256, 4, ([130, 256], None))])
def test_flash_forward_block_mask_in_interpret_mode(sq, sk, block, lens):
    q, k, v = _qkv(sq, sk)
    kw = {}
    if lens:
        kw["q_lens"] = jnp.asarray(lens[0], jnp.int32)
    o, lse = flash_attention(q, k, v, causal=True, return_lse=True,
                             block_causal=block, interpret=True,
                             block_q=128, block_k=128, **kw)
    want = reference_attention(q, k, v, causal=True, block_causal=block)
    for i in range(q.shape[0]):
        n = lens[0][i] if lens else sq
        np.testing.assert_allclose(o[i, :n], want[i, :n], atol=2e-5,
                                   rtol=2e-5)
    # a block's first query sees the block's last key: not causal
    plain = reference_attention(q, k, v, causal=True)
    assert float(jnp.abs(plain - want).max()) > 1e-2


def test_block_causal_zero_is_the_call_it_was_and_misuse_is_refused():
    q, k, v = _qkv(128, 128)

    def text(**kw):
        return str(jax.make_jaxpr(lambda q, k, v: flash_attention(
            q, k, v, causal=True, return_lse=True, interpret=False, **kw))(
                q, k, v))

    assert text() == text(block_causal=0)
    assert text() != text(block_causal=4)
    with pytest.raises(ValueError, match="block_causal"):
        flash_attention(q, k, v, causal=True, block_causal=4)  # backward
    with pytest.raises(ValueError, match="block_causal"):
        flash_attention(q, k, v, causal=True, return_lse=True,
                        block_causal=48)                       # 128 % 48


@pytest.mark.parametrize("impl", ["reference", "kernel (interpret)"])
def test_block_step_attention_sees_every_key_of_the_row(impl):
    """[S, B] queries against `lengths` keys, the block's own included,
    no mask between them, against plain softmax over the gathered keys."""
    rng = np.random.default_rng(1)
    hq, hkv, d, page, mp, rows = 4, 2, 64, 16, 4, 3
    pool = jnp.asarray(rng.normal(size=(2, 1 + rows * mp, hkv, page, 2 * d)),
                       jnp.float32)
    bt = jnp.asarray(1 + np.arange(rows * mp).reshape(rows, mp), jnp.int32)
    lengths = jnp.asarray([B, 37, 0], jnp.int32)
    q = jnp.asarray(rng.normal(size=(rows, B, hq, d)), jnp.float32)
    got = paged_attention_block(
        q, pool, bt, lengths, layer=1,
        **({"interpret": True} if "kernel" in impl
           else {"force_reference": True}))
    # every query sits at the row's last position for the oracle
    want = paged_attention_reference(
        q, pool, bt, jnp.broadcast_to(jnp.maximum(lengths - 1, 0)[:, None],
                                      (rows, B)), layer=1)
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[2]).any()       # an inactive row: zeros


# ------------------------------------------------------------------ engine
@pytest.fixture(scope="module")
def generated():
    """{rule: (engine's outputs by request, the reference's)} for
    PROMPT_LENS, all requests of a rule in one batch. `dynamic` runs on a
    confident head (x200): several tokens a pass and an early exit."""
    out = {}
    for rule in RULES:
        gain = 200.0 if rule == "low_confidence_dynamic" else 1.0
        engine = _engine(rule, head_gain=gain, own=True)
        engine.warmup()
        built = engine.stats()["programs_built_total"]
        for i, p in enumerate(_prompts()):
            engine.add_request(f"r{i}", p, SamplingParams(max_tokens=13))
        got = _by_request(_run(engine))
        want = [_reference_generate(engine, p, 13) for p in _prompts()]
        out[rule] = (got, want, engine.stats(), built)
        engine.close()
    return out


@pytest.mark.parametrize("n", PROMPT_LENS)
@pytest.mark.parametrize("rule", RULES)
def test_engine_emits_the_references_tokens_in_its_order(generated, rule, n):
    got, want, _, _ = generated[rule]
    i = PROMPT_LENS.index(n)
    tokens, passes = want[i]
    assert got[f"r{i}"]["tokens"] == tokens
    assert got[f"r{i}"]["passes"] == passes
    assert got[f"r{i}"]["reason"] == "length"
    assert len(tokens) == 13
    # a block's tokens are one delta, in position order: the first block
    # holds what the prompt's tail left of it
    first = B - n % B
    assert [len(b) for b in got[f"r{i}"]["blocks"]][:2] == [first, B]


@pytest.mark.parametrize("rule", RULES)
def test_rules_differ_in_the_order_and_dynamic_leaves_early(generated, rule):
    got, _, stats, built = generated[rule]
    passes = [p for r in got.values() for p in r["passes"]]
    assert stats["programs_built_total"] == built     # nothing under traffic
    if rule == "sequential":
        # left to right: a block's passes ascend
        assert all(sorted(b) == b for b in (
            got["r1"]["passes"][:4], got["r1"]["passes"][4:8]))
    if rule == "low_confidence_static":
        assert any(sorted(got[r]["passes"][4:8]) != got[r]["passes"][4:8]
                   for r in got)                      # confidence's order
    # the seeded model does not repeat itself: order errors would show
    assert len({t for r in got.values() for t in r["tokens"]}) > 10
    per_pass = stats["block_tokens_total"] / stats["block_passes_total"]
    if rule == "low_confidence_dynamic":
        # a confident head fixes most of a block in one pass and the
        # program leaves before `denoising_steps`
        assert passes.count(0) > len(passes) // 2 and max(passes) < 3
        assert stats["block_early_exits_total"] == \
            stats["block_dispatches_total"]
        static = generated["low_confidence_static"][2]
        assert per_pass > 1.5 * (static["block_tokens_total"]
                                 / static["block_passes_total"])
    else:
        # a token a row and pass, and no pass more
        assert stats["block_early_exits_total"] <= 1
        assert set(passes) == {0, 1, 2, 3}


@pytest.mark.parametrize("max_tokens", [1, 5, 6, 7])
def test_max_tokens_cuts_a_last_block(max_tokens):
    engine = _engine()
    prompt = _prompts((42,))[0]
    engine.add_request("r", prompt, SamplingParams(max_tokens=max_tokens))
    got = _by_request(_run(engine))["r"]
    want, _ = _reference_generate(engine, prompt, max_tokens)
    assert got["tokens"] == want and len(want) == max_tokens
    assert got["reason"] == "length"
    engine.close()


def test_the_first_block_is_enqueued_right_behind_its_prefill():
    """The prefill yields no token the first block would wait for, and
    programs run in dispatch order: the step that enqueues a prompt's last
    pass enqueues its first block too (no harvest between them)."""
    engine = _engine()
    prompt = _prompts((42,))[0]
    engine.add_request("r", prompt, SamplingParams(max_tokens=8))
    engine.step()
    st = engine.stats()
    assert (st["prefill_dispatches_total"],
            st["block_dispatches_total"]) == (1, 1)
    got = _by_request(_run(engine))["r"]
    assert got["tokens"] == _reference_generate(engine, prompt, 8)[0]
    assert engine.stats()["prefill_tokenless_total"] == 1
    engine.close()


@pytest.mark.parametrize("which", [1, 5, 8])
def test_a_stop_token_inside_a_block_ends_the_request_there(which):
    """The `which`-th token of the free run is made a stop token: it and
    what lies left of it in its block are emitted, the rest dropped."""
    engine = _engine()
    prompt = _prompts((41,))[0]
    free, _ = _reference_generate(engine, prompt, 12)
    stop = free[which]
    cut = free.index(stop) + 1
    engine.add_request("r", prompt, SamplingParams(
        max_tokens=12, stop_token_ids=(stop,)))
    got = _by_request(_run(engine))["r"]
    assert got["tokens"] == free[:cut]
    assert got["reason"] == "stop"
    want, _ = _reference_generate(engine, prompt, 12, (stop,))
    assert got["tokens"] == want
    assert engine.stats()["free_pages"] == CFG["num_pages"] - 1
    engine.close()


def test_a_preempted_request_refills_and_goes_on_to_the_same_tokens():
    """Pages for two 40-token answers do not fit: one request is preempted
    mid-answer, its output folded into its prompt (whole blocks from
    position 0), and ends with the tokens it would have had alone."""
    with scarce(_engine(), 7) as engine:
        prompts = _prompts((30, 33), seed=5)
        for i, p in enumerate(prompts):
            engine.add_request(f"r{i}", p, SamplingParams(max_tokens=40))
        got = _by_request(_run(engine))
        assert engine.stats()["preempted_total"] >= 1
        for i, p in enumerate(prompts):
            assert got[f"r{i}"]["tokens"] == _reference_generate(
                engine, p, 40)[0], i
        reqs = [dict(zip(tracing.FIELDS["engine.request"], r))
                for r in tracing.records("engine.request")[-2:]]
        assert sorted(r["output_tokens"] for r in reqs) == [40, 40]
        engine.close()


def test_prefix_pages_are_reused_where_a_page_holds_whole_blocks():
    engine = _engine()
    shared = _prompts((48,), seed=11)[0]
    tails = _prompts((5, 7), seed=12)
    outs = []
    for i, tail in enumerate(tails):
        engine.add_request(f"r{i}", shared + tail,
                           SamplingParams(max_tokens=8))
        outs.append(_by_request(_run(engine))[f"r{i}"]["tokens"])
    st = engine.stats()
    assert st["prefix_token_hits"] == 48          # three pages of 16
    assert "prefix_reuse_refused_total" not in st
    for tail, got in zip(tails, outs):
        assert got == _reference_generate(engine, shared + tail, 8)[0]
    engine.close()


def test_prefix_reuse_is_refused_by_name_where_a_page_cuts_a_block():
    engine = _engine(page_size=6, max_model_len=96,
                     prefill_buckets=(12, 24, 48, 96))
    shared = _prompts((48,), seed=11)[0]
    for i, tail in enumerate(_prompts((5, 7), seed=12)):
        engine.add_request(f"r{i}", shared + tail,
                           SamplingParams(max_tokens=8))
        got = _by_request(_run(engine))[f"r{i}"]["tokens"]
        assert got == _reference_generate(engine, shared + tail, 8)[0]
    st = engine.stats()
    assert st["prefix_token_hits"] == 0
    assert st["prefix_reuse_refused_total"] == 2
    assert "page_size 6 holds no whole number of blocks of 4" in st[
        "prefix_reuse_refused_why"]
    engine.close()


def test_a_split_prompt_starts_its_passes_on_block_boundaries():
    """plan_passes' full passes are multiples of page and block: a prompt
    split in two (PassCost forced) gives the reference's tokens."""
    from ray_tpu.serve.llm.engine import PassCost

    engine = _engine()
    engine._pass_cost = PassCost(4, 0.0)
    prompt = _prompts((70,), seed=9)[0]
    engine.add_request("r", prompt, SamplingParams(max_tokens=6))
    got = _by_request(_run(engine))["r"]["tokens"]
    assert engine.stats()["prefill_split_prompts_total"] == 1
    assert got == _reference_generate(engine, prompt, 6)[0]
    engine.close()


@pytest.mark.parametrize("over, what", [
    (dict(spec_lookahead=4), "spec_lookahead=4"),
    (dict(tp=2), "tensor parallelism"),
    (dict(pp=2), "pipeline parallelism"),
    (dict(max_model_len=250), "whole blocks")])
def test_options_built_on_one_token_a_step_are_refused_by_name(over, what):
    with pytest.raises((NotImplementedError, ValueError), match=what) as e:
        LLMEngine(EngineConfig(**{**CFG, **over}))
    assert "diffusion over blocks of 4" in str(e.value)


def test_the_hand_off_is_refused_by_name():
    engine = _engine()
    for call in (
            lambda: engine.add_request("r", [1, 2, 3], SamplingParams(
                prefill_only=True)),
            lambda: engine.extract_kv("r"),
            lambda: engine.inject_request("r", {})):
        with pytest.raises(NotImplementedError, match="hand-off") as e:
            call()
        assert "a block's state" in str(e.value)
    engine.close()


def test_counters_records_and_the_first_tokens_three_parts():
    engine = _engine()
    n0 = {k: tracing.appended(k) for k in ("engine.dispatch",
                                           "engine.request")}
    before = engine.stats()
    for i, p in enumerate(_prompts((3, 41, 64))):
        engine.add_request(f"c{i}", p, SamplingParams(max_tokens=9))
    got = _by_request(_run(engine))
    after = engine.stats()
    moved = {k: after[k] - before[k] for k in after if k.endswith("_total")}
    fields = tracing.FIELDS["engine.dispatch"]
    recs = [dict(zip(fields, r)) for r in tracing.records(
        "engine.dispatch", since=n0["engine.dispatch"])]
    blocks = [r for r in recs if r["kind"] == "block"]
    assert {r["kind"] for r in recs} == {"prefill", "block"}
    assert moved["block_dispatches_total"] == len(blocks)
    assert moved["block_passes_total"] == sum(
        r["block_passes"] for r in blocks)
    assert moved["block_tokens_total"] == sum(
        r["block_tokens_fixed"] for r in blocks) == 27
    assert moved["block_rows_total"] == sum(len(r["rows"]) for r in blocks)
    # the 3-token prompt has no whole block: two prefills end tokenless
    assert moved["prefill_tokenless_total"] == 2
    assert moved["decode_dispatches_total"] == 0
    opened = set()
    for r in blocks:
        assert r["block_len"] == B and r["k"] == 4
        assert 1 <= r["block_passes"] <= 4
        assert all(q == B and ctx % B == 0 for _, q, ctx in r["rows"])
        # experts: every real token of every pass, 4 experts in 2 layers;
        # the first pass carries the pending block of every row that has
        # one (a request's every block but its first) beside the new one
        pending = sum(rid in opened for rid, _, _ in r["rows"])
        assert r["moe_assignments"] == (
            r["block_passes"] * len(r["rows"]) + pending) * B * 4 * 2
        opened |= {rid for rid, _, _ in r["rows"]}
        assert r["enqueued_ns"] <= r["device_start_ns"] <= r["device_end_ns"]
    # 9 tokens behind tails of 3, 1 and 0: 3, 3 and 3 blocks, of which
    # each request's last is never settled
    assert moved["block_settles_folded_total"] == 6
    assert moved["block_unsettled_dropped_total"] == 3
    prefills = [r for r in recs if r["kind"] == "prefill"]
    assert all(r["block_passes"] is None for r in prefills)
    assert fields.index("block_passes") == fields.index("final") + 1
    # (behind them a two-kind attention family's six, PR 47, and a
    # latent family's four, PR 43; then the stamps)
    assert fields.index("window_layers") == fields.index("block_len") + 1
    assert fields.index("mla_layers") == fields.index("block_len") + 7
    reqs = {r[0]: dict(zip(tracing.FIELDS["engine.request"], r))
            for r in tracing.records("engine.request",
                                     since=n0["engine.request"])}
    for i in range(3):
        r = reqs[f"c{i}"]
        assert r["output_tokens"] == 9 == len(got[f"c{i}"]["tokens"])
        assert (r["device_wait_ns"] + r["prefill_device_ns"]
                + r["harvest_host_ns"]
                == r["first_token_ns"] - r["dispatched_ns"])
        # its own programs: its prefill (none for c0) and its first block
        mine = [d for d in recs if any(row[0] == f"c{i}"
                                       for row in d["rows"])]
        own = [d for d in mine if d["kind"] == "prefill"] + [
            d for d in mine if d["kind"] == "block"][:1]
        assert r["prefill_device_ns"] == sum(
            d["device_end_ns"] - d["device_start_ns"] for d in own)
    # the span lies inside the step record's dispatch_decode
    from ray_tpu.serve.llm import server

    assert "block_tokens_total" in server._get_llm_metrics(
        engine.family_facts)
    engine.close()


def test_sampled_rows_are_seeded_and_greedy_rows_unmoved_beside_them():
    prompt = _prompts((41,))[0]
    outs = []
    for _ in range(2):
        engine = _engine()
        engine.add_request("g", prompt, SamplingParams(max_tokens=8))
        engine.add_request("s", prompt, SamplingParams(
            max_tokens=8, temperature=1.0, top_k=8, seed=5))
        outs.append(_by_request(_run(engine)))
        greedy = _reference_generate(engine, prompt, 8)[0]
        engine.close()
    assert outs[0]["g"]["tokens"] == outs[1]["g"]["tokens"] == greedy
    assert outs[0]["s"]["tokens"] == outs[1]["s"]["tokens"] != greedy


def test_a_drawing_row_takes_the_program_off_the_kernel_and_is_counted():
    """`block_drawn_dispatches_total`: the block programs whose batch had a
    row above temperature 0 (they write the head's logits and draw); a
    greedy batch's programs decide in the head's kernel and move nothing."""
    prompt = _prompts((41,))[0]
    engine = _engine()
    assert engine.stats()["block_drawn_dispatches_total"] == 0
    engine.add_request("g", prompt, SamplingParams(max_tokens=8))
    _run(engine)
    st = engine.stats()
    assert st["block_dispatches_total"] >= 2
    assert st["block_drawn_dispatches_total"] == 0
    n0 = tracing.appended("engine.dispatch")
    engine.add_request("g2", prompt, SamplingParams(max_tokens=16))
    engine.add_request("s", prompt, SamplingParams(
        max_tokens=3, temperature=1.0, top_k=8, seed=5))
    _run(engine)
    fields = tracing.FIELDS["engine.dispatch"]
    recs = [dict(zip(fields, r)) for r in tracing.records(
        "engine.dispatch", since=n0)]
    drew = [r["drawn"] for r in recs if r["kind"] == "block"]
    # `s` leaves with its first block; `g2` goes on alone, greedy again
    assert True in drew and drew[-1] is False
    moved = {k: engine.stats()[k] - st[k] for k in (
        "block_dispatches_total", "block_drawn_dispatches_total")}
    assert moved == {"block_dispatches_total": len(drew),
                     "block_drawn_dispatches_total": sum(drew)}
    from ray_tpu.serve.llm import server

    assert "block_drawn_dispatches_total" in server._get_llm_metrics(
        engine.family_facts)
    engine.close()


# --------------------------- the head of a greedy pass, decided in its tiles
def _plain_head(x, w):
    """What the plain head and `_sample`'s greedy body keep of a row on the
    chip: the product in float32 (XLA does not round it to bf16 on the way
    to the float32 reductions), reduced in float32."""
    logits = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                     precision="highest")
    return (jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1),
            jax.nn.logsumexp(logits, axis=-1))


@pytest.mark.parametrize("values", ["integers", "normal"])
@pytest.mark.parametrize("rows, h, v", [
    (4, 64, 1000),      # one tile, and it is partial
    (16, 64, 2500),     # three tiles of 1024, 452 columns in the last
    (100, 128, 2374),   # rows that are no whole sublane tile
    (256, 64, 1187 * 2),   # the cell's rows; 128 does not divide V
    (64, 2048, 1152)])  # the cell's hidden width: a second tile of 128
def test_head_kernel_keeps_what_the_plain_head_keeps(rows, h, v, values):
    """The kernel in interpret mode against argmax / max / logsumexp of
    the float32 product. "integers": operands of -3..3, so every product
    is exact in float32 whatever the order of its sum, and equal logits
    are common (the lowest index must win each); "normal": the sum's
    order moves a logit in its last bits, so near-ties may fall either
    way."""
    rng = np.random.default_rng(rows + v)
    if values == "integers":
        x = jnp.asarray(rng.integers(-3, 4, (rows, h)), jnp.bfloat16)
        w = jnp.asarray(rng.integers(-3, 4, (h, v)), jnp.bfloat16)
    else:
        x = jnp.asarray(rng.normal(size=(rows, h)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(h, v)) * h ** -0.5, jnp.bfloat16)
    arg, top, lse = head_op.head_argmax(x, w, impl="pallas_interpret")
    want_arg, want_top, want_lse = _plain_head(x, w)
    assert arg.dtype == jnp.int32 and arg.shape == (rows,)
    if values == "integers":
        np.testing.assert_array_equal(arg, want_arg)
        np.testing.assert_array_equal(top, want_top)
        np.testing.assert_allclose(lse, want_lse, atol=2e-5, rtol=2e-6)
        # and the jnp form every other backend takes is the same function
        # (float32 operands: a CPU's bf16 product is not float32's rounded)
        for got, want in zip(
                head_op.head_argmax(x.astype(jnp.float32),
                                    w.astype(jnp.float32), impl="jnp"),
                (want_arg, want_top, want_lse)):
            np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-6)
    else:
        np.testing.assert_allclose(top, want_top, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(lse, want_lse, atol=2e-5, rtol=2e-6)
        assert (arg < v).all() and (arg == want_arg).mean() > 0.98


@pytest.mark.parametrize("at", [
    (5, 1029),          # two tiles, the same lane
    (1029, 5),
    (700, 1500),        # two tiles, other lanes
    (133, 5),           # one tile, two of its 128-column chunks
    (1100, 2300),       # the second tile and the partial third
    (2499, 0),          # the vocabulary's last column and its first
    (3, 4)])            # neighbours on one chunk
def test_head_kernel_breaks_an_exact_tie_for_the_lowest_index(at):
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.integers(-2, 3, (8, 64)), jnp.bfloat16)
    w = rng.integers(-1, 2, (64, 2500)).astype(np.float32)
    # both columns are a row's own direction: the largest logit, twice
    w[:, at[0]] = w[:, at[1]] = 3 * np.asarray(x[2], np.float32)
    w = jnp.asarray(w, jnp.bfloat16)
    arg, top, _ = head_op.head_argmax(x, w, impl="pallas_interpret")
    want_arg, want_top, _ = _plain_head(x, w)
    assert int(arg[2]) == min(at) == int(want_arg[2])
    np.testing.assert_array_equal(arg, want_arg)
    np.testing.assert_array_equal(top, want_top)


def test_the_vocabulary_tile_fits_the_vmem_a_kernel_gets_unasked():
    assert head_op.vocab_tile(256, 2048) == 1024       # the cell's shape
    for rows, h, size in [(256, 2048, 2), (256, 4096, 2), (64, 8192, 2),
                          (256, 2048, 4), (1024, 7168, 2)]:
        tn = head_op.vocab_tile(rows, h, size)
        assert tn % head_op.LANES == 0 and tn >= head_op.LANES
        held = 2 * size * h * (tn + rows) + 4 * rows * tn
        assert held <= 16 << 20 or tn == head_op.LANES, (rows, h, tn)


def test_a_greedy_engine_on_the_kernel_emits_the_references_tokens(
        monkeypatch):
    """The block program with the kernel in it (interpret mode; a CPU
    backend's programs take the jnp form), at a vocabulary of two tiles
    that 128 does not divide: the reference's tokens in the reference's
    order, and the program holds no [S, B, V] product outside the branch
    that draws."""
    monkeypatch.setattr(head_op, "_impl", lambda: "pallas_interpret")
    engine = new_engine(**{**CFG, "model_overrides": {
        "vocab_size": 1100, "mask_token_id": 1099,
        "remasking": "low_confidence_static"}})
    text = engine.program_text("block", engine._block_shape_key())
    assert text.count("tensor<4x4x1100xf32>") > 0       # the drawn branch
    assert "tensor<16x1100xf32>" not in text            # no greedy logits
    prompts = _prompts((3, 41, 42))
    for i, p in enumerate(prompts):
        engine.add_request(f"k{i}", p, SamplingParams(max_tokens=9))
    got = _by_request(_run(engine))
    for i, p in enumerate(prompts):
        tokens, passes = _reference_generate(engine, p, 9)[:2]
        assert got[f"k{i}"]["tokens"] == tokens, i
        assert got[f"k{i}"]["passes"] == passes, i
    assert engine.stats()["block_drawn_dispatches_total"] == 0
    engine.close()


# ------------------------------------------- a settled block's keys, folded
def _settling_forward(engine, pool, pages, ids, start):
    """What the program had as a pass of its own before PR 42: one forward
    over a settled block alone, at width B, through the engine's model on
    `pool` -> the pool with the block's final keys and values."""
    from ray_tpu.models.llama import PagedCache

    L = engine.model_cfg.num_layers
    bt = np.zeros((1, engine.max_pages_per_seq), np.int32)
    bt[0, :len(pages)] = pages
    total = jnp.asarray([start + B], jnp.int32)
    pc = PagedCache(
        kv_pages=pool, block_tables=jnp.broadcast_to(bt, (L,) + bt.shape),
        total_lens=jnp.broadcast_to(total, (L, 1)), block_step=True)
    positions = jnp.asarray(start + np.arange(B, dtype=np.int32))[None]
    (_, new_pc), _ = engine.model.apply(
        {"params": engine.params}, jnp.asarray([ids], jnp.int32),
        positions=positions, kv_caches=pc,
        token_mask=positions < total[:, None], mutable=["routing"])
    return new_pc.kv_pages


def _block_kv(pool, pages, start, page=CFG["page_size"]):
    """[L, Hkv, B, 2D]: the keys and values at positions start..start+B-1
    (a block lies inside one page)."""
    return np.asarray(pool)[:, pages[start // page], :,
                            start % page:start % page + B]


def _finished_with_pages(engine, rid, prompt, **sampling):
    """Run one request to its end -> (its tokens, the pages it held: they
    are released and not cleared)."""
    engine.add_request(rid, prompt, SamplingParams(**sampling))
    pages, deltas = [], []
    while engine.has_work():
        deltas += engine.step()
        req = engine.requests.get(rid)
        if req is not None and req.pages:
            pages = list(req.pages)
    return _by_request(deltas)[rid]["tokens"], pages


@pytest.fixture(scope="module")
def one_answer():
    """A 41-token prompt (a tail of 1) and 15 tokens: four blocks."""
    engine = _engine(own=True)
    prompt = _prompts((41,))[0]
    tokens, pages = _finished_with_pages(engine, "r", prompt, max_tokens=15)
    assert tokens == _reference_generate(engine, prompt, 15)[0]
    yield engine, prompt + tokens, pages
    engine.close()


@pytest.mark.parametrize("n", [0, 1, 2])
def test_the_next_blocks_program_leaves_the_keys_a_settling_pass_writes(
        one_answer, n):
    """Block n is pending when its program ends; the pass that opens block
    n + 1 writes its keys. They are what a plain width-B forward over the
    settled block writes on the same pool."""
    engine, seq, pages = one_answer
    start = 40 + n * B
    want = _settling_forward(engine, engine.kv_pages, pages,
                             seq[start:start + B], start)
    np.testing.assert_allclose(
        _block_kv(engine.kv_pages, pages, start),
        _block_kv(want, pages, start), atol=1e-5, rtol=1e-5)
    # every earlier position as well: the settling forward changed nothing
    # but its block
    np.testing.assert_array_equal(
        np.asarray(want)[:, pages[:2]], np.asarray(engine.kv_pages)[:, pages[:2]])


def test_a_requests_last_block_is_never_settled_and_nothing_reads_it(
        one_answer):
    """The last block's pages hold its last denoising pass's keys (a mask
    among the inputs), not its settled ids', and no reader exists: a prompt
    that continues the answer reuses the PROMPT's full pages and no page of
    the answer, and gets the reference's tokens."""
    engine, seq, pages = one_answer
    start = 40 + 3 * B
    want = _settling_forward(engine, engine.kv_pages, pages,
                             seq[start:start + B], start)
    assert np.abs(_block_kv(engine.kv_pages, pages, start)
                  - _block_kv(want, pages, start)).max() > 1e-3
    hits = engine.stats()["prefix_token_hits"]
    got, _ = _finished_with_pages(engine, "again", seq, max_tokens=5)
    assert engine.stats()["prefix_token_hits"] - hits == 32   # of 41
    assert got == _reference_generate(engine, seq, 5)[0]
    st = engine.stats()
    assert (st["block_settles_folded_total"],
            st["block_unsettled_dropped_total"]) == (3 + 1, 1 + 1)


@pytest.fixture(scope="module")
def one_program():
    """ONE block program on a pool of noise, the carry full of another
    request's ids. Rows: 0 a first block behind 40 prompt tokens, 1 a
    prompt of 2 tokens (its block starts the sequence), 2 a row whose
    block is pending (the only one that may write left of its block), 3 a
    first block whose page left of it is row 0's too (a shared prefix
    page). -> (pool before, pool after, block tables, totals)"""
    engine = _engine(max_model_len=64)
    c = engine.compute
    rng = np.random.default_rng(0)
    before = rng.normal(size=c.kv_pages.shape).astype(np.float32)
    c.kv_pages = jnp.asarray(before)
    c.block_ids = jnp.asarray(rng.integers(0, 255, (4, B)), jnp.int32)
    bt = np.zeros((4, 4), np.int32)
    bt[0, :3], bt[1, :1], bt[2, :3], bt[3, :3] = (1, 2, 3), (4,), (5, 6, 7), (
        1, 2, 8)
    total = np.asarray([44, B, 36, 36], np.int32)
    ids = np.full((4, B), 255, np.int32)
    ids[1, :2] = (7, 9)
    masked = ids == 255
    out = c.run("block", engine._block_shape_key(), jnp.asarray(bt),
                jnp.asarray(total), jnp.asarray(ids), jnp.asarray(masked),
                jnp.asarray([False, False, True, False]),
                np.zeros((4,), np.float32), np.zeros((4,), np.int32),
                jnp.zeros((4, 4, 2), jnp.uint32))
    yield before, np.asarray(c.kv_pages), bt, total, np.asarray(out)
    engine.close()


@pytest.mark.parametrize("row, what", [
    (0, "a first block: the slot's carry is another request's"),
    (1, "a prompt shorter than a block: nothing lies left of it"),
    (3, "a first block right of a shared prefix page"),
    (2, "a block over its pending one: both are written, no more")])
def test_a_row_writes_its_block_and_left_of_it_only_a_pending_one(
        one_program, row, what):
    before, after, bt, total, _ = one_program
    page = CFG["page_size"]
    changed = np.abs(after - before).max(axis=(0, 2, 4)) > 0   # [P, page]
    lo = total[row] - (2 * B if row == 2 else B)
    want = np.zeros_like(changed)
    for pos in range(lo, total[row]):
        want[bt[row, pos // page], pos % page] = True
    mine = [p for p in bt[row] if p and not (row == 3 and p in (1, 2))]
    np.testing.assert_array_equal(changed[mine], want[mine])
    if row == 3:
        # the shared pages: only row 0's own block, in page 3 of its table
        assert not changed[[1, 2]].any()
    assert not changed[0].any()          # page 0, every unused column's


def test_the_program_returns_denoising_steps_of_counts_and_its_carry(
        one_program):
    *_, out = one_program
    S, L, E = 4, 2, 16
    assert out.shape == (2 * S * B + 1 + 4 * L * E,)
    assert out[2 * S * B] == 4                        # forwards run
    counts = out[-4 * L * E:].reshape(4, L, E)
    # 4 live blocks and 1 pending one in the first pass, 4 in the others
    assert counts.sum(axis=(1, 2)).tolist() == [
        n * B * 4 * L for n in (5, 4, 4, 4)]


def test_the_head_is_computed_over_the_new_block_alone():
    """The pass that opens a block is [S, 2B] wide; its logits [S, B, V]."""
    engine = _engine()
    text = engine.program_text("block", engine._block_shape_key())
    V = engine.model_cfg.vocab_size
    assert f"tensor<4x{B}x{V}xf32>" in text
    assert f"tensor<4x{2 * B}x{V}xf32>" not in text
    assert f"tensor<4x{2 * B}x64xf32>" in text        # hidden states
    engine.close()


def test_a_slot_that_changes_hands_in_flight_starts_from_its_own_prompt():
    """One slot. `a` stops inside its second block while the program of
    its third is in flight; `c` takes the slot, whose carry is `a`'s. The
    stale program is dropped and `c` is the reference's."""
    engine = _engine(max_batch=1)
    pa, pc = _prompts((41, 42), seed=21)
    stop = _reference_generate(engine, pa, 12)[0][5]
    engine.add_request("a", pa, SamplingParams(max_tokens=12,
                                               stop_token_ids=(stop,)))
    engine.add_request("c", pc, SamplingParams(max_tokens=9))
    got = _by_request(_run(engine))
    assert got["a"]["tokens"] == _reference_generate(engine, pa, 12,
                                                     (stop,))[0]
    assert got["a"]["reason"] == "stop"
    assert got["c"]["tokens"] == _reference_generate(engine, pc, 9)[0]
    st = engine.stats()
    blocks = sum(len(r["blocks"]) for r in got.values())
    assert st["block_rows_total"] > blocks            # a stale row went
    assert (st["block_settles_folded_total"]
            + st["block_unsettled_dropped_total"]) == blocks
    assert st["block_unsettled_dropped_total"] == 2
    engine.close()


def test_a_row_that_sits_a_round_out_is_settled_when_it_next_goes():
    """`r1` is left out of the third block program (as a page shortfall
    leaves a row out): its pending block waits in the carry, through a
    program that is not its own, and the program that next carries it
    settles it."""
    engine = _engine()
    prompts = _prompts((41, 42), seed=4)
    for i, p in enumerate(prompts):
        engine.add_request(f"r{i}", p, SamplingParams(max_tokens=14))
    reserve, calls = engine._reserve_decode_pages, []

    def skipping(elig, k_steps):
        calls.append([r.request_id for r in elig])
        kept = reserve(elig, k_steps)
        if len(calls) == 3:
            kept = [r for r in kept if r.request_id != "r1"]
        return kept

    engine._reserve_decode_pages = skipping     # (`renewed` takes it off)
    n0 = tracing.appended("engine.dispatch")
    got = _by_request(_run(engine))
    fields = tracing.FIELDS["engine.dispatch"]
    rows = [[row[0] for row in dict(zip(fields, r))["rows"]]
            for r in tracing.records("engine.dispatch", since=n0)
            if r[1] == "block"]
    assert rows[:4] == [["r0", "r1"], ["r0", "r1"], ["r0"], ["r0", "r1"]]
    for i, p in enumerate(prompts):
        assert got[f"r{i}"]["tokens"] == _reference_generate(
            engine, p, 14)[0], i
    st = engine.stats()
    assert (st["block_settles_folded_total"]
            + st["block_unsettled_dropped_total"]) == sum(
                len(r["blocks"]) for r in got.values())
    engine.close()


@pytest.mark.parametrize("rule", RULES)
def test_a_program_runs_denoising_steps_forwards_and_no_settling_one(
        generated, rule):
    got, _, stats, _ = generated[rule]
    blocks = sum(len(r["blocks"]) for r in got.values())
    assert (stats["block_settles_folded_total"]
            + stats["block_unsettled_dropped_total"]) == blocks
    assert stats["block_unsettled_dropped_total"] == len(got)
    steps = 4
    if rule == "low_confidence_dynamic":
        # the confident head fixes a block in its opening pass
        assert stats["block_passes_total"] == stats["block_dispatches_total"]
    else:
        # a block of 4 masks takes 4 forwards, where it took 5
        full = stats["block_dispatches_total"] - stats[
            "block_early_exits_total"]
        assert full >= 7
        assert stats["block_passes_total"] <= steps * stats[
            "block_dispatches_total"]
        assert stats["block_passes_total"] >= steps * full + 1


# ---------------------------------------------------------------- the server
def test_the_openai_stream_sends_a_block_as_one_chunk():
    """The ingress over an in-process server with the tiny preset:
    `stream: true` gives one chunk an engine delta, and a block model's
    delta is a block's tokens in position order."""
    import asyncio

    from ray_tpu.serve.llm import LLMConfig
    from ray_tpu.serve.llm.server import LLMServer, OpenAIIngress
    from ray_tpu.serve.replica import Request

    config = LLMConfig(model_id="tiny-sdar", engine=EngineConfig(**{
        **CFG, "model_overrides": {"vocab_size": 512,
                                   "mask_token_id": 511}}))
    server = LLMServer.func_or_class(config)

    class Handle:
        def options(self, **_):
            return self

        def remote(self, *args, **kw):
            return server.generate(*args, **kw)

    ingress = OpenAIIngress.func_or_class(Handle(), "tiny-sdar", config)

    async def ask(stream):
        body = json.dumps({
            "model": "tiny-sdar", "max_tokens": 10, "stream": stream,
            "messages": [{"role": "user", "content": "hello there"}],
        }).encode()
        return await ingress(Request(
            method="POST", path="/v1/chat/completions", body=body))

    async def both():
        try:
            return await ask(False), await ask(True)
        finally:
            await server.shutdown()

    whole, chunks = asyncio.run(both())
    assert whole["usage"]["completion_tokens"] == 10
    assert all(c["object"] == "chat.completion.chunk" for c in chunks)
    sizes = [len(c["token_ids"]) for c in chunks]
    assert sum(sizes) == 10 and max(sizes) == B and len(chunks) <= 4
    assert [c["choices"][0]["finish_reason"] for c in chunks] == (
        [None] * (len(chunks) - 1) + ["length"])
    assert "".join(c["choices"][0]["delta"]["content"] for c in chunks) \
        == whole["choices"][0]["message"]["content"]
    assert all(len(c["fixed_pass"]) == len(c["token_ids"]) for c in chunks)
