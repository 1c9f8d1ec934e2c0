"""A hybrid decoder (Mamba-1 state-space layers beside attention,
models/jamba.py) through the paged engine, against the plain reference
(chipbench/references/jamba_decoder.py): seeded random weights, float32,
`tiny-jamba` (two periods of Mamba, attention, Mamba, Mamba; one kv head
under 6 q heads), on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import jamba_decoder as reference
from chipbench.runners import engine_ssm
from ray_tpu.models import jamba, llama
from ray_tpu.ops import selective_scan as ss
from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.serve.llm.stage import model_family
from ray_tpu.util import tracing

from _engines import applied, fresh_params, jitted, scarce, tiny_engine

F32 = jnp.float32
CFG = jamba.get_config("tiny-jamba", dtype=F32, param_dtype=F32)
# the published keys of `tiny-jamba`, as a configuration file would hold them
PUB = dict(num_hidden_layers=8, attn_layer_period=4, attn_layer_offset=1,
           hidden_size=96, intermediate_size=128, num_attention_heads=6,
           num_key_value_heads=1, rms_norm_eps=1e-6, mamba_d_state=16,
           mamba_d_conv=4, mamba_dt_rank=8, tie_word_embeddings=True)
VOCAB = CFG.vocab_size


BASE = dict(model="tiny-jamba", dtype="float32", num_pages=64,
            page_size=8, max_model_len=128, max_batch=4,
            prefill_buckets=(16, 32, 64, 128), seed=3)


def _engine_config(**over):
    return EngineConfig(**{**BASE, **over})


def _engine(params, **over):
    """The module's engine of this configuration over the seeded weights,
    renewed."""
    return tiny_engine(**{**BASE, **over}, params=params)


@pytest.fixture(scope="module")
def model():
    return jamba.JambaModel(CFG)


@pytest.fixture(scope="module")
def params(model):
    return fresh_params(model, 11)


@pytest.fixture(scope="module")
def ref_weights(params):
    return reference.weights_from_program_tree(params)


def _ref_logits(ref_weights, seq):
    return np.asarray(jitted(reference.forward)(
        ref_weights, jnp.asarray([seq], jnp.int32), PUB)[0])


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


def _generate(engine, prompts, max_tokens):
    out = {}
    for i, p in enumerate(prompts):
        rid = p[0] if isinstance(p, tuple) else f"r{i}"
        ids = p[1] if isinstance(p, tuple) else p
        engine.add_request(rid, ids, SamplingParams(max_tokens=max_tokens))
        out[rid] = []
    while engine.has_work():
        for d in engine.step():
            out[d.request_id].extend(d.new_token_ids)
    return out


def _assert_greedy(ref_weights, prompt, tokens):
    """`tokens` are the reference's own greedy choices on prompt+tokens."""
    seq = list(prompt) + list(tokens)
    logits = _ref_logits(ref_weights, seq)
    want = logits.argmax(-1)[len(prompt) - 1:len(seq) - 1].tolist()
    assert tokens == want


# ------------------------------------------------------------------ model
def test_preset_counts_its_parameters_and_its_state():
    full = jamba.get_config("jamba2-3b")
    assert full.num_params() == 3_029_337_472            # 6.06 GB in bf16
    assert (full.n_mamba_layers, full.n_attn_layers) == (26, 2)
    assert full.ssm_state_bytes_row() == 26 * (5120 * 16 * 4 + 5120 * 3 * 2)
    spec = jamba.pool_spec(full, 28, 10753, 16, 64)
    assert spec["kv_pages"][0] == (2, 10753, 1, 16, 256)
    assert spec["ssm_h"] == ((26, 64, 16, 8, 640), jnp.float32)
    assert spec["ssm_conv"][0] == (26, 3, 64, 5120)
    with pytest.raises(ValueError, match="whole number of periods"):
        jamba.get_config("jamba2-3b", num_layers=27)


def test_family_is_chosen_in_one_place():
    assert model_family("tiny-jamba") is jamba
    assert model_family("jamba2-3b") is jamba
    assert model_family("tiny") is llama
    with pytest.raises(KeyError, match="no model preset"):
        model_family("no-such-model")


@pytest.mark.parametrize("length", [1, 5, 33, 70, 130])
def test_full_forward_matches_the_reference(model, params, ref_weights,
                                            length):
    ids = jnp.asarray([_prompt(length, length)], jnp.int32)
    got = np.asarray(applied(model, params, ids)[0])
    want = _ref_logits(ref_weights, ids[0].tolist())
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_program_initialisers_are_mambas(params):
    mixer = params["period_0"]["post"]["mixer"]
    np.testing.assert_allclose(
        mixer["A_log"][0, :, 3], np.log(np.arange(1, 17)), rtol=1e-6)
    assert (mixer["D"] == 1).all()
    step = jax.nn.softplus(mixer["dt_bias"])
    assert 1e-3 * 0.99 <= float(step.min()) and float(step.max()) <= 0.101
    assert float(jnp.abs(mixer["conv_kernel"]).max()) <= 0.5


def test_prefill_then_decode_through_pages_and_state(ref_weights, params):
    """Prefill, then token by token through the cache (the benchmark's own
    logits path: its pages and per-slot state), against the reference's
    full forward at every position."""
    engine = _engine(params)
    prompts = [_prompt(s, n) for s, n in ((1, 5), (2, 21), (3, 40))]
    rows, fed = engine_ssm._paged_logits(engine, prompts, 6)
    for prompt, logits, toks in zip(prompts, rows, fed):
        seq = prompt + toks[:-1]
        assert logits.shape[0] == len(seq)
        np.testing.assert_allclose(logits, _ref_logits(ref_weights, seq),
                                   atol=3e-5)


def _cache(pool, lens, slots, mp=4):
    b = len(lens)
    bt = jnp.arange(1, 1 + 4 * mp, dtype=jnp.int32).reshape(4, mp)[:b]
    return jamba.serving_cache(CFG, pool, bt, jnp.asarray(lens, jnp.int32),
                               slots)


def _empty_pool(fill=0.0):
    return jax.tree.map(
        lambda sd: jnp.full(sd[0], fill, sd[1]),
        jamba.pool_spec(CFG, CFG.num_layers, 17, 8, 4),
        is_leaf=lambda sd: isinstance(sd, tuple))


def _poisoned_state(pool):
    return {**pool, "ssm_h": jnp.full_like(pool["ssm_h"], jnp.nan),
            "ssm_conv": jnp.full_like(pool["ssm_conv"], jnp.nan)}


def _slot(pool, name, slot):
    """The bits of one slot's state: h [n_mamba, slots, N, 8, d/8] float32,
    conv [n_mamba, K-1, slots, d]."""
    a = np.asarray(pool[name])
    return (a[:, slot] if name == "ssm_h" else a[:, :, slot]).view(
        np.uint32)


def test_a_rows_logits_do_not_depend_on_slot_padding_or_neighbours(
        model, params):
    """The same prompt prefilled into slot 0 padded to 16, and into slot 2
    of a pool whose state is NaN, padded to 32 beside two other rows; then
    decoded beside idle slots and beside a live one: the same logits. A
    slot that does not decode keeps its state bit for bit."""
    prompt, other = _prompt(5, 11), _prompt(6, 20)

    def prefill(pool, rows, width):
        ids = np.zeros((len(rows), width), np.int32)
        for i, r in enumerate(rows):
            ids[i, :len(r)] = r
        cache = _cache(pool, [len(r) for r in rows],
                       jnp.arange(len(rows)))
        logits, cache = applied(model, params, jnp.asarray(ids),
                                kv_caches=cache)
        return np.asarray(logits), cache.pool

    def decode(pool, lens, tokens):
        pos = jnp.maximum(jnp.asarray(lens)[:, None] - 1, 0)
        logits, cache = applied(
            model, params, jnp.asarray(tokens, jnp.int32)[:, None],
            positions=pos, kv_caches=_cache(pool, lens, None))
        return np.asarray(logits[:, 0]), cache.pool

    alone, pool_a = prefill(_empty_pool(), [prompt], 16)
    trio, pool_b = prefill(_poisoned_state(_empty_pool()),
                           [[3], other, prompt], 32)
    np.testing.assert_allclose(alone[0, :11], trio[2, :11], atol=2e-5)
    # the state a row leaves is its own, wherever it sits
    np.testing.assert_allclose(pool_a["ssm_h"][:, 0], pool_b["ssm_h"][:, 2],
                               atol=2e-5)
    np.testing.assert_allclose(pool_a["ssm_conv"][:, :, 0],
                               pool_b["ssm_conv"][:, :, 2], atol=2e-5)
    assert np.isnan(np.asarray(pool_b["ssm_h"][:, 3])).all()   # untouched

    tok = int(alone[0, 10].argmax())
    got_a, _ = decode(pool_a, [12, 0, 0, 0], [tok, 0, 0, 0])
    got_b, after = decode(pool_b, [0, 21, 12, 0], [7, 9, tok, 3])
    np.testing.assert_allclose(got_a[0], got_b[2], atol=2e-5)
    for name in ("ssm_h", "ssm_conv"):
        for slot in (0, 3):        # idle in that step: one clean, one NaN
            np.testing.assert_array_equal(_slot(after, name, slot),
                                          _slot(pool_b, name, slot))
        for slot in (1, 2):        # live: moved
            assert not np.array_equal(_slot(after, name, slot),
                                      _slot(pool_b, name, slot))


def test_an_idle_slot_of_a_poisoned_pool_stays_poisoned_bit_for_bit(
        model, params):
    pool = _poisoned_state(_empty_pool())
    # one live row (slot 1, freshly prefilled), three idle NaN slots
    ids = jnp.asarray([_prompt(9, 7) + [0]], jnp.int32)
    bt = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    cache = jamba.serving_cache(CFG, pool, bt, jnp.asarray([7]),
                                jnp.asarray([1]))
    _, cache = applied(model, params, ids, kv_caches=cache)
    before = cache.pool
    bt4 = jnp.arange(1, 17, dtype=jnp.int32).reshape(4, 4)
    cache = jamba.serving_cache(CFG, before, bt4, jnp.asarray([0, 8, 0, 0]))
    logits, cache = applied(
        model, params, jnp.asarray([[1], [2], [3], [4]], jnp.int32),
        positions=jnp.asarray([[0], [7], [0], [0]]), kv_caches=cache)
    assert np.isfinite(np.asarray(logits[1])).all()
    for name in ("ssm_h", "ssm_conv"):
        for slot in (0, 2, 3):
            assert np.isnan(_slot(cache.pool, name, slot).view(
                np.float32)).all()
            np.testing.assert_array_equal(_slot(cache.pool, name, slot),
                                          _slot(before, name, slot))


# ----------------------------------------------------------------- engine
def test_engine_serves_greedy_tokens_the_reference_agrees_with(
        params, ref_weights):
    engine = _engine(params)
    prompts = [_prompt(s, n) for s, n in ((1, 5), (2, 20), (3, 33), (4, 60))]
    out = _generate(engine, prompts, 10)
    for i, p in enumerate(prompts):
        assert len(out[f"r{i}"]) == 10
        _assert_greedy(ref_weights, p, out[f"r{i}"])


def test_a_repeated_prompt_is_prefilled_again_not_reused(params,
                                                         ref_weights):
    """Prefix reuse is off: the second request of the same prompt finds no
    page, registers none, and gets the same tokens from its own state."""
    engine = _engine(params)
    prompt = _prompt(21, 40)               # five full pages
    first = _generate(engine, [("a", prompt)], 8)["a"]
    second = _generate(engine, [("b", prompt), ("c", prompt)], 8)
    assert first == second["b"] == second["c"]
    _assert_greedy(ref_weights, prompt, first)
    st = engine.stats()
    assert st["prefix_token_hits"] == 0 and st["cache_hits"] == 0
    assert st["prefix_reuse_refused_total"] == 3
    assert engine.allocator.frontier_snapshot()["hashes"] == []
    assert st["prefill_tokens_total"] == 3 * 40


def test_engine_is_right_through_a_preemption(params, ref_weights):
    """11 usable pages cannot hold three 7-page sequences: a request is
    preempted, its output folded into its prompt, and prefilled again from
    ZERO state into whatever slot it then gets."""
    with scarce(_engine(params), 11) as engine:
        prompts = [_prompt(s, 20) for s in (31, 32, 33)]
        out = _generate(engine, prompts, 30)
        assert engine.stats()["preempted_total"] >= 1
        for i, p in enumerate(prompts):
            assert len(out[f"r{i}"]) == 30
            _assert_greedy(ref_weights, p, out[f"r{i}"])


def test_a_poisoned_state_pool_gives_the_same_tokens(params):
    """NaN in every slot of the state pool before any request: a prefill
    row starts from zero and never reads what its slot held."""
    prompts = [_prompt(s, n) for s, n in ((41, 9), (42, 30))]
    clean = _generate(_engine(params), prompts, 8)
    engine = _engine(params)              # the same engine, renewed
    engine.compute.kv_pages = _poisoned_state(engine.compute.kv_pages)
    assert _generate(engine, prompts, 8) == clean


def test_warm_up_builds_no_cached_prefix_program(params):
    engine = _engine(params)
    programs = engine._warmup_programs(None, True)
    assert [key[2] for kind, key in programs if kind == "prefill"] == [0] * 4
    assert ("decode", engine._decode_shape_key()) in programs
    dense = tiny_engine("tiny")
    assert len(dense._warmup_programs(None, False)) == 2 * len(
        dense.config.prefill_buckets)


REFUSED = {
    "spec_lookahead": (dict(spec_lookahead=4), "rolled back"),
    "prefill_chunk_tokens": (dict(prefill_chunk_tokens=16),
                             "resumes from a slot's state"),
    "tp": (dict(tp=2), "d_inner"),
    "pp": (dict(pp=2), "pattern of two kinds"),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_engine_options_that_need_the_state_moved_are_refused(option):
    over, why = REFUSED[option]
    with pytest.raises(NotImplementedError, match=why) as e:
        LLMEngine(_engine_config(**over))
    assert "recurrent state-space state" in str(e.value)
    if option == "pp":
        from ray_tpu.serve.llm.pp import make_engine

        with pytest.raises(NotImplementedError, match=why):
            make_engine(_engine_config(**over))


def test_the_disaggregated_hand_off_is_refused(params):
    engine = _engine(params)
    for call in (
            lambda: engine.add_request("p", [1, 2, 3], SamplingParams(
                max_tokens=4, prefill_only=True)),
            lambda: engine.extract_kv("p"),
            lambda: engine.inject_request("p", {"kv": None}),
            engine._refuse_handoff):
        with pytest.raises(NotImplementedError, match="hand-off"):
            call()
    # a dense engine's hand-off is what it was
    tiny_engine("tiny")._refuse_handoff()


# ------------------------------------------------------ spans and counters
def test_records_and_stats_say_what_the_state_costs(params):
    tracing.reset_ring()
    # (two steps a dispatch: the updates are counted by a record's `k`)
    engine = _engine(params, decode_steps_per_dispatch=2)
    st = engine.stats()
    assert st["ssm_slots"] == 4
    assert st["ssm_state_pool_bytes"] == 4 * CFG.ssm_state_bytes_row()
    _generate(engine, [_prompt(51, 9), _prompt(52, 30)], 5)
    st = engine.stats()
    assert st["ssm_scan_tokens_total"] == 6 * (9 + 30)
    assert st["prefix_reuse_refused_total"] == 2
    fields = tracing.FIELDS["engine.dispatch"]
    recs = [dict(zip(fields, r)) for r in tracing.records("engine.dispatch")]
    assert {r["kind"] for r in recs} == {"prefill", "decode"}
    for r in recs:
        assert r["ssm_layers"] == 6
        assert r["ssm_state_bytes_row"] == CFG.ssm_state_bytes_row()
        assert r["moe_assignments"] is None
    assert st["ssm_state_updates_total"] == 6 * sum(
        r["k"] * len(r["rows"]) for r in recs if r["kind"] == "decode")


def test_dense_and_expert_engines_carry_none_of_it():
    for preset in ("tiny", "tiny-moe"):
        tracing.reset_ring()
        engine = tiny_engine(preset)
        _generate(engine, [[1, 2, 3, 4, 5]], 3)
        assert not [k for k in engine.stats()
                    if k.startswith(("ssm_", "prefix_reuse"))]
        # the positions behind an expert model's `moe_*` hold None (the
        # device stamps come last, so a record has every position)
        fields = tracing.FIELDS["engine.dispatch"]
        at = fields.index("moe_assignments" if preset == "tiny"
                          else "ssm_layers")
        recs = tracing.records("engine.dispatch")
        assert recs and all(set(r[at:fields.index("enqueued_ns")]) == {None}
                            for r in recs)
        assert engine.compute.operands("prefill")[-1] == "keys"


# --------------------------------------------------------------- the scan
def _token_by_token(x, dt, a, b, c, d_skip, h0):
    def step(h, t):
        xt, dtt, bt, ct = t
        h = jnp.exp(dtt[None] * a) * h + (dtt * xt)[None] * bt[:, None]
        return h, (h * ct[:, None]).sum(0) + d_skip * xt
    h, y = jax.lax.scan(step, h0, (x, dt, b, c))
    return y, h


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("s, d, real", [(37, 256, 37), (100, 1152, 91),
                                        (300, 1024, 263)])
def test_scan_matches_token_by_token_from_a_nonzero_state(impl, s, d, real):
    """Lengths that divide neither form's chunk, channels that do not
    fill the kernel's block, a padded tail (delta 0 past `real`)."""
    n = 16
    ks = jax.random.split(jax.random.PRNGKey(s), 8)
    x, z = (jax.random.normal(k, (s, d)) for k in ks[:2])
    dt = jax.nn.softplus(jax.random.normal(ks[2], (s, d)) - 2)
    dt = jnp.where(jnp.arange(s)[:, None] < real, dt, 0.0)
    a = -jnp.exp(0.5 * jax.random.normal(ks[3], (n, d)))
    b, c = (jax.random.normal(k, (s, n)) for k in ks[4:6])
    d_skip, h0 = jax.random.normal(ks[6], (d,)), jax.random.normal(
        ks[7], (n, d))
    want_y, want_h = _token_by_token(x, dt, a, b, c, d_skip, h0)
    got_y, got_h = ss.selective_scan(x, dt, a, b, c, d_skip, h0, z,
                                     length=real, impl=impl)
    np.testing.assert_allclose(got_y[:real],
                               (want_y * jax.nn.silu(z))[:real], atol=3e-5)
    np.testing.assert_allclose(got_h, want_h, atol=1e-5)


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_update_is_one_token_of_the_scan_in_place_for_live_slots(impl):
    """One layer of a pool of two; slots 1 and 4 of six are idle: their
    state (NaN in slot 4) stays bit for bit, their y is 0, the other
    layer is untouched; no live slot at all moves nothing."""
    n, d, slots = 16, 256, 6
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    x, z = (jax.random.normal(k, (slots, d)) for k in ks[:2])
    dt = jax.nn.softplus(jax.random.normal(ks[2], (slots, d)))
    a = -jnp.exp(jax.random.normal(ks[3], (n, d)))
    b, c = (jax.random.normal(k, (slots, n)) for k in ks[4:6])
    d_skip = jax.random.normal(ks[6], (d,))
    pool = jax.random.normal(
        ks[0], (2, slots) + ss.state_shape(n, d)).at[:, 4].set(jnp.nan)
    live = jnp.asarray([True, False, True, True, False, True])
    y, new = ss.selective_update(x, dt, a, b, c, d_skip, pool, 1, live, z,
                                 impl=impl)
    for i in range(slots):
        if not live[i]:
            assert (y[i] == 0).all()
            continue
        want_y, want_h = _token_by_token(x[i:i + 1], dt[i:i + 1], a,
                                         b[i:i + 1], c[i:i + 1], d_skip,
                                         pool[1, i].reshape(n, d))
        np.testing.assert_allclose(y[i], want_y[0] * jax.nn.silu(z[i]),
                                   atol=2e-5)
        np.testing.assert_allclose(new[1, i].reshape(n, d), want_h,
                                   atol=1e-5)
    bits = lambda t: np.asarray(t).view(np.uint32)   # noqa: E731
    np.testing.assert_array_equal(bits(new[0]), bits(pool[0]))
    np.testing.assert_array_equal(bits(new[1, [1, 4]]),
                                  bits(pool[1, [1, 4]]))
    y, same = ss.selective_update(x, dt, a, b, c, d_skip, pool, 1,
                                  jnp.zeros((slots,), bool), z, impl=impl)
    assert (y == 0).all()
    np.testing.assert_array_equal(bits(same), bits(pool))
    order, n_live = ss.live_slots(live)
    assert order.tolist() == [0, 2, 3, 5, 5, 5] and int(n_live) == 4


def test_llama_configs_still_rotate():
    """Rotation is something a config can be without; no Llama preset is."""
    assert all(c.rope_theta is not None for c in llama.CONFIGS.values())
    assert dataclasses.replace(CFG).rope_theta is None
