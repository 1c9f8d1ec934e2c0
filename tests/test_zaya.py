"""ZAYA1's family (models/zaya.py) on the CPU at a tiny size: attention
inside a compressed latent with a tail of its last inputs a decode slot
beside every layer's pages, top-1 experts chosen by an MLP router whose
state runs down the stack, a tied head, and the engine's normal path, each
against the plain reference of the benchmark
(chipbench/references/cca_moe_decoder.py). Logits, not tokens, wherever a
number can be compared.

Tolerances: float32 on the CPU at `highest`; the logits' largest value is
about 0.7, and program and reference differ by the order of their sums
(4e-7 on a fresh tree): 2e-4 is 300 times that and a thousandth of what
any term left out moves (the negative checks below read 1e-2 and more).
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import cca_moe_decoder as ref
from ray_tpu.models import zaya
from ray_tpu.models.llama import LlamaConfig, MoEMLP
from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.serve.llm.stage import init_params, model_family
from ray_tpu.util import tracing

from _engines import (applied, fresh_params, jitted, new_engine,
                      tiny_engine)

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
CFG = dict(model="tiny-zaya", dtype="float32", page_size=16, num_pages=64,
           max_model_len=256, max_batch=4, prefill_buckets=(32, 64))
# the tiny preset as the reference reads a configuration
PUB = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
           rope_theta=5000000.0, rms_norm_eps=1e-5, num_experts=4,
           num_experts_per_tok=1, router_hidden_size=16,
           moe_intermediate_size=32)
TOL = 2e-4


def _seeded(params, seed=2):
    """Every term shows: taps, the head-mixing matrices and the router's
    state gain are non-zero from the initialiser; a bias that moves
    choices, temperatures of 8-16 a kv head, norm scales off 1, a router
    whose probabilities spread."""
    key = jax.random.PRNGKey(seed)

    def one(path, a):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        k = jax.random.fold_in(key, sum(map(ord, name)))
        if name.endswith("scale"):
            return 1 + 0.1 * jax.random.normal(k, a.shape)
        if name.endswith("router/bias"):
            return 0.1 * jax.random.normal(k, a.shape)
        if name.endswith("log_tau"):
            return jnp.log(jax.random.uniform(k, a.shape, minval=8.0,
                                              maxval=16.0))
        if name.endswith("router/out"):
            return a * 4
        return a

    return jax.tree_util.tree_map_with_path(one, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = zaya.get_config("tiny-zaya", **F32)
    model = zaya.ZayaModel(cfg)
    params = fresh_params(model, 1, _seeded)
    return cfg, model, params


def _ids(shape, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, 512)


@jitted
def _reference(params, ids, **over):
    return ref.forward(ref.weights_from_program_tree(params), ids,
                       {**PUB, **over})


MP = 12     # block-table columns of the tests' own pool: 192 tokens


@functools.lru_cache(maxsize=None)
def _step_fn(model, cfg, ctx_pages: int, prefill: bool):
    """One pass of the model through a CcaCache, jitted a shape; a prefill
    row's slot is 1 of the pool's 2."""
    def fn(params, pool, bt, total, ids, positions):
        cache = zaya.serving_cache(
            cfg, pool, bt, total,
            jnp.ones((1,), jnp.int32) if prefill else None,
            ctx_pages=ctx_pages)
        logits, new = model.apply({"params": params}, ids,
                                  positions=positions, kv_caches=cache)
        return logits, new.pool

    return jax.jit(fn)


def _fresh_pool(cfg, fill=None, page=16):
    spec = zaya.pool_spec(cfg, cfg.num_layers, 1 + MP, page, 2)
    if fill is None:
        return {k: jnp.zeros(*sd) for k, sd in spec.items()}
    rng = np.random.default_rng(fill)
    return {k: jnp.asarray(rng.normal(size=sd[0]), sd[1])
            for k, sd in spec.items()}


def _paged(cfg, model, params, seq, passes, decode=0, bucket=None,
           pool=None, between=None):
    """Prefill `seq` in `passes` (lengths; each padded to `bucket`
    positions where given), then `decode` more tokens one at a time
    (teacher-forced from `seq`'s tail), through a cache of slot 1 of 2:
    -> logits at every real position [len, V], the pool. `between(pool)`
    is applied to the pool after every prefill pass."""
    pool = _fresh_pool(cfg) if pool is None else pool
    bt = jnp.arange(1, 1 + MP, dtype=jnp.int32)[None]
    out, start = [], 0
    for n in passes:
        width = bucket or n
        ids = jnp.zeros((1, width), jnp.int32).at[0, :n].set(
            jnp.asarray(seq[start:start + n]))
        logits, pool = _step_fn(model, cfg, MP if start else 0, True)(
            params, pool, bt, jnp.asarray([start + n], jnp.int32), ids,
            (start + jnp.arange(width))[None])
        out.append(logits[0, :n])
        start += n
        if between is not None:
            pool = between(pool)
    for _ in range(decode):
        # a decode step is the slot set: row 0 idle, row 1 this sequence
        ids = jnp.stack([jnp.zeros((1,), jnp.int32),
                         jnp.asarray(seq[start:start + 1])])
        logits, pool = _step_fn(model, cfg, 0, False)(
            params, pool, jnp.concatenate([jnp.zeros_like(bt), bt]),
            jnp.asarray([0, start + 1], jnp.int32), ids,
            jnp.asarray([[0], [start]], jnp.int32))
        out.append(logits[1])
        start += 1
    return jnp.concatenate(out), pool


# ------------------------------------------------ (a) against the reference
def test_the_full_forward_is_the_references(tiny):
    _, model, params = tiny
    ids = _ids((2, 100))
    with jax.default_matmul_precision("highest"):
        got = applied(model, params, ids)
    want = _reference(params, ids)
    assert float(jnp.abs(want).max()) > 0.3
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("reading", [
    "value_shift", "conv0", "conv1", "qk_mean", "temperature", "rotation",
    "eda", "bias"])
def test_every_term_is_live_in_the_comparison(tiny, reading, monkeypatch):
    """The reference with one term taken out is NOT the program's: the
    seeded weights make each of them move the logits."""
    _, model, params = tiny
    ids = _ids((1, 64))
    with jax.default_matmul_precision("highest"):
        got = applied(model, params, ids)
    w = ref.weights_from_program_tree(params)
    layers, over = dict(w["layers"]), {}
    if reading == "value_shift":
        monkeypatch.setattr(ref, "_value_shift",
                            lambda v1, v2: jnp.stack([v1, v2], axis=1))
    elif reading == "conv0":      # the tap on the token before: off
        layers["a"] = layers["a"].at[:, 0].set(0.0)
    elif reading == "conv1":
        layers["B"] = layers["B"].at[:, 0].set(0.0)
    elif reading == "qk_mean":
        monkeypatch.setattr(ref, "_qk_mean", lambda q, k: (q, k))
    elif reading == "temperature":
        layers["log_tau"] = jnp.zeros_like(layers["log_tau"])
    elif reading == "rotation":
        over = dict(partial_rotary_factor=1.0)
    elif reading == "eda":
        layers["r_eda"] = jnp.zeros_like(layers["r_eda"])
    else:
        layers["r_bias"] = jnp.zeros_like(layers["r_bias"])
    other = ref.forward({**w, "layers": layers}, ids, {**PUB, **over})
    assert float(jnp.abs(got - other).max()) > 5e-3, reading


# ------------------------------- (b) prefill, then decode, through the pools
@pytest.mark.parametrize("n", [15, 16, 17, 47])
def test_prefill_then_decode_through_the_pools_is_the_references(tiny, n):
    """n on both sides of a page boundary (16), then 10 decode steps: the
    tail a prefill leaves is what the first decode step starts from."""
    cfg, model, params = tiny
    seq = _ids((n + 10,), seed=5)
    with jax.default_matmul_precision("highest"):
        got, _ = _paged(cfg, model, params, seq, (n,), decode=10)
    want = _reference(params, seq[None])[0]
    assert float(jnp.abs(got - want).max()) < TOL


# --------------------------------------------- (c) a prompt in resumed passes
@pytest.mark.parametrize("passes, bucket", [
    ((32, 48), None), ((32, 48), 64), ((48, 16, 16), 48), ((16, 64), 64)])
def test_a_prompt_in_resumed_passes_is_one_pass(tiny, passes, bucket):
    """The boundary at a page edge; with `bucket` a pass is PADDED past
    its real tokens (the tail it leaves is its last real token's, not the
    padding's), and a padded row sits beside a full one."""
    cfg, model, params = tiny
    seq = _ids((sum(passes) + 4,), seed=6)
    with jax.default_matmul_precision("highest"):
        got, _ = _paged(cfg, model, params, seq, passes, decode=4,
                        bucket=bucket)
    want = _reference(params, seq[None])[0]
    assert float(jnp.abs(got - want).max()) < TOL


def test_a_tail_dropped_at_a_pass_boundary_is_seen(tiny):
    """The same two passes with the slot's tail zeroed between them: the
    second pass's first tokens convolve and shift over zeros, and the
    logits leave the reference's by far more than the tolerance."""
    cfg, model, params = tiny
    seq = _ids((80,), seed=6)

    def drop(pool):
        return dict(pool, cca_tail=jnp.zeros_like(pool["cca_tail"]))

    with jax.default_matmul_precision("highest"):
        got, _ = _paged(cfg, model, params, seq, (32, 48), between=drop)
    want = _reference(params, seq[None])[0]
    assert float(jnp.abs(got[:32] - want[:32]).max()) < TOL
    assert float(jnp.abs(got[32:] - want[32:]).max()) > 50 * TOL


# ------------------------------------------------- (d) a slot that is reused
def test_a_reused_slot_starts_from_a_zero_tail(tiny):
    """A pool whose tails (and pages) hold another request's values: a
    prompt that starts at position 0 reads none of them."""
    cfg, model, params = tiny
    seq = _ids((40,), seed=7)
    with jax.default_matmul_precision("highest"):
        got, _ = _paged(cfg, model, params, seq, (30,), decode=10,
                        pool=_fresh_pool(cfg, fill=11))
    want = _reference(params, seq[None])[0]
    assert float(jnp.abs(got - want).max()) < TOL


def test_an_idle_slot_keeps_its_tail_and_pages_bit_for_bit(tiny):
    cfg, model, params = tiny
    pool = _fresh_pool(cfg, fill=0)
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    cache = zaya.serving_cache(cfg, pool, bt, jnp.asarray([20, 0], jnp.int32))
    _, new = applied(model, params, _ids((2, 1)),
                     positions=jnp.asarray([[19], [0]]), kv_caches=cache)
    was, now = pool["cca_tail"], new.pool["cca_tail"]
    assert bool((now[:, 1] == was[:, 1]).all())
    assert not bool((now[:, 0] == was[:, 0]).any(-1).all())
    pages = np.asarray(new.pool["kv_pages"] != pool["kv_pages"])
    assert pages[:, 2].any() and not pages[:, 5:].any()


# ------------------------------------------------ (e) the router's state
def _router_outputs(model, params, ids):
    """Each layer's ((probs, gate, idx), r) as the scan stacks them."""
    _, got = model.apply(
        {"params": params}, ids, mutable=["intermediates"],
        capture_intermediates=lambda m, _: m.name == "router")
    (choice, r), = got["intermediates"]["layers"]["router"]["__call__"]
    return choice, r


def test_the_routers_state_crosses_layers_through_the_scan(tiny):
    """With the experts' outputs zeroed the hidden states do not depend on
    the routing, so a later layer's router state can move only through
    `r`. Layer 0's gain multiplies r_(-1) = 0 and moves nothing; layer 0's
    projection moves layer 2's state, unless layer 1's gain is zero."""
    _, model, params = tiny
    ids = _ids((1, 24), seed=8)
    layers = params["layers"]
    mute = {**layers, "moe": {**layers["moe"], "experts_down": jnp.zeros_like(
        layers["moe"]["experts_down"])}}

    def run(**router):
        return _router_outputs(model, {**params, "layers": {
            **mute, "router": {**layers["router"], **router}}}, ids)

    eda, down = layers["router"]["eda"], layers["router"]["down"]
    (probs, _, _), r = run()
    (probs_g0, _, _), r_g0 = run(eda=eda.at[0].set(7.0))
    assert bool((r_g0 == r).all()) and bool((probs_g0 == probs).all())
    moved = down.at[0].multiply(1.5)
    (probs_d0, _, _), r_d0 = run(down=moved)
    assert float(jnp.abs(r_d0[2] - r[2]).max()) > 1e-3
    assert float(jnp.abs(probs_d0[2] - probs[2]).max()) > 1e-5
    cut = eda.at[1].set(0.0)
    _, r_cut = run(eda=cut)
    _, r_cut_d0 = run(eda=cut, down=moved)
    assert bool((r_cut_d0[2] == r_cut[2]).all())
    assert float(jnp.abs(r_cut_d0[0] - r_cut[0]).max()) > 1e-3


# ------------------------------------------ (f) the bias, and the weight
def test_the_bias_moves_the_choice_and_not_the_weight(tiny):
    cfg, model, params = tiny
    router = zaya.ZayaRouter(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 64))
    r_prev = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 16))
    p = jax.tree.map(lambda a: a[1], params["layers"]["router"])
    (probs, gate, idx), _ = applied(router, p, x, r_prev)
    # the weight is the chosen expert's probability as it is: not 1.0
    assert bool((gate == jnp.take_along_axis(probs, idx, -1)).all())
    assert float(gate.max()) < 0.9 and abs(float(probs.sum(-1).mean()) - 1
                                           ) < 1e-6
    pushed = {**p, "bias": p["bias"].at[3].add(10.0)}
    (probs_b, gate_b, idx_b), _ = applied(router, pushed, x, r_prev)
    assert bool((idx_b == 3).all()) and not bool((idx == 3).all())
    assert bool((probs_b == probs).all())
    assert bool((gate_b[:, 0] == probs[:, 3]).all())


# ------------------------------------------------ (g) the expert layer's seam
def _moe(k=2, **over):
    cfg = LlamaConfig(hidden_size=32, intermediate_size=64, num_layers=1,
                      num_heads=2, num_kv_heads=2, num_experts=4,
                      num_experts_per_tok=k, **F32, **over)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 32))
    layer = MoEMLP(cfg)
    params = nn.meta.unbox(layer.init(jax.random.PRNGKey(1), x)["params"])
    params["router"] = params["router"] * 30
    return cfg, layer, params, x


def test_a_choice_from_outside_is_served_as_the_layers_own():
    """The seam unused, the layer has its router and is what it was (the
    sha256 table of tests/test_program_pins.py holds every family's lowered
    text); given the choice its own router would make, it has no router
    and returns the same values."""
    cfg, layer, params, x = _moe()
    own = applied(layer, params, x)
    probs = jax.nn.softmax(jnp.einsum("th,he->te", x.reshape(-1, 32),
                                      params["router"]), axis=-1)
    gate, idx = jax.lax.top_k(probs, 2)
    gate = gate / gate.sum(-1, keepdims=True)
    outside = {k: v for k, v in params.items() if k != "router"}
    assert "router" not in nn.meta.unbox(layer.init(
        jax.random.PRNGKey(1), x, choice=(probs, gate, idx))["params"])
    given = applied(layer, outside, x, choice=(probs, gate, idx))
    assert bool((given == own).all())


def test_a_padded_token_reaches_no_expert_and_one_weight_is_not_one():
    cfg, layer, params, x = _moe(k=1, norm_topk_prob=False)
    t = x.shape[0] * x.shape[1]
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (t, 4)))
    idx = jnp.argmax(probs, -1)[:, None]
    gate = jnp.take_along_axis(probs, idx, -1)
    mask = jnp.arange(12)[None, :] < jnp.asarray([[12], [5]])
    outside = {k: v for k, v in params.items() if k != "router"}
    out, sown = applied(layer, outside, x, mask,
                        choice=(probs, gate, idx), mutable=["routing"])
    counts, = jax.tree.leaves(sown["routing"])
    assert int(counts.sum()) == 17
    assert bool((out[1, 5:] == 0).all()) and bool((out[1, :5] != 0).any())
    # un-normalised: doubling the weight doubles the output
    twice = applied(layer, outside, x, mask,
                    choice=(probs, 2 * gate, idx))
    assert float(jnp.abs(twice - 2 * out).max()) < 1e-5


# --------------------------------------------------- (h) the published sizes
@pytest.mark.parametrize("layers, billions", [(20, 4.688), (40, 8.840)])
def test_the_program_trees_count_at_the_published_widths(layers, billions):
    cfg = zaya.get_config("zaya1-8b", num_layers=layers)
    tree = jax.eval_shape(lambda: init_params(
        zaya.ZayaModel(cfg), jnp.zeros((1, 8), jnp.int32),
        jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert n == cfg.num_params()
    assert abs(n / 1e9 - billions) < 0.001
    # a layer: 5.57 M of attention (+ its norm), 0.66 M of router, 201.33 M
    # of experts
    assert abs(cfg.attn_params() / 1e6 - 5.573) < 0.001
    assert abs(cfg.router_params() / 1e6 - 0.660) < 0.001
    assert abs(cfg.active_params() / layers / 1e6 - 18.82) < 0.01
    spec = zaya.pool_spec(cfg, layers, 3600, 64, 64)
    assert spec["kv_pages"][0] == (layers, 3600, 2, 64, 256)
    assert spec["cca_tail"][0] == (layers, 64, 2688)
    assert cfg.tail_bytes_row() == layers * 2688 * 2
    weights, pair = zaya.pass_cost_ratios(cfg)
    assert abs(240 * weights - 2647) < 2 and pair > 0


# ----------------------------------------------------- through the engine
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
    """The module's engine of this configuration on the seeded weights,
    renewed."""
    return tiny_engine(**{**CFG, **over}, params=_seeded)


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _prompts(lens, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, n).tolist() for n in lens]


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
    # the family's two come behind every earlier family's (only the
    # engine's own behind them): hand-made records hold those to their
    # places
    assert fields[-4:] == ("cca_layers", "cca_tail_bytes_row", "drawn",
                           "program_key")
    row_bytes = 3 * (2 * 96 + 16) * 4                    # float32 here
    for r in recs:
        assert len(r) == len(fields)
        assert (r["cca_layers"], r["cca_tail_bytes_row"]) == (3, row_bytes)
        assert r["gdn_layers"] is None and r["mla_layers"] is None
        real = sum(q for _, q, _ in r["rows"]) * r["k"]
        assert r["moe_assignments"] == real * 3          # top-1, 3 layers
    st = engine.stats()
    pre = [r for r in recs if r["kind"] == "prefill"]
    rows = [row for r in pre for row in r["rows"]]
    # 20 in one pass, 70 = 64 + 6, 130 = 64 + 64 + 2: three start at 0
    assert st["cca_tail_resets_total"] == sum(c == q for _, q, c in rows) == 3
    assert st["cca_resumed_rows_total"] == sum(c > q for _, q, c in rows) == 3
    assert st["prefill_resumed_passes_total"] == 3
    assert st["cca_tail_pool_bytes"] == CFG["max_batch"] * row_bytes
    assert st["moe_assignments_total"] == 3 * (220 + 3 * 7)
    # no page is matched by its hash, and stats() says why
    assert st["prefix_reuse_refused_total"] >= 3
    assert "carries no tail" in st["prefix_reuse_refused_why"]


def test_a_slot_given_to_a_new_request_leaks_no_tail():
    """One slot: a long request, then a short one in the slot it left; the
    second's tokens are the reference's on its own sequence."""
    eng = _engine(max_batch=1)
    for name, n in (("long", 150), ("short", 9)):
        prompt = _prompts((n,), len(name))[0]
        eng.add_request(name, prompt, SamplingParams(max_tokens=12))
        assert _judge(eng, prompt, _run(eng)[name]) >= 8
    assert eng.stats()["cca_tail_resets_total"] == 2


def test_a_prompt_seen_before_is_prefilled_again_not_matched(engine):
    prompt = _prompts((48,), 11)[0]
    for i in range(2):
        engine.add_request(f"p{i}", prompt + [i], SamplingParams(max_tokens=6))
        assert _judge(engine, prompt + [i], _run(engine)[f"p{i}"]) >= 4
    st = engine.stats()
    assert st["prefix_token_hits"] == 0
    assert st["prefix_reuse_refused_total"] >= 1


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
    # every program carries both kinds of its pool in place
    text = eng.program_text("decode", eng._decode_shape_key())
    assert text.count("tf.aliasing_output") >= 3
    # and its phases carry the family's scope beside the shared ones
    scopes = set(eng.program_scopes("decode", eng._decode_shape_key()
                                    ).values())
    for name in ("rtpu.attn.cca", "rtpu.attn.cache_write", "rtpu.moe.route",
                 "rtpu.head"):
        assert any(name in s for s in scopes), name
    eng.close()


def test_the_family_is_found_by_its_presets():
    assert model_family("zaya1-8b") is zaya
    assert model_family("tiny-zaya") is zaya
    assert "rtpu.attn.cca" in tracing.SCOPES


@pytest.mark.parametrize("over, what", [
    (dict(tp=2), "tensor parallelism"),
    (dict(pp=3), "pipeline parallelism"),
    (dict(spec_lookahead=4), "spec_lookahead=4")])
def test_what_this_family_cannot_be_given_is_refused_by_name(over, what):
    with pytest.raises(NotImplementedError, match=what) as e:
        LLMEngine(EngineConfig(**{**CFG, **over}))
    assert "a tail of its last inputs a decode slot" in str(e.value)


def test_the_handoff_and_a_slice_are_refused_by_name(engine):
    with pytest.raises(NotImplementedError, match="hand-off") as e:
        engine.add_request("h", [1, 2, 3], SamplingParams(
            max_tokens=2, prefill_only=True))
    assert "tail would be left behind" in str(e.value)
    with pytest.raises(NotImplementedError, match="beside the hidden"):
        zaya.serving_model(zaya.get_config("tiny-zaya"), 1, True, False)
