"""The Laguna family (models/laguna.py: models/mellum.py's mixed stack with
the shapes a kind) on the CPU at a tiny size: the published order's first
five layers (full + dense FFN, three sliding, full), 6 and 8 query heads
over 2 kv heads of 16 (groups of 3 and 4), a window of 32, half of a full
layer's head rotated by YaRN at one theta and a sliding layer's whole head
at another, a per-head output gate, 16 sigmoid-routed experts (4 a token, a
selection bias, scale 2.5) beside a shared one; each against the plain
reference of the benchmark (chipbench/references/laguna_decoder.py).
Logits, not tokens, wherever a number can be compared; the reference with
ONE part taken out has to FAIL the same limit.
"""

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import laguna_decoder as ref
from ray_tpu.models import laguna, mellum
from ray_tpu.ops import paged_attention as pa
from ray_tpu.serve.llm import EngineConfig, SamplingParams
from ray_tpu.serve.llm.engine import (PassCost, _attn_visits, _kind_shares,
                                      plan_passes, refuse)
from ray_tpu.serve.llm.stage import init_params, model_family
from ray_tpu.util import tracing

from _engines import applied, fresh_params, jitted, scarce, tiny_engine

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
WINDOW = 32
CFG = dict(model="tiny-laguna", dtype="float32", page_size=16, num_pages=96,
           max_model_len=768, max_batch=4, prefill_buckets=(32, 64, 128))
# the tiny preset as the reference reads a configuration
PUB = dict(
    num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
    rms_norm_eps=1e-6, num_experts_per_tok=4, sliding_window=WINDOW,
    gating=True, moe_routed_scaling_factor=2.5,
    shared_expert_intermediate_size=32,
    layer_types=(["full_attention"] + ["sliding_attention"] * 3) * 2,
    mlp_layer_types=["dense"] + ["sparse"] * 7,
    num_attention_heads_per_layer=[6, 8, 8, 8] * 2,
    rope_parameters={
        "full_attention": dict(
            rope_type="yarn", rope_theta=500000, factor=64,
            original_max_position_embeddings=64, beta_fast=64, beta_slow=1,
            attention_factor=1.4158883083359672, partial_rotary_factor=0.5),
        "sliding_attention": dict(rope_type="default", rope_theta=10000,
                                  partial_rotary_factor=1)})
# logits differ from the reference's by rounding; from a reference with a
# part taken out by the mechanism
TOL = 3e-4


def _seeded(params, seed=2):
    """Norm scales off one, a router whose scores spread and a selection
    bias that moves choices: a reference that forgot one would disagree."""
    key = jax.random.PRNGKey(seed)

    def one(path, a):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        k = jax.random.fold_in(key, sum(map(ord, name)))
        if name.endswith("scale"):
            return 1 + 0.1 * jax.random.normal(k, a.shape)
        if name.endswith("router_bias"):
            return 0.2 * jax.random.normal(k, a.shape)
        if name.endswith("router"):
            return a * 20
        return a

    return jax.tree_util.tree_map_with_path(one, params)


@pytest.fixture(scope="module", autouse=True)
def contexts_walked_in_chunks():
    """As tests/test_mellum.py: a full layer's context wider than four
    pages is walked in chunks at this size."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "FLASH_RESIDENT_KV_BYTES", 64 * 2 * 32 * 4)
        yield


@pytest.fixture(scope="module")
def tiny():
    cfg = laguna.get_config("tiny-laguna", **F32)
    model = laguna.serving_model(cfg)
    params = fresh_params(model, 1, _seeded)
    return cfg, model, params


def _ids(shape, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, 256)


def _rope(kind, **more):
    return {"rope_parameters": {
        **PUB["rope_parameters"],
        kind: {**PUB["rope_parameters"][kind], **more}}}


def _without(part, weights):
    """(weights, cfg) of the reference with ONE part taken out."""
    w = copy.copy(weights)
    w["runs"] = [dict(r) for r in weights["runs"]]
    if part == "bias":
        for r in w["runs"]:
            if "router_bias" in r:
                r["router_bias"] = jnp.zeros_like(r["router_bias"])
        return w, PUB
    return w, {**PUB, **{
        "gate": dict(gating=False),
        "half-rotation": _rope("full_attention", partial_rotary_factor=1),
        "theta-a-kind": _rope("sliding_attention", rope_theta=500000),
        "scale": dict(moe_routed_scaling_factor=1.0),
        "shared-expert": dict(shared_expert_intermediate_size=0),
        "one-key-more": dict(sliding_window=WINDOW + 1),
        "one-key-fewer": dict(sliding_window=WINDOW - 1),
        "all-full": dict(sliding_window=None),
    }[part]}


PARTS = ("gate", "half-rotation", "theta-a-kind", "bias", "scale",
         "shared-expert", "one-key-more", "one-key-fewer")


@jitted
def _reference(params, ids, part=None):
    w = ref.weights_from_program_tree(params)
    cfg = PUB
    if part is not None:
        w, cfg = _without(part, w)
    return ref.forward(w, ids, cfg)


MP = 48     # block-table columns of the tests' own pool: 768 tokens


@functools.lru_cache(maxsize=None)
def _step_fn(model, cfg, ctx_pages: int, prefill: bool):
    """One pass of the model through a WindowCache, jitted a shape."""
    def fn(params, pool, bt, total, ids, positions):
        cache = laguna.serving_cache(
            cfg, pool, bt, total,
            jnp.zeros((1,), jnp.int32) if prefill else None,
            ctx_pages=ctx_pages)
        logits, new = model.apply({"params": params}, ids,
                                  positions=positions, kv_caches=cache)
        return logits[0], new.pool

    return jax.jit(fn)


def _paged(cfg, model, params, seq, passes, decode=0, bucket=None):
    """Prefill `seq` in `passes` (lengths; each padded to `bucket`), then
    `decode` more tokens one at a time (teacher-forced from `seq`'s tail),
    through a WindowCache of one slot: -> logits at every position."""
    pool = {k: jnp.zeros(*sd) for k, sd in laguna.pool_spec(
        cfg, cfg.num_layers, 1 + MP, 16, 1).items()}
    bt = jnp.arange(1, 1 + MP, dtype=jnp.int32)[None]
    out, start = [], 0
    steps = [(n, True) for n in passes] + [(1, False)] * decode
    for n, prefill in steps:
        sb = (bucket or n) if prefill else 1
        ids = np.zeros((1, sb), np.int32)
        ids[0, :n] = seq[start:start + n]
        logits, pool = _step_fn(model, cfg,
                                MP if (prefill and start) else 0, prefill)(
            params, pool, bt, jnp.asarray([start + n], jnp.int32),
            jnp.asarray(ids), (start + jnp.arange(sb))[None])
        out.append(logits[:n])
        start += n
    return jnp.concatenate(out)


# ------------------------------------------------ (a) against the reference
def test_the_layers_are_the_published_kinds_heads_and_ffns_in_runs():
    cfg = laguna.get_config("laguna-xs.2")
    assert cfg.runs == (("full_attention", 1), ("sliding_attention", 3)) * 10
    assert cfg.stack_runs[:3] == (
        (("full_attention", True), 1), (("sliding_attention", False), 3),
        (("full_attention", False), 1))
    assert (cfg.n_window_layers, cfg.n_full_layers, cfg.n_expert_layers) == (
        30, 10, 39)
    assert (cfg.heads("full_attention"), cfg.heads("sliding_attention")) == (
        48, 64)
    assert (cfg.rotary_dim("full_attention"),
            cfg.rotary_dim("sliding_attention")) == (64, 128)
    assert cfg.inv_freq("full_attention").shape == (32,)
    assert cfg.inv_freq("sliding_attention")[1] == pytest.approx(
        10000 ** (-2 / 128))
    # "33.4B-A3B"
    assert abs(cfg.num_params() / 1e9 - 33.44) < 0.01
    assert 2.5 < (cfg.active_params() + 2 * 100352 * 2048) / 1e9 < 3.5
    cut = laguna.get_config("laguna-xs.2", num_layers=5)
    assert laguna.attention_kinds(cut) == ((2, None, 48), (3, 512, 64))
    # a kind whose heads differ from layer to layer is no scan
    with pytest.raises(NotImplementedError, match="one count a kind"):
        laguna.get_config("tiny-laguna", num_attention_heads_per_layer=(
            6, 8, 8, 4, 6, 8, 8, 8))
    # Mellum answers every question with its one shape
    m = mellum.get_config("tiny-mellum")
    assert m.stack_runs == tuple(((k, False), n) for k, n in m.runs)
    assert m.heads("full_attention") == m.heads("sliding_attention") == 4
    assert m.n_expert_layers == m.num_layers and not m.attn_gate


def test_the_trees_parameter_count_at_the_published_widths():
    """5 of 40 layers at every published width, by `jax.eval_shape` (no
    memory): what the configuration's `reduced_why` states."""
    cfg = laguna.get_config("laguna-xs.2", num_layers=5)
    tree = jax.eval_shape(lambda: init_params(
        laguna.serving_model(cfg), jnp.zeros((1, 8), jnp.int32),
        jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert count == cfg.num_params() == 3_869_858_816
    shapes = jax.tree.map(lambda a: a.shape, tree)
    assert shapes["run_00"]["attn"]["qkv_proj"]["kernel"] == (
        1, 2048, (48 + 16) * 128)
    assert shapes["run_01"]["attn"]["qkv_proj"]["kernel"] == (
        3, 2048, (64 + 16) * 128)
    assert shapes["run_01"]["attn"]["gate_proj"] == (3, 2048, 64)
    assert shapes["run_00"]["mlp"]["gate_up_proj"]["kernel"] == (
        1, 2048, 2 * 8192)
    assert shapes["run_02"]["moe"]["experts_gate_up"] == (
        1, 256, 2048, 2 * 512)
    assert shapes["run_02"]["moe"]["router_bias"] == (1, 256)


@pytest.fixture(scope="module")
def forwards(tiny):
    """The program's full forward and the reference's, prompts under,
    across and far past the window (and YaRN's original 64)."""
    _, model, params = tiny
    out = {}
    for n in (24, 100, 640):
        ids = _ids((1, n), seed=n)
        with jax.default_matmul_precision("highest"):
            out[n] = (ids, applied(model, params, ids),
                      _reference(params, ids))
    return out


@pytest.mark.parametrize("n", [24, 100, 640])
def test_the_full_forward_is_the_references_and_not_the_all_full_ones(
        tiny, forwards, n):
    _, _, params = tiny
    ids, got, want = forwards[n]
    assert float(jnp.sqrt((want ** 2).mean())) > 0.3
    assert float(jnp.abs(got - want).max()) < TOL, n
    differs = float(jnp.abs(got - _reference(params, ids, "all-full")).max())
    if n <= WINDOW:
        assert differs < TOL       # inside one window both kinds agree
    else:
        assert differs > 100 * TOL, (n, differs)


@pytest.mark.parametrize("part", PARTS)
def test_the_forward_fails_a_reference_with_one_part_taken_out(
        tiny, forwards, part):
    """The gate (g = 1), the half rotation (the whole head rotated), the
    sliding kind's own theta (the full kind's), the selection bias, the
    scale 2.5, the shared expert, one key more or fewer at the band's
    edge: each moves the logits by far more than rounding."""
    _, _, params = tiny
    ids, got, _ = forwards[100]
    off = _reference(params, ids, part)
    assert float(jnp.abs(got - off).max()) > 50 * TOL, part


@pytest.mark.parametrize("passes, decode, bucket", [
    ((20,), 6, 32),                 # under the window
    ((64,), 10, None),              # one pass across it
    ((32, 32, 40), 8, 64),          # a boundary on the window's edge
    ((48, 16, 64), 8, 64),          # boundaries inside a band
    ((128, 128, 128, 128, 100), 30, 128),   # twenty windows long
], ids=["under", "across", "on-the-edge", "inside-a-band", "far-past"])
def test_resumed_passes_then_decode_through_the_cache_are_the_references(
        tiny, passes, decode, bucket):
    """Logits at every position, fresh and in resumed passes (padded to
    their bucket) whose boundary falls inside a band, then decode through
    the rings and the pages at groups of 3 and 4, past YaRN's original
    length of 64."""
    cfg, model, params = tiny
    n = sum(passes) + decode
    seq = np.asarray(_ids((n,), seed=n))
    with jax.default_matmul_precision("highest"):
        got = _paged(cfg, model, params, seq, passes, decode, bucket)
    want = _reference(params, jnp.asarray(seq)[None])[0]
    assert float(jnp.abs(got - want).max()) < TOL
    if n > 2 * WINDOW:
        for part in ("all-full", "gate", "half-rotation"):
            off = _reference(params, jnp.asarray(seq)[None], part)[0]
            assert float(jnp.abs(got - off).max()) > 50 * TOL, part


def test_the_selection_sown_is_the_references_experts(tiny):
    cfg, model, params = tiny
    ids = _ids((1, 80), seed=9)
    _, sown = applied(model, params, ids, mutable=["selection"])
    got = jnp.concatenate([v["chosen"][0][:, 0] for _, v in sorted(
        sown["selection"].items())])                     # [L, S, 1, E]
    w = ref.weights_from_program_tree(params)
    with jax.default_matmul_precision("highest"):
        _, want = ref.hidden(w, ids[0], PUB, want_selection=True)
        _, unbiased = ref.hidden(_without("bias", w)[0], ids[0], PUB,
                                 want_selection=True)
    # the four sparse layers' choices; the dense layer sows none
    assert got.shape == want.shape == (4, 80, 1, 16)
    assert float((got != want).mean()) < 0.01
    assert (np.asarray(want).sum(-1) == 4).all()
    # and the bias is in the choice
    assert float((got != unbiased).mean()) > 0.02


# ----------------------------------------------- (b) the engine's normal path
@functools.cache
def _engine_params():
    cfg = laguna.get_config("tiny-laguna", **F32)
    return fresh_params(laguna.serving_model(cfg), 1, _seeded)


def _engine(**more):
    """The module's engine of this configuration on the seeded weights (a
    selection bias off zero), renewed."""
    return tiny_engine(**{**CFG, **more}, params=_engine_params())


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _generate(engine, prompts, g):
    out = {f"r{i}": [] for i in range(len(prompts))}
    for i, p in enumerate(prompts):
        engine.add_request(f"r{i}", p, SamplingParams(max_tokens=g,
                                                      temperature=0.0))
    while engine.has_work():
        for d in engine.step():
            out[d.request_id].extend(d.new_token_ids)
    return [out[f"r{i}"] for i in range(len(prompts))]


def _worst_by_the_reference(engine, prompts, emitted, part=None):
    """The largest distance of an emitted token's logit from the
    reference's best at its position, on the engine's own sequence."""
    worst = 0.0
    for p, toks in zip(prompts, emitted):
        seq = jnp.asarray(p + toks[:-1])[None]
        logits = np.asarray(_reference(engine.params, seq, part)[0])
        for k, t in enumerate(toks):
            row = logits[len(p) - 1 + k]
            worst = max(worst, float(row.max() - row[t]))
    return worst


@pytest.fixture(scope="module")
def generated(engine):
    tracing.reset_ring()
    before = engine.stats()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).tolist() for n in (20, 70, 200, 600)]
    return before, prompts, _generate(engine, prompts, 12), engine.stats()


def test_greedy_tokens_through_add_request_and_step_are_the_references(
        engine, generated):
    """Prompts under, across and far past the window, in planned passes
    that resume (the longest is five), decoded together over the slot
    set."""
    before, prompts, emitted, st = generated
    assert _worst_by_the_reference(engine, prompts, emitted) < 2e-4
    assert (st["prefill_resumed_passes_total"]
            - before["prefill_resumed_passes_total"]) >= 5
    assert (st["prefix_reuse_refused_total"]
            - before["prefix_reuse_refused_total"]) == 4
    assert "sliding layers' keys" in st["prefix_reuse_refused_why"]
    # both parts of the pool, by the family's own names: 2 full layers'
    # pages, 3 sliding layers' rings
    spec = laguna.pool_spec(engine.model_cfg, 5, 96, 16, 4)
    assert spec["kv_pages"][0][0] == 2 and spec["win_pages"][0][0] == 3
    assert st["kv_full_pool_bytes"] == 2 * 96 * 2 * 16 * 32 * 4
    assert st["kv_window_pool_bytes"] == 3 * 4 * 2 * 2 * 16 * 32 * 4
    # the four sparse layers' counters moved; the dense layer has none
    assert st["moe_assignments_total"] - before["moe_assignments_total"] == (
        4 * 4 * (sum(map(len, prompts)) + 4 * 11))


@pytest.mark.parametrize("part", PARTS)
def test_the_engines_tokens_fail_a_reference_with_one_part_taken_out(
        engine, generated, part):
    _, prompts, emitted, _ = generated
    # (a token is a coarse reading: the bias and the scale move a best
    # token's margin by 4e-3 and 1e-2 here, the others by 0.1 to 5; the
    # sound engine reads 0 to 2e-4)
    assert _worst_by_the_reference(engine, prompts, emitted, part) > 2e-3


def test_a_sequence_twenty_windows_long_holds_a_window(engine):
    """The sliding layers of a 640-token sequence hold 32 tokens a layer
    whatever its context; the records say each kind's layers AND heads."""
    tracing.reset_ring()
    released = engine.stats()["kv_window_tokens_released_total"]
    before = np.asarray(engine.compute.kv_pages["win_pages"])
    prompt = np.random.default_rng(6).integers(0, 256, 640).tolist()
    emitted = _generate(engine, [prompt], 10)
    assert _worst_by_the_reference(engine, [prompt], emitted) < 1e-3
    fields = tracing.FIELDS["engine.dispatch"]
    recs = [dict(zip(fields, r)) for r in tracing.records("engine.dispatch")]
    assert recs and all(
        (r["window_layers"], r["full_layers"], r["window_heads"],
         r["full_heads"]) == (3, 2, 8, 6) for r in recs)
    for r in recs:
        ends = [end for _, _, end in r["rows"]]
        k = r["k"] if r["kind"] == "decode" else 1
        assert r["window_tokens_held"] == sum(
            min(e + k - 1, WINDOW) for e in ends)
        assert r["full_tokens_held"] == sum(e + k - 1 for e in ends)
    decodes = [r for r in recs if r["kind"] == "decode"]
    assert decodes and all(r["window_tokens_read"] == WINDOW * r["k"]
                           and r["full_tokens_read"] > 640 for r in decodes)
    prefills = [r for r in recs if r["kind"] == "prefill"]
    assert [r["window_tokens_read"] for r in prefills] == [
        128, 160, 160, 160, 160]
    st = engine.stats()
    assert (st["kv_window_tokens_released_total"] - released
            == 640 + 9 - WINDOW)
    rings = np.asarray(engine.compute.kv_pages["win_pages"])
    assert rings.shape[:2] == (3, CFG["max_batch"] * WINDOW // 16)
    used = (rings != before).reshape(3, CFG["max_batch"], -1).any(-1)
    assert used.sum(1).tolist() == [1] * 3, used


def test_a_preempted_request_refills_and_agrees():
    """Two pages short: the decode step preempts a request, which refills
    its pages AND its slot's rings from its tokens; every token of both is
    still the reference's."""
    with scarce(_engine(), 21) as engine:
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 256, n).tolist() for n in (150, 140)]
        emitted = _generate(engine, prompts, 40)
        assert engine.stats()["preempted_total"] >= 1
        assert [len(e) for e in emitted] == [40, 40]
        assert _worst_by_the_reference(engine, prompts, emitted) < 1e-3


def test_a_pass_is_priced_by_each_kinds_pairs_and_heads():
    """`PassCost` over the two kinds at their own head counts: a kind's
    share is its share of the (layer, head) pairs, 96 of 288 for the two
    full layers of 48 and 192 for the three sliding ones of 64; Mellum's
    shares, with one head count, are its layers' to the bit."""
    cfg = laguna.get_config("laguna-xs.2", num_layers=5)
    kinds = laguna.attention_kinds(cfg)
    shares = _kind_shares(kinds)
    assert shares == ((96 / 288, None), (192 / 288, 512))
    m = mellum.get_config("mellum2-12b-a2.5b", num_layers=8)
    assert _kind_shares(mellum.attention_kinds(m)) == (
        (2 / 8, None), (6 / 8, 1024))
    # the counters count a LAYER's visits, whatever its heads
    full = _attn_visits(4096, 8192, 4096, 8192)
    window = _attn_visits(4096, 8192, 4096, 8192, ((1, 512),))
    assert _attn_visits(4096, 8192, 4096, 8192, kinds) == tuple(
        2 * f + 3 * w for f, w in zip(full, window))
    weights, pair = laguna.pass_cost_ratios(cfg)
    assert pair == 288 / cfg.active_params()
    # the weight read: 240 x 3.459 B / 0.338 B tokens
    assert 2400 < 240 * weights < 2500
    by_layers = tuple((n / 5, w) for n, w, _ in kinds)
    costs = [PassCost(2454.0, 3e-4, k)(4096, 4096, 8192)
             for k in (((1.0, None),), by_layers, shares, ((1.0, 512),))]
    # more of the pairs are the window's than of the layers
    assert costs[0] > costs[1] > costs[2] > costs[3]
    # under the floor a prompt is one pass; far over it, passes of the
    # largest bucket
    cost = PassCost(240 * weights, 375 * pair, shares)
    buckets = (512, 1024, 2048, 4096)
    assert plan_passes(2300, buckets, 64, cost) == [4096]
    assert plan_passes(9000, buckets, 64, cost)[:2] == [4096, 4096]
    engine_cost = _engine()._pass_cost
    tiny = laguna.get_config("tiny-laguna")
    assert engine_cost.kinds == _kind_shares(laguna.attention_kinds(tiny))
    assert engine_cost.kinds[0][0] == 12 / 36


def test_what_the_family_cannot_be_given_is_refused_by_name():
    family = model_family("tiny-laguna")
    assert family is laguna and family.RESUMES_PREFILL
    assert family.HEAD_AT_GATHER and model_family("laguna-xs.2") is laguna
    cfg = laguna.get_config("tiny-laguna")
    for option, more in (("spec_lookahead", dict(spec_lookahead=4)),
                         ("tp", dict(tp=2)), ("pp", dict(pp=2))):
        with pytest.raises(NotImplementedError, match="ring a decode slot"):
            refuse(EngineConfig(**{**CFG, **more}), cfg)
    with pytest.raises(NotImplementedError, match="rings would be left"):
        refuse(EngineConfig(**CFG), cfg, handoff=True)
    with pytest.raises(ValueError, match="does not divide the sliding"):
        laguna.pool_spec(cfg, 5, 8, 24, 2)
    with pytest.raises(NotImplementedError, match="two kinds"):
        laguna.serving_model(cfg, 4, True, False)


def test_the_reference_imports_nothing_from_the_program():
    import inspect

    from chipbench.references import dense_decoder

    for module in (ref, dense_decoder):
        assert "ray_tpu" not in inspect.getsource(module).replace(
            "ray_tpu/", "")
    assert callable(ref.next_token_nll) and callable(ref.forward_rows)
