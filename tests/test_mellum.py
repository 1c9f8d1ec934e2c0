"""The Mellum family (models/mellum.py) on the CPU at a tiny size: sliding-
window and full attention layers in one stack (a window of 32, two periods
of three sliding layers and a full one, 8 experts of which 2), a rotation a
layer kind, the rings beside the pages, and the engine's normal path, each
against the plain reference of the benchmark
(chipbench/references/window_moe_decoder.py). Logits, not tokens, wherever
a number can be compared; the reference with every layer full (`window=
None`, the benchmark's second control) has to FAIL the same limit.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import window_moe_decoder as ref
from ray_tpu.models import mellum
from ray_tpu.ops import paged_attention as pa
from ray_tpu.serve.llm import EngineConfig, SamplingParams
from ray_tpu.serve.llm.engine import PassCost, plan_passes, refuse
from ray_tpu.serve.llm.stage import model_family
from ray_tpu.util import tracing

from _engines import applied, fresh_params, jitted, scarce, tiny_engine

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
WINDOW = 32
CFG = dict(model="tiny-mellum", dtype="float32", page_size=16, num_pages=96,
           max_model_len=768, max_batch=4, prefill_buckets=(32, 64, 128))
# the tiny preset as the reference reads a configuration
PUB = dict(
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, rms_norm_eps=1e-6, num_experts_per_tok=2,
    norm_topk_prob=True, sliding_window=WINDOW,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    rope_parameters={
        "full_attention": dict(
            rope_type="yarn", rope_theta=500000, factor=16,
            original_max_position_embeddings=64, beta_fast=32, beta_slow=1,
            attention_factor=1.2772588722239782),
        "sliding_attention": dict(rope_type="default", rope_theta=500000)})
# logits differ from the reference's by rounding; from the all-full control
# by the mechanism
TOL = 2e-4


def _seeded(params, seed=2):
    """Norm scales off one and a router whose scores spread: a reference
    that forgot one of them would disagree."""
    key = jax.random.PRNGKey(seed)

    def one(path, a):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        k = jax.random.fold_in(key, sum(map(ord, name)))
        if name.endswith("scale"):
            return 1 + 0.1 * jax.random.normal(k, a.shape)
        if name.endswith("router"):
            return a * 20
        return a

    return jax.tree_util.tree_map_with_path(one, params)


@pytest.fixture(scope="module", autouse=True)
def contexts_walked_in_chunks():
    """A full layer's context wider than four pages (64 tokens: a row of
    16 keys and 16 values in float32, two buffers) is walked in chunks at
    this size, as one wider than 12k tokens is at the published: every
    resumed pass of this module's longer prompts takes that path."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "FLASH_RESIDENT_KV_BYTES", 64 * 2 * 32 * 4)
        yield


@pytest.fixture(scope="module")
def tiny():
    cfg = mellum.get_config("tiny-mellum", **F32)
    model = mellum.MellumModel(cfg)
    params = fresh_params(model, 1, _seeded)
    return cfg, model, params


def _ids(shape, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, 256)


@jitted
def _reference(params, ids, **cfg):
    return ref.forward(ref.weights_from_program_tree(params), ids,
                       {**PUB, **cfg})


MP = 48     # block-table columns of the tests' own pool: 768 tokens


@functools.lru_cache(maxsize=None)
def _step_fn(model, cfg, ctx_pages: int, prefill: bool):
    """One pass of the model through a WindowCache, jitted a shape."""
    def fn(params, pool, bt, total, ids, positions):
        cache = mellum.serving_cache(
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
    through a WindowCache of one slot: -> logits at every position [len,
    V], the pool."""
    pool = {k: jnp.zeros(*sd) for k, sd in mellum.pool_spec(
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
    return jnp.concatenate(out), pool


# ------------------------------------------------ (a) against the reference
def test_the_layers_are_the_published_kinds_in_runs():
    cfg = mellum.get_config("mellum2-12b-a2.5b")
    assert cfg.runs == (("sliding_attention", 3), ("full_attention", 1)) * 7
    assert (cfg.n_window_layers, cfg.n_full_layers) == (21, 7)
    cut = mellum.get_config("mellum2-12b-a2.5b", num_layers=8)
    assert mellum.attention_kinds(cut) == ((2, None), (6, 1024))
    # 12.15 B parameters, 2.44 B a token with the head
    assert abs(cfg.num_params() / 1e9 - 12.15) < 0.01
    assert abs((cfg.active_params() + 2 * 98304 * 2304) / 1e9 - 2.44) < 0.01
    assert abs(cut.num_params() / 1e9 - 3.795) < 0.001


def test_the_full_forward_is_the_references_and_not_the_all_full_ones(tiny):
    """Prompts under, across and far past the window; the control whose
    sliding layers see the whole context differs by the mechanism."""
    _, model, params = tiny
    for n in (24, 100, 640):
        ids = _ids((1, n), seed=n)
        with jax.default_matmul_precision("highest"):
            got = applied(model, params, ids)
        want = _reference(params, ids)
        rms = float(jnp.sqrt((want ** 2).mean()))
        assert rms > 0.3
        assert float(jnp.abs(got - want).max()) < TOL, n
        control = _reference(params, ids, sliding_window=None)
        differs = float(jnp.abs(got - control).max())
        if n <= WINDOW:
            assert differs < TOL       # inside one window both kinds agree
        else:
            assert differs > 100 * TOL, (n, differs)


def test_one_key_too_many_or_too_few_at_the_bands_edge_fails(tiny):
    """A program whose window is off by one key differs from the reference
    by far more than rounding."""
    _, model, params = tiny
    ids = _ids((1, 100), seed=8)
    want = _reference(params, ids)
    for window in (WINDOW - 1, WINDOW + 1):
        off = _reference(params, ids, sliding_window=window)
        assert float(jnp.abs(off - want).max()) > 50 * TOL, window


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
    the rings and the pages, past YaRN's original length of 64."""
    cfg, model, params = tiny
    n = sum(passes) + decode
    seq = np.asarray(_ids((n,), seed=n))
    with jax.default_matmul_precision("highest"):
        got, _ = _paged(cfg, model, params, seq, passes, decode, bucket)
    want = _reference(params, jnp.asarray(seq)[None])[0]
    assert float(jnp.abs(got - want).max()) < TOL
    if n > 2 * WINDOW:
        control = _reference(params, jnp.asarray(seq)[None],
                             sliding_window=None)[0]
        assert float(jnp.abs(got - control).max()) > 100 * TOL


def test_the_selection_sown_is_the_references_experts(tiny):
    cfg, model, params = tiny
    ids = _ids((1, 80), seed=9)
    _, sown = applied(model, params, ids, mutable=["selection"])
    got = jnp.concatenate([v["chosen"][0][:, 0] for _, v in sorted(
        sown["selection"].items())])                     # [L, S, 1, E]
    with jax.default_matmul_precision("highest"):
        _, want = ref.hidden(ref.weights_from_program_tree(params), ids[0],
                             PUB, want_selection=True)
    assert got.shape == want.shape == (8, 80, 1, 8)
    assert float((got != want).mean()) < 0.01
    assert (np.asarray(want).sum(-1) == 2).all()


# ----------------------------------------------- (b) the engine's normal path
def _engine(**more):
    """The module's engine of this configuration, renewed."""
    return tiny_engine(**{**CFG, **more})


@pytest.fixture(scope="module")
def engine():
    """One engine for the tests that only read it."""
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


def _assert_greedy_by_the_reference(engine, prompts, emitted):
    """Every emitted token is the reference's best at its position, or
    within rounding of it, on the engine's own sequence."""
    w = ref.weights_from_program_tree(engine.params)
    for p, toks in zip(prompts, emitted):
        seq = jnp.asarray(p + toks[:-1])[None]
        logits = np.asarray(ref.forward(w, seq, PUB)[0])
        for k, t in enumerate(toks):
            row = logits[len(p) - 1 + k]
            assert row.max() - row[t] < 1e-3, (len(p), k)


def test_greedy_tokens_through_add_request_and_step_are_the_references(
        engine):
    """Prompts under, across and far past the window, in planned passes
    that resume (the longest is five), decoded together over the slot
    set."""
    tracing.reset_ring()
    before = engine.stats()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).tolist() for n in (20, 70, 200, 600)]
    emitted = _generate(engine, prompts, 12)
    _assert_greedy_by_the_reference(engine, prompts, emitted)
    st = engine.stats()
    assert (st["prefill_resumed_passes_total"]
            - before["prefill_resumed_passes_total"]) >= 5
    assert (st["prefix_reuse_refused_total"]
            - before["prefix_reuse_refused_total"]) == 4
    assert "sliding layers' keys" in st["prefix_reuse_refused_why"]
    # both parts of the pool, by the family's own names
    spec = mellum.pool_spec(engine.model_cfg, 8, 96, 16, 4)
    assert spec["kv_pages"][0][0] == 2 and spec["win_pages"][0][0] == 6
    assert st["kv_full_pool_bytes"] == 2 * 96 * 2 * 16 * 32 * 4
    assert st["kv_window_pool_bytes"] == 6 * 4 * 2 * 2 * 16 * 32 * 4


def test_a_sequence_twenty_windows_long_holds_a_window(engine):
    """The sliding layers of a 640-token sequence hold 32 tokens a layer
    whatever its context (the dispatch records say what each kind holds
    and reads), the counter of released tokens moves with every token past
    the window, and the rings are a window a slot: nothing grows."""
    tracing.reset_ring()
    released = engine.stats()["kv_window_tokens_released_total"]
    before = np.asarray(engine.compute.kv_pages["win_pages"])
    prompt = np.random.default_rng(6).integers(0, 256, 640).tolist()
    emitted = _generate(engine, [prompt], 10)
    _assert_greedy_by_the_reference(engine, [prompt], emitted)
    fields = tracing.FIELDS["engine.dispatch"]
    recs = [dict(zip(fields, r)) for r in tracing.records("engine.dispatch")]
    assert recs and all(r["window_layers"] == 6 and r["full_layers"] == 2
                        for r in recs)
    for r in recs:
        ends = [end for _, _, end in r["rows"]]
        k = r["k"] if r["kind"] == "decode" else 1
        assert r["window_tokens_held"] == sum(
            min(e + k - 1, WINDOW) for e in ends)
        assert r["full_tokens_held"] == sum(e + k - 1 for e in ends)
        assert r["window_tokens_held"] <= WINDOW * len(ends)
    decodes = [r for r in recs if r["kind"] == "decode"]
    assert decodes and all(r["window_tokens_read"] == WINDOW * r["k"]
                           and r["full_tokens_read"] > 640 for r in decodes)
    prefills = [r for r in recs if r["kind"] == "prefill"]
    # a resumed pass of 128 reads its own tokens and one window behind them
    assert [r["window_tokens_read"] for r in prefills] == [
        128, 160, 160, 160, 160]
    st = engine.stats()
    # every token past the first window left it: 640 + 9 written - 32
    assert (st["kv_window_tokens_released_total"] - released
            == 640 + 9 - WINDOW)
    assert (sum(r["window_tokens_read"] for r in decodes) * 10
            < sum(r["full_tokens_read"] for r in decodes))
    # (those counters are arithmetic on the rows' lengths.) What the DEVICE
    # holds: the sliding layers' part of the pool is a window a slot and no
    # more, and the 649 tokens went through one slot's 32 rows a layer: the
    # other slots' rings are as they were allocated
    rings = np.asarray(engine.compute.kv_pages["win_pages"])
    assert rings.shape[:2] == (6, CFG["max_batch"] * WINDOW // 16)
    used = (rings != before).reshape(6, CFG["max_batch"], -1).any(-1)
    assert used.sum(1).tolist() == [1] * 6, used


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
        _assert_greedy_by_the_reference(engine, prompts, emitted)


def test_a_pass_is_priced_by_the_pairs_each_kind_makes():
    """`PassCost` over the two kinds: behind 16k tokens a sliding layer's
    pass makes a window's pairs where a full layer's makes the context's,
    so the mixed stack's pass costs less than an all-full stack's and more
    than an all-sliding one's; the counters count the kernel's visits so."""
    from ray_tpu.serve.llm.engine import _attn_visits

    cfg = mellum.get_config("mellum2-12b-a2.5b", num_layers=8)
    kinds = mellum.attention_kinds(cfg)
    full = _attn_visits(4096, 16384, 4096, 16384)
    window = _attn_visits(4096, 16384, 4096, 16384, ((1, 1024),))
    both = _attn_visits(4096, 16384, 4096, 16384, kinds)
    assert both == tuple(2 * f + 6 * w for f, w in zip(full, window))
    assert window[1] * 6 < full[1]
    shares = tuple((n / 8, w) for n, w in kinds)
    costs = [PassCost(900.0, 2e-4, k)(4096, 4096, 16384)
             for k in (((1.0, None),), shares, ((1.0, 1024),))]
    assert costs[0] > costs[1] > costs[2]
    # and plans are made with it
    assert plan_passes(9000, (256, 512, 1024, 2048, 4096), 64,
                       PassCost(900.0, 2e-4, shares))[:2] == [4096, 4096]


def test_what_the_family_cannot_be_given_is_refused_by_name():
    family = model_family("tiny-mellum")
    assert family is mellum and family.RESUMES_PREFILL
    cfg = mellum.get_config("tiny-mellum")
    for option, more in (("spec_lookahead", dict(spec_lookahead=4)),
                         ("tp", dict(tp=2)), ("pp", dict(pp=2))):
        with pytest.raises(NotImplementedError, match="ring a decode slot"):
            refuse(EngineConfig(**{**CFG, **more}), cfg)
    with pytest.raises(NotImplementedError, match="rings would be left"):
        refuse(EngineConfig(**CFG), cfg, handoff=True)
    with pytest.raises(ValueError, match="does not divide the sliding"):
        mellum.pool_spec(cfg, 8, 8, 24, 2)
    with pytest.raises(NotImplementedError, match="two kinds"):
        mellum.serving_model(cfg, 4, True, False)
