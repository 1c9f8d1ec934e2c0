"""A prompt is prefilled in the passes that cost least
(serve/llm/engine.py: plan_passes): the plan as a pure function over the
benchmark cells' own length cycles, and the engine through `add_request` /
`step()` with a prompt planned as two and as three passes against the same
prompt prefilled whole."""

import json
import os

import numpy as np
import pytest

from chipbench import generator
from ray_tpu.models import jamba, llama, minicpm_sala
from ray_tpu.serve.llm.engine import (_BALANCE_TOKENS, _bucket, EngineConfig,
                                      LLMEngine, PassCost, SamplingParams,
                                      plan_passes)
from ray_tpu.util import tracing

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
CHAT = (128, 256, 512, 1024, 2048)
DOCS = (2048, 4096, 8192)


def _cycle(mix: str, n: int):
    with open(os.path.join(CHIPBENCH, "traffic", mix + ".json")) as f:
        return [int(x) for x in generator.quantile_lengths(
            json.load(f)["prompt_len"], n)]


def _cost(width: int, preset: str = "llama3-8b", **over) -> PassCost:
    """What an engine of `width` tokens of model length gives its plan."""
    weights, scores = llama.pass_cost_ratios(llama.get_config(preset, **over))
    return PassCost(_BALANCE_TOKENS * weights, 4 * _BALANCE_TOKENS * scores,
                    width)


def _bare(floor: float) -> PassCost:
    """The floor alone: no attention term."""
    return PassCost(floor, 0.0, 0)


MISTRAL = dict(vocab_size=32768, hidden_size=4096, intermediate_size=14336,
               num_layers=16, num_heads=32, num_kv_heads=8, head_dim=128)


# ------------------------------------------------------ the plan, alone
def test_the_cost_is_the_balance_times_what_the_family_answers():
    mistral = _cost(2688, **MISTRAL)
    assert mistral.floor == _BALANCE_TOKENS == 240
    # 7650 (query, key) pairs of a context part cost one token's products
    assert 7000 < 1 / mistral.pair < 8500
    # the benchmark's Mixtral (3 layers, all 8 experts) and the whole one
    assert 740 <= _cost(2688, "mixtral-8x7b", num_layers=3).floor <= 800
    assert 840 <= _cost(2688, "mixtral-8x7b").floor <= 900
    weights, scores = minicpm_sala.pass_cost_ratios(
        minicpm_sala.get_config("minicpm-sala"))
    assert weights == 1.0 and 0 < scores < llama.pass_cost_ratios(
        llama.get_config("llama3-8b", **MISTRAL))[1]
    assert not jamba.RESUMES_PREFILL and not hasattr(jamba,
                                                     "pass_cost_ratios")


@pytest.mark.parametrize("bucket, start, ms", [
    (128, 0, 13.8), (256, 0, 14.9), (512, 0, 26.1), (1024, 0, 49.2),
    (2048, 0, 98.4), (128, 256, 18.9), (256, 512, 21.6), (512, 1024, 34.4),
    (1024, 1024, 65.1), (2048, 512, 130.5)])
def test_the_cost_follows_the_passes_timed_on_the_chip(bucket, start, ms):
    """`mistral-7b-v0.3-serve`'s passes as benchmarks/prefill_split_probe.py
    timed them on a TPU v5e (PERF.md section 6, PR 36), dispatch and fetch
    included, against the model at 0.048 ms a token (a fresh 2048 pass):
    within 2.5 ms + 12%, and on the dear side for a resumed pass (its
    extra floor is a bar, not a time)."""
    got = 0.048 * _cost(2688, **MISTRAL)(bucket, start > 0)
    assert abs(got - ms) <= 2.5 + 0.12 * ms + (0.048 * 240 if start else 0)
    assert not start or got >= ms - 2.5


@pytest.mark.parametrize("mix, n, buckets, cost, split", [
    ("chat", 50, CHAT, _cost(2688, **MISTRAL), {
        536: [512, 128], 573: [512, 128], 616: [512, 128],
        1120: [1024, 128], 1326: [1024, 512]}),
    ("docbatch", 16, DOCS, _cost(8320, **MISTRAL), {
        n: [4096, 2048] for n in (4104, 4529, 5090, 5938)}),
    ("chat-mixtral", 238, CHAT, _cost(2688, "mixtral-8x7b", num_layers=3),
     {}),
    ("chat-mixtral", 238, CHAT, _cost(2688, "mixtral-8x7b"), {}),
    # the same chat buckets under a model length of 32k: a resumed pass's
    # context part attends 32k columns, and only the smallest still pays
    ("chat", 50, CHAT, _cost(32768, **MISTRAL), {1120: [1024, 128]}),
])
def test_the_plan_over_a_cells_own_cycle(mix, n, buckets, cost, split):
    """Which prompts of a cell's fixed schedule are split, and how: the
    two Mistral cells' tails are, nothing of Mixtral's is (a pass of its
    model reads 3.2 times the weights a token multiplies)."""
    lens = _cycle(mix, n)
    plans = {x: plan_passes(x, buckets, 16, cost) for x in lens}
    assert {x: p for x, p in plans.items() if len(p) > 1} == split
    assert all(p == [_bucket(x, buckets)] for x, p in plans.items()
               if x not in split)


@pytest.mark.parametrize("buckets, page", [
    (CHAT, 16), (DOCS, 16), ((512, 1024, 2048, 4096), 64),
    ((32, 64, 160), 8), ((24, 48, 96), 16)])
@pytest.mark.parametrize("cost", [
    _bare(0), _bare(4), _bare(60), _bare(240), _bare(765),
    PassCost(240, 1 / 7650, 2688), PassCost(240, 1 / 7650, 42240),
    PassCost(4, 1e-3, 256)], ids=str)
@pytest.mark.parametrize("resumed", [False, True])
def test_every_plan_covers_the_prompt_in_page_aligned_full_passes(
        buckets, page, cost, resumed):
    """Over every length up to past three largest buckets: every pass but
    the last is a full bucket that ends on a page boundary, the last is
    the smallest bucket that holds the rest, and the plan never computes
    more tokens, nor costs more by its own model, than today's (passes of
    the largest, then the one bucket that holds the rest)."""
    largest = buckets[-1]
    step = max(1, largest // 97)
    for n in list(range(1, 3 * largest + 40, step)) + list(buckets):
        plan = plan_passes(n, buckets, page, cost, resumed)
        assert all(b in buckets for b in plan)
        done = sum(plan[:-1])
        assert done < n <= done + plan[-1]
        assert plan[-1] == _bucket(n - done, buckets)
        lead, rest = divmod(n, largest)
        if rest == 0:
            lead, rest = lead - 1, largest
        assert plan[:lead] == [largest] * lead
        assert all(b % page == 0 for b in plan[lead:-1])
        assert sum(plan[lead:]) <= _bucket(rest, buckets)
        tail, first = plan[lead:], resumed or lead > 0
        spent = cost(tail[0], first) + sum(cost(b, True) for b in tail[1:])
        whole = cost(_bucket(rest, buckets), first)
        assert spent < whole if len(tail) > 1 else spent == whole


def test_three_passes_only_where_they_pay():
    chat, docs = _cost(2688, **MISTRAL), _cost(8320, **MISTRAL)
    # 1326 as 1024 + 256 + 128 computes 128 tokens fewer than 1024 + 512 and
    # costs a pass more: two passes; at no floor at all, three
    assert plan_passes(1326, CHAT, 16, chat) == [1024, 512]
    assert plan_passes(1326, CHAT, 16, _bare(0)) == [1024, 256, 128]
    assert plan_passes(1679, CHAT, 16, chat) == [2048]
    assert plan_passes(1679, CHAT, 16, _bare(100)) == [1024, 512, 256]
    # two passes of 2048 are no cheaper than one of 4096, whatever the floor
    assert plan_passes(2300, DOCS, 16, docs) == [4096]
    assert plan_passes(7797, DOCS, 16, docs) == [8192]
    # past the largest bucket: passes of it, then the rest by the same
    # rule, and the more readily as every pass there pays a context part
    sala = PassCost(240, 5.9e-5, 42240)
    assert plan_passes(10283, (512, 1024, 2048, 4096), 64, sala) == [
        4096, 4096, 2048, 512]
    assert plan_passes(8192 + 600, (512, 1024, 2048, 4096), 64, sala) == [
        4096, 4096, 1024]
    # (on the chip a resumed 4096 pass is 388 ms and two of 2048 are 369)
    assert plan_passes(8192 + 4100, DOCS, 16, docs) == [8192, 2048, 2048,
                                                        2048]
    # behind a cached prefix every pass resumes: padding costs its context
    # part too, so 665 tokens split there and not from a fresh start
    assert plan_passes(665, CHAT, 16, chat) == [1024]
    assert plan_passes(665, CHAT, 16, chat, True) == [512, 256]


# ------------------------------------------------------------ the engine
def _config(**over):
    base = dict(model="tiny", dtype="float32", num_pages=64, page_size=8,
                max_model_len=256, max_batch=4,
                prefill_buckets=(16, 32, 64, 128), seed=5)
    return EngineConfig(**{**base, **over})


def _prompt(seed, n, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, n).tolist()


def _generate(engine, prompts, max_tokens):
    out = {}
    for i, p in enumerate(prompts):
        engine.add_request(f"r{i}", p, SamplingParams(max_tokens=max_tokens))
        out[f"r{i}"] = []
    while engine.has_work():
        for d in engine.step():
            out[d.request_id].extend(d.new_token_ids)
    return out


def _pair(floor=4, **over):
    """(an engine that prefills every prompt whole, the same engine with a
    floor at which the tiny buckets split)."""
    whole = LLMEngine(_config(**over))
    split = LLMEngine(_config(**over), params=whole.params)
    assert whole._pass_cost.floor >= 240   # the tiny buckets never pay
    split._pass_cost = _bare(floor)
    return whole, split


def _pools(engine):
    pool = engine.kv_pages
    return pool if isinstance(pool, dict) else {"kv_pages": pool}


@pytest.mark.parametrize("preset", ["tiny", "tiny-moe", "tiny-sala"])
@pytest.mark.parametrize("n, plan", [(70, [64, 16]), (100, [64, 32, 16])])
def test_a_split_prompt_gives_the_whole_prompts_tokens_and_pages(
        preset, n, plan):
    """One prompt planned as two passes, one as three: the greedy tokens
    of the prompt prefilled whole; and where the request ends with its
    prefill, the same pages (the context part merges by log-sum-exp: the
    rounding a prefix hit has always had)."""
    page = 16 if preset == "tiny-sala" else 8
    whole, split = _pair(model=preset, page_size=page)
    assert plan_passes(n, split.config.prefill_buckets, page,
                       split._pass_cost) == plan
    prompts = [_prompt(n, n)]
    assert _generate(split, prompts, 10) == _generate(whole, prompts, 10)
    st = split.stats()
    assert st["prefill_split_prompts_total"] == 1
    assert st["prefill_resumed_passes_total"] == len(plan) - 1
    assert st["prefill_passes_total"] == len(plan)
    assert st["prefill_padded_tokens_total"] == sum(plan) < 128
    ws = whole.stats()
    assert (ws["prefill_split_prompts_total"], ws["prefill_passes_total"],
            ws["prefill_padded_tokens_total"]) == (0, 1, 128)
    whole, split = _pair(model=preset, page_size=page)
    assert _generate(split, prompts, 1) == _generate(whole, prompts, 1)
    for part, got in _pools(split).items():
        np.testing.assert_allclose(got, _pools(whole)[part], rtol=1e-4,
                                   atol=1e-4)


@pytest.mark.parametrize("preset", ["tiny", "tiny-moe"])
def test_a_split_rows_last_pass_shares_no_wave_with_a_fresh_row(preset):
    """A split prompt (70 = 64 + 16) and a fresh one of the same last
    bucket (12 tokens) admitted in one step: the full pass, then the two
    last passes as two dispatches, so that the fresh row runs the program
    with no context part; tokens as each gives alone."""
    whole, split = _pair(model=preset)
    prompts = [_prompt(1, 70), _prompt(2, 12)]
    tracing.reset_ring()
    got = _generate(split, prompts, 8)
    fields = tracing.FIELDS["engine.dispatch"]
    pre = [dict(zip(fields, r)) for r in tracing.records("engine.dispatch")
           if r[1] == "prefill"]
    assert [(r["tokens_padded"], [row[1:] for row in r["rows"]])
            for r in pre] == [(64, [(64, 64)]), (16, [(6, 70)]),
                              (16, [(12, 12)])]
    assert got == _generate(whole, prompts, 8)
    assert split.stats()["prefill_split_prompts_total"] == 1


def test_a_preempted_split_request_finishes_with_the_roomy_engines_tokens():
    """Two split prompts (40 = 32 + 16) that cannot both keep their
    pages: one is preempted, and its folded prompt is planned anew from
    what the prefix cache still holds of it (one resumed pass more)."""
    over = dict(max_model_len=128, max_batch=2)
    prompts = [_prompt(31, 40), _prompt(32, 40)]
    roomy, _ = _pair(**over)
    want = _generate(roomy, prompts, 40)
    tight = LLMEngine(_config(num_pages=17, **over), params=roomy.params)
    tight._pass_cost = _bare(4)
    assert _generate(tight, prompts, 40) == want
    st = tight.stats()
    assert st["preempted_total"] >= 1
    assert st["prefill_split_prompts_total"] >= 2
    assert st["prefill_resumed_passes_total"] >= 3


def test_a_last_pass_whose_padding_runs_past_max_model_len():
    """106 tokens as 64 + 32 + 16 in a model length of 112: the last pass
    covers positions 96-111 and its row's block table ends with them; one
    of 110 tokens (64 + 32 + 16 again) pads up to the very end."""
    over = dict(max_model_len=112, prefill_buckets=(16, 32, 64, 128))
    whole, split = _pair(**over)
    prompts = [_prompt(7, 106), _prompt(8, 110)]
    assert plan_passes(110, (16, 32, 64, 128), 8, _bare(4)) == [64, 32, 16]
    assert _generate(split, prompts, 4) == _generate(whole, prompts, 4)
    assert split.stats()["prefill_split_prompts_total"] == 2


def test_a_family_that_cannot_resume_never_splits():
    engine = LLMEngine(_config(model="tiny-jamba"))
    assert engine._pass_cost is None and not engine._resumes
    engine._pass_cost = _bare(4)       # even so: the plan is never asked
    _generate(engine, [_prompt(3, 70), _prompt(4, 90)], 3)
    st = engine.stats()
    assert (st["prefill_split_prompts_total"], st["prefill_passes_total"],
            st["prefill_resumed_passes_total"]) == (0, 2, 0)


@pytest.mark.parametrize("preset, parts", [
    ("tiny", (0, 32)), ("tiny-moe", (0, 32)), ("tiny-sala", (0, 16)),
    ("tiny-jamba", (0,))])
def test_the_plan_adds_no_program(preset, parts):
    """What warm-up builds is what it built before prompts were split:
    every bucket with and without a context part for a family that
    resumes, one decode program; no key the plan could add."""
    engine = LLMEngine(_config(
        model=preset, page_size=16 if preset == "tiny-sala" else 8))
    rb = engine._wave_rb
    assert engine._warmup_programs(None, True) == [
        ("prefill", (sb, rb, cp)) for sb in (16, 32, 64, 128)
        for cp in parts] + [("decode", (1, engine.max_pages_per_seq))]
    if engine._resumes:
        engine._pass_cost = _bare(4)
    _generate(engine, [_prompt(5, 70), _prompt(6, 90), _prompt(7, 12)], 3)
    assert set(engine.compute.programs) <= {
        (kind,) + key for kind, key in engine._warmup_programs(None, True)}
