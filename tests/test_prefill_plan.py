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
from ray_tpu.serve.llm.engine import (_BALANCE_TOKENS, _PAIR_PARAMS, _bucket,
                                      PassCost, SamplingParams, plan_passes)
from ray_tpu.util import tracing

from _engines import new_engine, scarce, tiny_engine

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
CHAT = (128, 256, 512, 1024, 2048)
DOCS = (2048, 4096, 8192)


def _cycle(mix: str, n: int):
    with open(os.path.join(CHIPBENCH, "traffic", mix + ".json")) as f:
        return [int(x) for x in generator.quantile_lengths(
            json.load(f)["prompt_len"], n)]


def _cost(preset: str = "llama3-8b", **over) -> PassCost:
    """What an engine gives its plan, whatever its model length."""
    weights, scores = llama.pass_cost_ratios(llama.get_config(preset, **over))
    return PassCost(_BALANCE_TOKENS * weights, _PAIR_PARAMS * scores)


def _bare(floor: float) -> PassCost:
    """The floor alone: no attention term."""
    return PassCost(floor, 0.0)


MISTRAL = dict(vocab_size=32768, hidden_size=4096, intermediate_size=14336,
               num_layers=16, num_heads=32, num_kv_heads=8, head_dim=128)


# ------------------------------------------------------ the plan, alone
def test_the_cost_is_the_balance_times_what_the_family_answers():
    mistral = _cost(**MISTRAL)
    assert mistral.floor == _BALANCE_TOKENS == 240
    # 19600 (query, key) pairs the kernel visits cost one token's products
    assert 18000 < 1 / mistral.pair < 21000
    # the benchmark's Mixtral (3 layers, all 8 experts) and the whole one
    assert 740 <= _cost("mixtral-8x7b", num_layers=3).floor <= 800
    assert 840 <= _cost("mixtral-8x7b").floor <= 900
    sala = minicpm_sala.get_config("minicpm-sala")
    weights, scores = minicpm_sala.pass_cost_ratios(sala)
    # 8 of its 32 layers attend a context, in plain XLA: a pair there costs
    # 6.5 of the flash kernel's, 16000 pairs one token's products
    assert weights == 1.0 and scores == pytest.approx(
        6.5 * 8 * 32 / sala.num_params())
    assert 14000 < 1 / (_PAIR_PARAMS * scores) < 17000
    assert not jamba.RESUMES_PREFILL and not hasattr(jamba,
                                                     "pass_cost_ratios")


@pytest.mark.parametrize("bucket, start, real, ms", [
    # `mistral-7b-v0.3-serve` (a block table of 2688 columns): whole, half
    # padding, resumed at three contexts
    (128, 0, 128, 13.89), (128, 256, 128, 14.65), (128, 512, 128, 14.54),
    (128, 2432, 128, 15.37), (256, 0, 256, 15.45), (256, 512, 256, 16.61),
    (256, 2304, 256, 17.58), (512, 0, 512, 26.38), (512, 512, 512, 27.93),
    (512, 1024, 512, 28.15), (512, 2048, 512, 29.38), (1024, 0, 1024, 49.07),
    (1024, 0, 512, 47.57), (1024, 384, 1024, 52.34),
    (1024, 1024, 1024, 53.15), (1024, 1536, 1024, 54.17),
    (2048, 0, 2048, 98.61), (2048, 0, 1024, 93.84), (2048, 128, 2048, 104.38),
    (2048, 512, 2048, 104.85),
    # `mistral-7b-v0.3-serve-long` (8320 columns)
    (2048, 0, 2048, 98.38), (2048, 0, 1024, 93.44),
    (2048, 1536, 2048, 109.86), (2048, 4096, 2048, 120.84),
    (2048, 6144, 2048, 129.90), (4096, 0, 4096, 218.48),
    (4096, 0, 2048, 201.56), (4096, 1024, 4096, 237.06),
    (4096, 4096, 4096, 263.30), (8192, 0, 8192, 480.48),
    (8192, 0, 4096, 418.46)])
def test_the_cost_follows_the_passes_timed_on_the_chip(bucket, start, real,
                                                       ms):
    """The two Mistral configurations' passes as
    benchmarks/prefill_split_probe.py timed them on a TPU v5e with the
    lengths in the kernel (PERF.md section 6, PR 39), dispatch and fetch
    included, against the model at 0.047 ms a token: all 31 within 2.5 ms
    + 12%, and on the dear side for a resumed pass (its extra floor is a
    bar, not a time)."""
    got = 0.047 * _cost(**MISTRAL)(bucket, real, start)
    assert abs(got - ms) <= 2.5 + 0.12 * ms + (0.047 * 240 if start else 0)
    assert not start or got >= ms - 2.5


@pytest.mark.parametrize("mix, n, buckets, cost, ctx, split", [
    ("chat", 50, CHAT, _cost(**MISTRAL), 0, {
        536: [512, 128], 573: [512, 128], 616: [512, 128],
        665: [512, 256], 722: [512, 256],
        1120: [1024, 128], 1326: [1024, 512]}),
    ("docbatch", 16, DOCS, _cost(**MISTRAL), 0, {
        n: [4096, 2048] for n in (4104, 4529, 5090, 5938)}),
    ("chat-mixtral", 238, CHAT, _cost("mixtral-8x7b", num_layers=3), 0, {}),
    ("chat-mixtral", 238, CHAT, _cost("mixtral-8x7b"), 0, {}),
    # the same chat cycle behind a cached prefix of 1024 tokens (every
    # pass resumes and attends the prefix too): the same plans
    ("chat", 50, CHAT, _cost(**MISTRAL), 1024, {
        536: [512, 128], 573: [512, 128], 616: [512, 128],
        665: [512, 256], 722: [512, 256], 1120: [1024, 128],
        1326: [1024, 512]}),
])
def test_the_plan_over_a_cells_own_cycle(mix, n, buckets, cost, ctx, split):
    """Which prompts of a cell's fixed schedule are split, and how: the
    two Mistral cells' tails are, nothing of Mixtral's is (a pass of its
    model reads 3.2 times the weights a token multiplies). The model
    length is no part of it: a pass attends the context it has."""
    lens = _cycle(mix, n)
    plans = {x: plan_passes(x, buckets, 16, cost, ctx) for x in lens}
    assert {x: p for x, p in plans.items() if len(p) > 1} == split
    assert all(p == [_bucket(x, buckets)] for x, p in plans.items()
               if x not in split)


def _spent(plan, n, cost, ctx):
    """What `cost` says the plan's passes cost for `n` tokens behind
    `ctx`: every pass but the last full."""
    total = 0.0
    for b in plan:
        real = min(b, n)
        total += cost(b, real, ctx)
        ctx, n = ctx + real, n - real
    return total


@pytest.mark.parametrize("buckets, page", [
    (CHAT, 16), (DOCS, 16), ((512, 1024, 2048, 4096), 64),
    ((32, 64, 160), 8), ((24, 48, 96), 16)])
@pytest.mark.parametrize("cost", [
    _bare(0), _bare(4), _bare(60), _bare(240), _bare(765),
    PassCost(240, 1 / 7650), PassCost(240, 5.9e-5),
    PassCost(4, 1e-3)], ids=str)
@pytest.mark.parametrize("ctx", [0, 1024])
def test_every_plan_covers_the_prompt_in_page_aligned_full_passes(
        buckets, page, cost, ctx):
    """Over every length up to past three largest buckets: every pass but
    the last is a full bucket that ends on a page boundary, the last is
    the smallest bucket that holds the rest, and the plan never computes
    more tokens, nor costs more by its own model, than today's (passes of
    the largest, then the one bucket that holds the rest)."""
    largest = buckets[-1]
    step = max(1, largest // 97)
    for n in list(range(1, 3 * largest + 40, step)) + list(buckets):
        plan = plan_passes(n, buckets, page, cost, ctx)
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
        tail, behind = plan[lead:], ctx + lead * largest
        spent = _spent(tail, rest, cost, behind)
        whole = cost(_bucket(rest, buckets), rest, behind)
        assert spent < whole if len(tail) > 1 else spent == whole


@pytest.mark.parametrize("bucket, real", [(128, 96), (512, 302),
                                          (2048, 8), (2048, 1842)])
def test_a_resumed_pass_costs_by_the_context_it_has(bucket, real):
    """The context term is the pairs the kernel visits: real query blocks
    x the context's blocks. More context costs more, and beyond a block
    edge only; padding of the bucket past a query block costs nothing in
    attention; the memo of one plan cannot leak into another's context."""
    cost = _cost(**MISTRAL)
    at = [cost(bucket, real, ctx) for ctx in (512, 1024, 4096, 8192)]
    assert at == sorted(at) and at[0] < at[1] < at[2] < at[3]
    assert cost(bucket, real, 1024) == cost(bucket, real, 520)
    assert cost(bucket, real, 0) < cost(bucket, real, 16) - cost.floor + 1e-9
    block = min(bucket, 512)
    assert cost(bucket, real, 1024) == cost(
        bucket, -(-real // block) * block, 1024)
    assert cost(bucket, real, 1024) <= cost(bucket, bucket, 1024)
    # one plan's memo is its own: the same length behind other contexts
    for ctx in (0, 1024, 4096):
        assert plan_passes(1326, CHAT, 16, cost, ctx) == [1024, 512]
    # (behind 32k tokens a padded query block is 64 key blocks)
    assert plan_passes(1326, CHAT, 16, cost, 32768) == [1024, 256, 128]
    assert plan_passes(1326, CHAT, 16, cost) == [1024, 512]


def test_three_passes_only_where_they_pay():
    chat = docs = _cost(**MISTRAL)
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
    sala = PassCost(240, 5.9e-5)
    assert plan_passes(10283, (512, 1024, 2048, 4096), 64, sala) == [
        4096, 4096, 2048, 512]
    assert plan_passes(8192 + 600, (512, 1024, 2048, 4096), 64, sala) == [
        4096, 4096, 1024]
    # (the same 6144 tokens and 189 block visits as three passes of 2048,
    # at one resumed pass's bar less)
    assert plan_passes(8192 + 4100, DOCS, 16, docs) == [8192, 4096, 2048]
    # 665 tokens split since the context part is the context's (PR 39): a
    # resumed 256 pass behind 512 tokens is one block more, not 2688 columns
    assert plan_passes(665, CHAT, 16, chat) == [512, 256]
    assert plan_passes(665, CHAT, 16, chat, 512) == [512, 256]


# ------------------------------------------------------------ the engine
BASE = dict(model="tiny", dtype="float32", num_pages=64, page_size=8,
            max_model_len=256, max_batch=4,
            prefill_buckets=(16, 32, 64, 128), seed=5)


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


def _whole(**over):
    """The module's engine of this configuration, renewed: it prefills
    every prompt whole."""
    whole = tiny_engine(**{**BASE, **over})
    assert whole._pass_cost.floor >= 240   # the tiny buckets never pay
    return whole


def _split(floor=4, **over):
    """Its twin (the same seed: the same weights), with a floor at which
    the tiny buckets split."""
    split = tiny_engine(**{**BASE, **over}, twin="split")
    split._pass_cost = _bare(floor)
    return split


def _pair(**over):
    return _whole(**over), _split(**over)


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
    prompts = [_prompt(31, 40), _prompt(32, 40)]
    roomy = _whole()
    want = _generate(roomy, prompts, 40)
    # (16 pages of 8 are what two rows of 80 tokens cannot both keep)
    with scarce(_split(), 16) as tight:
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
    engine = tiny_engine(**{**BASE, "model": "tiny-jamba"})
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
    resumes, one decode program; no key the plan could add. (An engine of
    its own: the module's has the programs of every case before.)"""
    engine = new_engine(**{**BASE, "model": preset,
                           "page_size": 16 if preset == "tiny-sala" else 8})
    rb = engine._wave_rb
    assert engine._warmup_programs(None, True) == [
        ("prefill", (sb, rb, cp)) for sb in (16, 32, 64, 128)
        for cp in parts] + [("decode", (1, engine.max_pages_per_seq))]
    if engine._resumes:
        engine._pass_cost = _bare(4)
    _generate(engine, [_prompt(5, 70), _prompt(6, 90), _prompt(7, 12)], 3)
    assert set(engine.compute.programs) <= {
        (kind,) + key for kind, key in engine._warmup_programs(None, True)}


def test_the_counters_say_what_the_rows_lengths_cut():
    """`prefill_attn_blocks_total` / `_skipped_total`: host arithmetic at
    dispatch over (bucket, real tokens, context, table width), the flash
    kernel's own trip counts. A fresh 1024 bucket of 500 tokens: of its 3
    causal visits of 512 x 512 the padded second query block's 2 are cut.
    700 tokens as 256 + 256 + 256 behind a table of 1024 columns (two key
    blocks of 512): the first pass 1 visit, each resumed one its own 1
    and 1 of the 2 context blocks. Published under the same names as
    `rtpu_llm_*` counters. `_masked_total` (PR 45): of the visits made,
    those that take the kernel's edge body: a real query block's diagonal
    tile, and the context's tile that holds the end of the row's keys
    inside it (256 and 512 columns of context end a block of 512 in the
    middle and on its edge)."""
    from ray_tpu.serve.llm.server import _LLM_WORK_TOTALS

    over = dict(max_model_len=1024, num_pages=300,
                prefill_buckets=(256, 1024))
    whole, split = _pair(**over)
    whole._pass_cost = _bare(float("inf"))      # at this length too
    keys = ("prefill_attn_blocks_total", "prefill_attn_blocks_skipped_total",
            "prefill_resumed_passes_total",
            "prefill_attn_blocks_masked_total")
    assert set(keys) <= set(_LLM_WORK_TOTALS)
    prompts = [_prompt(1, 500)]
    want = _generate(whole, prompts, 2)
    st = whole.stats()
    assert tuple(st[k] for k in keys) == (3, 2, 0, 1)
    assert plan_passes(700, (256, 1024), 8, split._pass_cost) == [256] * 3
    _generate(split, [_prompt(2, 700)], 2)
    st = split.stats()
    assert tuple(st[k] for k in keys) == (1 + 3 + 3, 2, 2, 1 + 2 + 1)
    # the split engine's own 500-token prompt is 256 + 256(244): the same
    # tokens as the whole bucket's
    assert _generate(split, prompts, 2) == want
    st = split.stats()
    assert tuple(st[k] for k in keys) == (7 + 1 + 3, 2 + 1, 3, 4 + 1 + 2)


def test_no_more_visits_pay_the_mask_than_are_made():
    """A mixed batch (fresh rows of several buckets beside split ones,
    contexts that end inside a key block and on its edge): every visit
    that builds a mask is a visit made."""
    split = _split(max_model_len=1024, num_pages=400,
                   prefill_buckets=(128, 256, 1024))
    _generate(split, [_prompt(i, n) for i, n in enumerate(
        (500, 700, 30, 129, 256, 1000, 385))], 2)
    st = split.stats()
    made = (st["prefill_attn_blocks_total"]
            - st["prefill_attn_blocks_skipped_total"])
    assert st["prefill_split_prompts_total"] > 0
    assert 0 < st["prefill_attn_blocks_masked_total"] <= made
    # a fresh pass is its diagonal alone, so some visit runs bare only
    # where a query block has context blocks before its last
    assert st["prefill_attn_blocks_masked_total"] >= (
        st["prefill_passes_total"])


def test_a_sparse_familys_resumed_pass_counts_no_flash_blocks():
    """MiniCPM-SALA attends a context through ops/sparse_attention.py: only
    its fresh pass under the dense length is the flash kernel's."""
    split = _split(model="tiny-sala", page_size=16)
    _generate(split, [_prompt(3, 70)], 2)
    st = split.stats()
    assert st["prefill_resumed_passes_total"] == 1
    assert (st["prefill_attn_blocks_total"],
            st["prefill_attn_blocks_skipped_total"]) == (1, 0)
