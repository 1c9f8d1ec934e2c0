import numpy as np
import pytest

from chipbench import generator
from chipbench.cell import HERE, load_json
import os

CHAT = load_json(os.path.join(HERE, "traffic", "chat.json"))
DOC = load_json(os.path.join(HERE, "traffic", "docbatch.json"))


def _sig(reqs):
    return [(r.rid, r.due_s, tuple(r.prompt_ids), r.max_tokens) for r in reqs]


def test_same_seed_same_schedule_other_seed_other_tokens():
    a = generator.make_schedule(CHAT, 3_000_000_001, 20, 32768)
    b = generator.make_schedule(CHAT, 3_000_000_001, 20, 32768)
    c = generator.make_schedule(CHAT, 5, 20, 32768)
    assert _sig(a) == _sig(b)
    assert _sig(a) != _sig(c)


@pytest.mark.parametrize("mix", [CHAT, DOC], ids=["chat", "docbatch"])
def test_clips_hold(mix):
    reqs = generator.make_schedule(mix, 7, 30, 32768)
    p, o = mix["prompt_len"], mix["output_len"]
    assert reqs
    for r in reqs:
        assert p["min"] <= len(r.prompt_ids) <= p["max"]
        assert o["min"] <= r.max_tokens <= o["max"]
        assert all(0 <= t < 32768 for t in r.prompt_ids[:8])


def test_every_seed_offers_the_same_work_in_the_window():
    runs = [generator.make_schedule(CHAT, s, 40, 32768)
            for s in (1, 2, 2**31 + 5)]

    def window(reqs):
        w = [r for r in reqs if r.counted]
        assert all(-1e-9 <= r.due_s < 40 for r in w)
        return (sorted(len(r.prompt_ids) for r in w),
                sorted(r.max_tokens for r in w))

    first = window(runs[0])
    assert len(first[0]) == round(CHAT["arrivals"]["rate_per_s"] * 40)
    for other in runs[1:]:
        assert window(other) == first


def test_a_seed_rotates_the_cycle_and_keeps_every_neighbour():
    a = [r for r in generator.make_schedule(CHAT, 0, 40, 32768) if r.counted]
    b = [r for r in generator.make_schedule(CHAT, 3, 40, 32768) if r.counted]
    la = [(len(r.prompt_ids), r.max_tokens) for r in a]
    lb = [(len(r.prompt_ids), r.max_tokens) for r in b]
    assert la != lb and lb == la[3:] + la[:3]
    gaps_a = [y.due_s - x.due_s for x, y in zip(a, a[1:])]
    gaps_b = [y.due_s - x.due_s for x, y in zip(b, b[1:])]
    assert gaps_b[:len(gaps_a) - 3] == pytest.approx(gaps_a[3:])
    # the ramp and the tail continue the same cycle
    full = generator.make_schedule(CHAT, 0, 40, 32768)
    ramp = [r for r in full if r.due_s < 0]
    assert ramp and all(not r.counted for r in ramp)
    assert (len(ramp[-1].prompt_ids), ramp[-1].max_tokens) == la[-1]


def test_lognormal_quantiles_have_the_stated_median():
    lens = generator.quantile_lengths(
        {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 32,
         "max": 2048}, 1001)
    assert abs(int(np.median(lens)) - 256) <= 1
    assert lens.min() == 32 and lens.max() == 2048


def test_gaps_sum_to_the_span_and_exponential_quantiles_have_cv_one():
    g = generator.quantile_gaps({"process": "quantile_exponential"}, 2000,
                                50.0)
    assert abs(g.sum() - 50.0) < 1e-9
    assert 0.9 < g.std() / g.mean() < 1.05
    with pytest.raises(ValueError):
        generator.quantile_gaps({"process": "poisson"}, 10, 1.0)


def test_backlog_is_all_due_at_the_ramp_start_and_long_enough():
    reqs = generator.make_schedule(DOC, 9, 40, 32768)
    assert {r.due_s for r in reqs} == {-float(DOC["ramp_s"])}
    assert len(reqs) >= DOC["arrivals"]["max_rate_per_s"] * 40
    block = DOC["block"]
    lens = [len(r.prompt_ids) for r in reqs]
    assert sorted(lens[:block]) == sorted(lens[block:2 * block])
    assert lens[:block] == lens[block:2 * block]


def test_token_batches():
    a = generator.make_token_batches({"batch": 4, "seq": 64}, 3, 5, 32768)
    b = generator.make_token_batches({"batch": 4, "seq": 64}, 3, 5, 32768)
    assert a.shape == (5, 4, 64) and (a == b).all() and a.max() < 32768
    assert not (a[0] == a[1]).all()
