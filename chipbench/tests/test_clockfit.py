"""Placing flight records on a trace: the session's offset from two views
of the same steps, and the dispatch record of each executed program — on
made-up sequences and on the trace recorded from the chip (tests/data/)
with records made to fit it."""

import os

import numpy as np

from chipbench import clockfit
from chipbench import tracered as t

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OFFSET = 1_790_000_000_000_000_000


def _steps(n, seed=0):
    """Start times of n steps: mostly ~60 ms apart, a long prefill wave
    now and then, so no two stretches look alike."""
    rng = np.random.default_rng(seed)
    gaps = rng.integers(55_000_000, 65_000_000, n)
    gaps[rng.integers(0, n, n // 10)] += rng.integers(
        100_000_000, 1_800_000_000, n // 10)
    return np.cumsum(gaps)


def test_fit_finds_the_one_alignment_and_its_residual():
    trace_clock = _steps(400)
    records = trace_clock + OFFSET + 40_000       # the span opens first
    jitter = np.random.default_rng(1).integers(-20_000, 20_000, 60)
    spans = trace_clock[170:230] + jitter
    fit, why = clockfit.fit(spans, records)
    assert why == "ok" and fit.first == 170 and fit.spans == 60
    assert abs(fit.offset_ns - (OFFSET + 40_000)) <= 20_000
    assert fit.residual_ns <= 40_000


def test_fit_refuses_no_alignment_many_alignments_and_too_few_spans():
    trace_clock = _steps(400)
    records = trace_clock + OFFSET
    spans = trace_clock[170:230].copy()
    spans[30:] += 5_000_000                       # a step the ring lacks
    fit, why = clockfit.fit(spans, records)
    assert fit is None and why.startswith("0 alignments")
    even = np.arange(100) * 60_000_000            # every stretch alike
    fit, why = clockfit.fit(even[:10], even + OFFSET)
    assert fit is None and why.startswith("91 alignments")
    assert clockfit.fit(spans[:2], records)[0] is None
    assert clockfit.fit(spans, records[:10])[0] is None


def _dispatch(seq, kind, sent, got, rows=()):
    return {"seq": seq, "kind": kind, "dispatch_ns": int(sent),
            "fetch_end_ns": int(got), "rows": tuple(rows), "k": 1}


def test_pair_follows_dispatch_order_across_kinds():
    # device: D P D D, back to back; two decode dispatches queued ahead
    events = [("decode", 0, 60), ("prefill", 60, 900), ("decode", 960, 60),
              ("decode", 1020, 60)]
    ms = 1_000_000
    events = [(k, s * ms, d * ms) for k, s, d in events]
    recs = [_dispatch(1, "decode", -130 * ms, -59 * ms),
            _dispatch(2, "decode", -61 * ms, 61 * ms),
            _dispatch(3, "prefill", -1 * ms, 961 * ms),
            _dispatch(4, "decode", 0, 1021 * ms),
            _dispatch(5, "decode", 962 * ms, 1081 * ms),
            _dispatch(6, "decode", 1022 * ms, 1141 * ms)]
    for r in recs:
        r["dispatch_ns"] += OFFSET
        r["fetch_end_ns"] += OFFSET
    paired, why = clockfit.pair(events, recs, OFFSET)
    assert [r["seq"] for r in paired] == [2, 3, 4, 5], why
    # a record dispatched after its program started cannot be its cause
    recs[1]["dispatch_ns"] += 70 * ms
    assert clockfit.pair(events, recs, OFFSET)[0] is None
    assert clockfit.pair(events, recs[:3], OFFSET)[0] is None


def test_pair_prefers_the_closest_fetch_when_the_device_idles():
    ms = 1_000_000
    events = [("decode", 1000 * ms, 10 * ms)]
    recs = [_dispatch(1, "decode", OFFSET + 900 * ms, OFFSET + 1011 * ms),
            _dispatch(2, "decode", OFFSET + 990 * ms, OFFSET + 1500 * ms)]
    paired, _ = clockfit.pair(events, recs, OFFSET)
    assert [r["seq"] for r in paired] == [1]


def test_recorded_chat_trace_pairs_with_records_made_to_fit_it():
    tr = t.Trace.load(os.path.join(DATA, "mistral7b-chat.trace.json.gz"))
    spans = [s for name, s, _ in tr.host if name == "chipbench.engine.step"]
    # the ring holds the whole run's steps: 40 before the traced ones, 40
    # after, on the recorder's clock, each opening 30 us into its span
    before = spans[0] - np.cumsum(_steps(40, 2)[::-1])[::-1]
    after = spans[-1] + _steps(40, 3)
    records = np.concatenate([before, spans, after]) + OFFSET + 30_000
    fit, why = clockfit.fit(spans, records)
    assert why == "ok" and fit.first == 40
    assert fit.offset_ns == OFFSET + 30_000 and fit.residual_ns == 0
    programs = sorted((("decode", s, d) for name, s, d in tr.modules[0]
                       if name.startswith("jit_run_decode(")),
                      key=lambda e: e[1])
    assert len(programs) == 9
    # two dispatches in flight: record i is sent as program i-1 starts and
    # fetched 0.2 ms after program i ends; two more before, two after
    starts = [programs[0][1] - 120_000_000, programs[0][1] - 60_000_000] \
        + [e[1] for e in programs] + [programs[-1][1] + 61_000_000]
    ends = [programs[0][1] - 60_000_000, programs[0][1]] \
        + [e[1] + e[2] for e in programs] \
        + [programs[-1][1] + 122_000_000] * 2
    recs = [_dispatch(100 + i, "decode", starts[max(i - 1, 0)] + fit.offset_ns,
                      ends[i] + 200_000 + fit.offset_ns)
            for i in range(len(starts))]
    recs.append(_dispatch(100 + len(starts), "decode", recs[-1]["dispatch_ns"],
                          recs[-1]["fetch_end_ns"]))
    paired, why = clockfit.pair(programs, recs, fit.offset_ns)
    assert [r["seq"] for r in paired] == list(range(102, 111)), why
