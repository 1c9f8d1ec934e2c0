"""The readers of the program's flight recorder, on hand-made records:
each one's arithmetic, the window, dropped records, a program without a
recorder, and the roofline reader on the trace recorded from the chip with
records made to fit it."""

import os
import time
import types

import pytest

from chipbench import cell as cell_mod
from chipbench import flops, kernel_work
from chipbench import tracered as t
from chipbench.stats import RequestRecord
from ray_tpu.util import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


@pytest.fixture(autouse=True)
def empty_ring():
    tracing.reset_ring()
    yield
    tracing.reset_ring()


def _reader(name):
    return cell_mod.load_module("readers", name)


def _ctx(seconds=50.0, **more):
    """A window that started 60 s ago on the monotonic clock."""
    lines = []
    runner = types.SimpleNamespace(t0=time.monotonic() - 60.0)
    ctx = {"runner": runner, "seconds": seconds, "log": lines.append,
           "records": [], "trace": None, "peaks": {}, "lines": lines}
    ctx.update(more)
    return ctx


def _t0_ns(ctx):
    return int(ctx["runner"].t0 * 1e9) + time.time_ns() - time.monotonic_ns()


def _request(rid, arrival, wait_ms, prefill_ms, preemptions=0):
    sent = arrival + int(wait_ms * MS)
    first = sent + int(prefill_ms * MS)
    tracing.record("engine.request", (
        rid, arrival, arrival + 1000, sent, first, first + 500 * MS, 100, 0,
        20, preemptions, "length"))


def test_request_percentile_over_counted_requests_and_the_partition():
    ctx = _ctx()
    t0 = _t0_ns(ctx)
    for i in range(21):
        _request(f"r{i}", t0 + i * 1000 * MS, wait_ms=i, prefill_ms=100 + i)
        rec = RequestRecord(rid=f"r{i}", due_s=float(i), counted=i < 20,
                            prompt_tokens=100, max_tokens=20)
        rec.sent_s = i + 0.050                    # 50 ms late
        rec.first_s = rec.sent_s + (i + 100 + i + 2) / 1e3
        rec.finish_s, rec.finish_reason = rec.first_s + 1.0, "length"
        rec.out_tokens = 20
        ctx["records"].append(rec)
    _request("check0", t0 - 5000 * MS, wait_ms=900, prefill_ms=900)
    read = _reader("ring_request_percentile").read
    assert read(ctx, "arrival_ns", "dispatched_ns", 95) == pytest.approx(
        19 * 0.95)
    assert read(ctx, "dispatched_ns", "first_token_ns", 50,
                partition=True) == pytest.approx(109.5)
    (line,) = [x for x in ctx["lines"] if x.startswith("ttft partition")]
    assert "over 20 requests" in line
    assert "late 50.0 + queue_wait 9.500 + prefill 109.5 = 169.0" in line
    assert "median 2.000 max 2.000" in line


def test_median_ms_keeps_the_window_and_takes_fields_out():
    ctx = _ctx()
    t0 = _t0_ns(ctx)
    # one step before the window, five inside (host 1..5 ms + 60 ms
    # blocked in the fetch), one after
    for i, at_s in enumerate([-1.0, 0.5, 10, 20, 30, 49.5, 50.5]):
        start = t0 + int(at_s * 1e9)
        tracing.record("engine.step", (
            i, start, start + (60 + i) * MS, 0, 0, 0, 0, 60 * MS, 0, 3, 0))
    read = _reader("ring_median_ms").read
    assert read(ctx, "engine.step") == pytest.approx(63.0)
    assert read(ctx, "engine.step", minus=["fetch_ns"]) == pytest.approx(3.0)
    assert read(ctx, "train.step") is None


def test_prefill_pad_pct_counts_real_tokens_of_the_windows_waves():
    ctx = _ctx()
    t0 = _t0_ns(ctx)

    def wave(seq, at_s, kind, tokens_padded, rows):
        at = t0 + int(at_s * 1e9)
        tracing.record("engine.dispatch", (
            seq, kind, seq, seq + 1, at, at + MS, at + 2 * MS, 16,
            tokens_padded, tuple(rows), 1))

    wave(1, -2.0, "prefill", 16 * 2048, [("w", 2000, 2000)])   # the ramp
    wave(2, 1.0, "prefill", 16 * 256, [("a", 200, 200)])
    wave(3, 2.0, "decode", 32, [("a", 1, 201)])
    wave(4, 3.0, "prefill", 16 * 512, [("b", 300, 300), ("c", 500, 756)])
    assert _reader("ring_prefill_pad_pct").read(ctx) == pytest.approx(
        100 * (1 - 1000 / (16 * 768)))


def test_a_ring_that_dropped_records_of_the_window_reads_nothing():
    ctx = _ctx()
    t0 = _t0_ns(ctx)
    cap = tracing.CAPACITY["train.step"]
    for i in range(cap + 5):
        start = t0 + 10 * MS * (i + 1)
        tracing.record("train.step", (i, start, start + MS))
    read = _reader("ring_median_ms").read
    assert read(ctx, "train.step") is None
    assert any("dropped" in x for x in ctx["lines"])
    # dropped, but before the window: the window is whole
    ctx = _ctx()
    ctx["runner"].t0 += 1.0
    assert read(ctx, "train.step") == pytest.approx(1.0)


def test_a_program_without_the_recorder_reads_nothing(monkeypatch):
    monkeypatch.delattr(tracing, "records")
    ctx = _ctx()
    assert _reader("ring_median_ms").read(ctx, "engine.step") is None
    assert _reader("ring_prefill_pad_pct").read(ctx) is None
    assert _reader("ring_request_percentile").read(
        ctx, "arrival_ns", "dispatched_ns", 95) is None
    assert any("no flight recorder" in x for x in ctx["lines"])


def test_kernel_work_counts_tokens():
    w = kernel_work.paged_decode(1000, hq=32, hkv=8, d=128)
    assert w["ops"] == 2 * 2 * 128 * 1000 * 32
    assert w["bytes"] == 2 * 128 * (2 * 1000 * 8 + 2 * 32)
    three = kernel_work.paged_decode_chunk(1000, 3, 32, 8, 128)
    assert three["ops"] == 2 * 2 * 128 * 3003 * 32


def test_roofline_on_the_recorded_trace_with_records_made_to_fit_it():
    tr = t.Trace.load(os.path.join(DATA, "mistral7b-chat.trace.json.gz"))
    cell = cell_mod.load_cell("mistral7b-chat")
    ctx = _ctx(trace=t.reduce_trace(tr), cell=cell,
               peaks=cell_mod.load_peaks("TPU v5 lite"))
    read = _reader("ring_kernel_roofline").read
    params = cell_mod.load_json(os.path.join(
        cell_mod.HERE, "layer_metrics",
        "paged_decode_roofline.tpot.json"))["params"]
    offset = time.time_ns() - 30 * 10**9       # the session began 30 s ago
    spans = [(s, d) for name, s, d in tr.host
             if name == "chipbench.engine.step"]
    programs = sorted((s, d) for name, s, d in tr.modules[0]
                      if name.startswith("jit_run_decode("))
    rows = tuple((f"r{i}", 1, 300 + 10 * i) for i in range(18))
    # no record yet: nothing to fit
    assert read(ctx, **params) is None
    for i, (s, d) in enumerate(spans):
        tracing.record("engine.step", (
            i, s + offset + 25_000, s + d + offset, 0, 0, 0, 0, 0, 0, 18, 0))
    for i, (s, d) in enumerate(programs):
        sent = (programs[i - 1][0] if i else s - 60 * MS) + offset
        tracing.record("engine.dispatch", (
            i, "decode", i, i + 1, sent, s + offset, s + d + offset + 200_000,
            32, 32, rows, 1))
    got = read(ctx, **params)
    # the recorded trace is cut at its window: the first and the last
    # program touch its edges and count as whole
    kernel_ns = sum(own for name, _, own in t.self_times(tr.ops[0])
                    if name.startswith("_decode_call."))
    need = {"ops": 0.0, "bytes": 0.0}
    for _, _, c in rows:
        w = kernel_work.paged_decode(c, 32, 8, 128)
        need = {k: need[k] + 16 * len(programs) * w[k] for k in need}
    roof = flops.roofline_seconds(need, ctx["peaks"])
    assert roof["bound"] == "memory"
    assert got == pytest.approx(100 * roof["seconds"] / (kernel_ns / 1e9))
    assert 0 < got < 100
    assert any("clock fit: record time - trace time" in x
               and "largest residual 0 ns" in x for x in ctx["lines"])
    assert any("9 decode programs paired with records (of 9" in x
               and "memory-bound" in x for x in ctx["lines"])
