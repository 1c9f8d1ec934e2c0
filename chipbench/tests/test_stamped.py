"""The readers of the engine's device stamps (chipbench/stamped.py and the
three `stamped_*` readers) on hand-made flight records, laid out by the
program's `tracing.FIELDS` by NAME; and `stamped.check` on the recorded
trace `test_ring_readers.py` uses, with records made to fit it."""

import os
import time
import types

import pytest

from chipbench import cell as cell_mod
from chipbench import paired, stamped, stats
from chipbench import tracered as t
from chipbench.stats import RequestRecord
from ray_tpu.util import tracing

MS = 1_000_000
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# what the program's records held before PR 37
OLD = {"engine.request": 11, "engine.dispatch": 11, "engine.step": 11}


@pytest.fixture(autouse=True)
def empty_ring():
    tracing.reset_ring()
    yield
    tracing.reset_ring()


def _reader(name):
    return cell_mod.load_module("readers", name).read


def _ctx(**more):
    """A 50 s window that started 60 s ago; the profiler starts at half of
    it, so the part that is read is its first 25 s."""
    lines = []
    ctx = {"runner": types.SimpleNamespace(t0=time.monotonic() - 60.0),
           "seconds": 50.0, "log": lines.append, "records": [],
           "trace": None, "lines": lines,
           "cell": types.SimpleNamespace(
               traffic={"trace": {"start_share": 0.5, "seconds": 4.0}})}
    ctx.update(more)
    return ctx


def _t0_ns(ctx):
    return int(ctx["runner"].t0 * 1e9) + time.time_ns() - time.monotonic_ns()


def _record(ring_kind, old=False, **values):
    """One record of `ring_kind` by field NAME; `old`: cut to what a
    commit before the stamps wrote."""
    rec = tuple(values.get(f) for f in tracing.FIELDS[ring_kind])
    tracing.record(ring_kind, rec[:OLD[ring_kind]] if old else rec)


def _dispatches(t0, n=40, exact_every=1, old=False, seq0=0, prefill_every=0):
    """n programs of 10 ms back to back from the window's first second;
    every `exact_every`-th ends exactly; every `prefill_every`-th is a
    pass of 100 real tokens in a bucket of 128, the others decode."""
    end = t0 + 1000 * MS
    for i in range(n):
        sent = end - 7 * MS
        prefill = prefill_every and i % prefill_every == 0
        _record("engine.dispatch", old, seq=seq0 + i,
                kind="prefill" if prefill else "decode",
                step_dispatched=i, step_harvested=i + 1, dispatch_ns=sent,
                fetch_start_ns=end + 1 * MS, fetch_end_ns=end + 10 * MS,
                rows_padded=1 if prefill else 32,
                tokens_padded=128 if prefill else 32,
                rows=(("a", 100 if prefill else 1, 100),), k=1,
                enqueued_ns=sent + MS // 10, device_start_ns=end,
                device_end_ns=end + 10 * MS,
                end_exact=i % exact_every == 0)
        end += 10 * MS


def _request(ctx, rid, due_s, wait, own, lag, old=False, preemptions=0,
             exact=True):
    """A request due `due_s` into the window, sent 2 ms late, dispatched
    1 ms after it arrived; the three parts in ms; the runner saw its first
    token 0.3 ms after the engine stamped it."""
    t0 = _t0_ns(ctx)
    arrival = t0 + int((due_s + 0.002) * 1e9)
    sent = arrival + 1 * MS
    first = sent + int((wait + own + lag) * MS)
    _record("engine.request", old, request_id=rid, arrival_ns=arrival,
            admitted_ns=arrival + 1000, dispatched_ns=sent,
            first_token_ns=first, finish_ns=first + 500 * MS,
            prompt_tokens=100, cached_tokens=0, output_tokens=20,
            preemptions=preemptions, finish_reason="length",
            device_wait_ns=int(wait * MS), prefill_device_ns=int(own * MS),
            harvest_host_ns=int(lag * MS), parts_exact=exact)
    rec = RequestRecord(rid=rid, due_s=due_s, counted=True,
                        prompt_tokens=100, max_tokens=20)
    rec.sent_s = due_s + 0.002
    rec.first_s = (first - t0) / 1e9 + 0.0003
    rec.finish_s, rec.finish_reason = rec.first_s + 0.5, "length"
    rec.out_tokens = 20
    ctx["records"].append(rec)


def _steps(t0, took_ms, old=False):
    """Steps 0.2 s apart from the window's start, each `took_ms` long,
    all of it in the fetch; the third ended a 40 ms gap of the device."""
    for i, took in enumerate(took_ms):
        start = t0 + (100 + i * 200) * MS
        _record("engine.step", old, seq=i, start_ns=start,
                end_ns=start + int(took * MS), intake_ns=0, admit_ns=0,
                dispatch_prefill_ns=0, dispatch_decode_ns=MS // 2,
                fetch_ns=int(took * MS) - MS, harvest_ns=MS // 2,
                running=7, waiting=0, fetch_blocked=1,
                device_idle_ns=40 * MS if i == 2 else 0)


def test_the_part_before_the_profiler_is_what_is_read():
    ctx = _ctx()
    t0 = _t0_ns(ctx)
    lo, hi = stamped.before_profiler_ns(ctx)
    assert (lo, hi) == pytest.approx((t0, t0 + 25 * 10**9), abs=MS)
    _dispatches(t0)
    for i in range(21):             # first tokens 1 .. 21 s into the window
        _request(ctx, f"r{i}", 1.0 + i, wait=i, own=20 + i, lag=0.5)
    # the backlog behind the profiler's start: not read
    for i in range(5):
        _request(ctx, f"late{i}", 26.0 + i, wait=9000, own=500, lag=300)
    _request(ctx, "again", 3.0, wait=700, own=700, lag=-600, preemptions=1)
    # the host came late to a pass of these two: their parts are bounds
    for i in range(2):
        _request(ctx, f"bound{i}", 4.0 + i, wait=0, own=900, lag=0.5,
                 exact=False)
    read = _reader("stamped_request_percentile")
    assert read(ctx, "device_wait_ns", 50, partition=True) \
        == pytest.approx(10.0)
    assert read(ctx, "prefill_device_ns", 95) == pytest.approx(39.0)
    lines = ctx["lines"]
    assert sum("before the profiler's session are read" in x
               for x in lines) == 1           # judged once a run
    assert any("23 of 29 counted requests" in x and "in 21 of 21" in x
               and "a program of 2 of them" in x for x in lines)
    (p50,) = [x for x in lines if "partition at p50 over 21" in x]
    assert ("ttft 43.800 ; late 2.000 ; queue_wait 1.000 ; device_wait "
            "10.000 ; prefill_device 30.000 ; harvest_host 0.500 ; rest "
            "0.300") in p50
    assert any("rest a request, median 0.300 max 0.300" in x for x in lines)


def test_device_time_a_real_token_and_the_steps_of_the_part_that_is_read():
    ctx = _ctx()
    t0 = _t0_ns(ctx)
    # ten passes of 10 ms and 100 real tokens among 40 programs; the
    # first has no program before it in the ring whose end is its start
    _dispatches(t0, prefill_every=4, exact_every=1)
    assert _reader("stamped_prefill_us_per_token")(ctx) \
        == pytest.approx(100.0)
    (line,) = [x for x in ctx["lines"] if "stamped prefill:" in x]
    assert "9 passes" in line and "900 real tokens (1152 computed)" in line
    # 103 steps in the part that is read, the eleventh stalled; a longer
    # one behind the profiler's start (25.1 s) is not read
    _steps(t0, [12.0] * 10 + [2900.0] + [12.0] * 90 + [60.0, 14.0]
           + [12.0] * 22 + [9000.0, 12.0])
    took = [12.0] * 122 + [14.0, 60.0, 2900.0]
    assert _reader("stamped_step_mean")(ctx) == pytest.approx(
        sum(took) / 125)
    (line,) = [x for x in ctx["lines"] if "the longest is" in x]
    assert "125 before the profiler, ms mean 35.504" in line
    assert f"p99 {stats.percentile(took, 99):.3f}" in line
    assert "seq 10, 2900.000 ms" in line and "fetch 2899.000" in line
    assert "fetch_blocked 1" in line
    assert "running 7 waiting 0; 1 steps over 20 x the median" in line
    assert "nothing enqueued 0.040 s" in line


def test_a_pass_behind_an_upper_bound_does_not_count():
    ctx = _ctx()
    t0 = _t0_ns(ctx)

    def ends(i):                  # every tenth program ends as a bound
        return i % 10 != 9
    end = t0 + 1000 * MS
    for i in range(40):
        # the passes at 10, 20, 30 follow a program that ended as a bound,
        # the one at 0 follows nothing the ring holds
        _record("engine.dispatch", seq=i,
                kind="prefill" if i % 5 == 0 else "decode",
                step_dispatched=i, step_harvested=i + 1,
                dispatch_ns=end - 7 * MS, fetch_start_ns=end + MS,
                fetch_end_ns=end + 10 * MS, rows_padded=1, tokens_padded=128,
                rows=(("a", 100, 100),), k=1, enqueued_ns=end - 6 * MS,
                device_start_ns=end, device_end_ns=end + 10 * MS,
                end_exact=ends(i))
        end += 10 * MS
    assert [r["seq"] for r in stamped.exact_programs(ctx, "prefill")] \
        == [5, 15, 25, 35]


def test_under_80_percent_exact_ends_read_nothing_of_the_timeline():
    ctx = _ctx()
    t0 = _t0_ns(ctx)
    _dispatches(t0, exact_every=2, prefill_every=4)
    _request(ctx, "r0", 2.0, wait=5, own=20, lag=0.5)
    _steps(t0, [12.0, 13.0, 12.0])
    assert _reader("stamped_request_percentile")(
        ctx, "device_wait_ns", 50) is None
    assert _reader("stamped_prefill_us_per_token")(ctx) is None
    assert any("under 80% of the ends are exact" in x for x in ctx["lines"])
    assert any("the host came late to 0 of 10 prefill programs" in x
               for x in ctx["lines"])
    # a step's length needs no exact stamp
    assert _reader("stamped_step_mean")(ctx) == pytest.approx(37.0 / 3)


def test_a_program_from_before_the_stamps_reads_its_steps_and_no_timeline():
    ctx = _ctx()
    t0 = _t0_ns(ctx)
    _dispatches(t0, old=True, prefill_every=4)
    _request(ctx, "r0", 2.0, wait=5, own=20, lag=0.5, old=True)
    _steps(t0, [12.0, 13.0, 12.0], old=True)
    for name, params in (
            ("stamped_request_percentile", {"field": "device_wait_ns"}),
            ("stamped_prefill_us_per_token", {})):
        assert _reader(name)(ctx, **params) is None
    assert any("has device stamps" in x for x in ctx["lines"])
    # its step records have their start and end: read, without the flag
    assert _reader("stamped_step_mean")(ctx) == pytest.approx(37.0 / 3)
    assert any("fetch_blocked None" in x for x in ctx["lines"])


def test_an_empty_ring_and_a_program_without_the_recorder_read_nothing(
        monkeypatch):
    ctx = _ctx()
    for name in ("stamped_prefill_us_per_token", "stamped_step_mean"):
        assert _reader(name)(ctx) is None
    monkeypatch.delattr(tracing, "records")
    ctx = _ctx()
    assert _reader("stamped_request_percentile")(
        ctx, "device_wait_ns") is None
    assert _reader("stamped_step_mean")(ctx) is None
    assert any("no flight recorder" in x for x in ctx["lines"])


def _traced_ctx(lag_ms, jitter_ms=0.0, idle_plane_ms=None):
    """The recorded trace with records made to fit it: every exact end
    `lag_ms` after its module event's, now `jitter_ms` more, now less; the
    third program had finished before its fetch. With `idle_plane_ms` the
    fifth program is taken out of the trace, so the sixth found the device
    idle, and was enqueued that long after its event's start."""
    tr = t.Trace.load(os.path.join(DATA, "mistral7b-chat.trace.json.gz"))
    programs = sorted((s, d) for name, s, d in tr.modules[0]
                      if name.startswith("jit_run_decode("))
    if idle_plane_ms is not None:
        gone = programs.pop(4)
        tr.modules[0] = [e for e in tr.modules[0] if e[1] != gone[0]]
    ctx = _ctx(trace=t.reduce_trace(tr))
    offset = time.time_ns() - 30 * 10**9       # the session began 30 s ago
    for i, (s, d) in enumerate(
            (s, d) for name, s, d in tr.host
            if name == "chipbench.engine.step"):
        _record("engine.step", seq=i, start_ns=s + offset + 25_000,
                end_ns=s + d + offset, running=18, waiting=0,
                fetch_blocked=1, device_idle_ns=0)
    before = None
    for i, (s, d) in enumerate(programs):
        sent = (programs[i - 1][0] if i else s - 60 * MS) + offset
        idle = idle_plane_ms is not None and i == 4
        if idle:
            # dispatched 1.9 ms AFTER its event starts, by the trace's
            # clock: `clockfit.pair`'s own tolerance refuses that
            sent = s + offset + int(idle_plane_ms * MS) - MS // 10
        exact = i != 2
        off = int((lag_ms + jitter_ms * (-1) ** i) * MS)
        end = s + d + offset + (off if exact else 12 * MS)
        _record("engine.dispatch", seq=i, kind="decode", step_dispatched=i,
                step_harvested=i + 1, dispatch_ns=sent,
                fetch_start_ns=s + offset, fetch_end_ns=end, rows_padded=32,
                tokens_padded=32, rows=(("r", 1, 300),), k=1,
                enqueued_ns=sent + MS // 10,
                device_start_ns=max(sent + MS // 10, before or 0),
                device_end_ns=end, end_exact=exact)
        before = end
    return ctx, len(programs)


@pytest.mark.parametrize("lag_ms, jitter_ms, ok", [
    (0.2, 0.0, True),
    (2.5, 0.1, True),      # lag and misplacement, as every chip run has
    (8.0, 0.0, False),     # a sum no fetch and no profiler explains
    (-1.0, 0.0, False),    # a fetch that returned before its program ended
    (3.0, 3.0, False)])    # ends that scatter about their median
def test_check_on_the_recorded_trace_with_records_made_to_fit_it(
        lag_ms, jitter_ms, ok):
    ctx, n = _traced_ctx(lag_ms, jitter_ms)
    got = stamped.check(ctx)
    assert got["programs"] == n and got["ok"] is ok
    assert got["exact_share"] == pytest.approx((n - 1) / n)
    # (the step records start 0.025 ms into their spans: the fit's offset)
    assert got["end_lag_ms"] == pytest.approx(lag_ms - 0.025, abs=0.11)
    assert got["end_median_ms"] == pytest.approx(jitter_ms, abs=0.11)
    assert "plane_ms" not in got
    lines = [x for x in ctx["lines"] if x.startswith("stamped.check")]
    assert any(f"end_exact {n - 1} of {n}" in x for x in lines)
    assert any("ends that are upper bounds: n=1 stamp - event ms median "
               "+11.975" in x for x in lines)
    assert any("starts behind an exactly-ended program: n=" in x
               for x in lines)
    assert any("starting on an idle device: none" in x for x in lines)
    # with the part before the profiler stamped and exact, the check decides
    _dispatches(_t0_ns(ctx), seq0=-100)       # dispatched before the trace's
    assert (stamped.usable(ctx) is not None) is ok
    assert ok or any("nothing is read" in x and "limits 0 to 4.0" in x
                     for x in ctx["lines"])


def test_check_takes_the_planes_offset_from_an_idle_found_program():
    ctx, n = _traced_ctx(2.5, idle_plane_ms=2.0)
    # dispatched 1.9 ms after its event's start: past the older pairing's
    # tolerance, which then pairs nothing; the check has its own
    assert paired.whole_programs(ctx, "decode", "a reader") is None
    got = stamped.check(ctx)
    assert got["ok"] and got["programs"] == n == 8
    assert got["plane_ms"] == pytest.approx(2.0 - 0.025, abs=0.01)
    lines = [x for x in ctx["lines"] if x.startswith("stamped.check")]
    assert any("on an idle device: n=1 stamp - event ms median +1.975" in x
               for x in lines)
    # the length of the program taken out; the stamps' gap opens a lag
    # (2.5 ms) later and closes at the enqueue (2.0 ms into the event)
    assert any("the stamps call 1 of those 1 idle-found" in x
               and "idled 60.975" in x and "nothing enqueued 60.470" in x
               for x in lines), lines
    assert any("the fetch's lag behind a program's end: 0.500 ms" in x
               for x in lines)


def test_a_traced_run_that_cannot_be_checked_reads_nothing():
    tr = t.Trace.load(os.path.join(DATA, "mistral7b-chat.trace.json.gz"))
    ctx = _ctx(trace=t.reduce_trace(tr))
    assert stamped.check(ctx) is None          # no record yet
    _dispatches(_t0_ns(ctx), prefill_every=4)
    _request(ctx, "r0", 2.0, wait=5, own=20, lag=0.5)
    # stamped and exact before the profiler, but no step record places the
    # ring on the trace: the stamps go unchecked, and unread
    assert _reader("stamped_request_percentile")(
        ctx, "device_wait_ns", 50) is None
    assert _reader("stamped_prefill_us_per_token")(ctx) is None
    assert any("could not be checked against the trace" in x
               for x in ctx["lines"])
    # the same records with no trace at all (nothing to check against)
    ctx = _ctx(records=ctx["records"])
    assert _reader("stamped_request_percentile")(
        ctx, "device_wait_ns", 50) == pytest.approx(5.0)
