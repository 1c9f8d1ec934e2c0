"""The flight recorder inside LLMEngine and ShardedTrainer.step
(ray_tpu/util/tracing.py): what each record kind holds, that the ring is
bounded and counts what it drops, that a region lies on the profiler's
clock, and that /metrics and stats() say what the records say.

CPU, tiny engine; no TPU library is loaded at import.
"""

import gc
import json
import os

import numpy as np
import pytest

from benchmarks.recorder_cost import Handle, StubEngine
from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.serve.llm.engine import PassCost
from ray_tpu.util import tracing

from _engines import new_engine, renewed, scarce, tiny_engine

ENGINE_CFG = dict(
    model="tiny", page_size=8, num_pages=64, max_model_len=128,
    max_batch=4, prefill_buckets=(16, 32, 64, 128), dtype="float32",
    model_overrides={"vocab_size": 512},
)


def _dicts(kind):
    return [dict(zip(tracing.FIELDS[kind], rec))
            for rec in tracing.records(kind)]


def _run(engine, max_steps=600):
    for _ in range(max_steps):
        if not engine.has_work():
            return
        engine.step()
    raise AssertionError("the engine did not run dry")


def _prompts(n, lo=9, step=7, seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, 500, lo + step * i)) for i in range(n)]


@pytest.fixture(scope="module")
def engine():
    """One engine for the cases that only read what it records (its
    programs compile once); every case starts from an empty ring."""
    return tiny_engine(**ENGINE_CFG)


@pytest.fixture(autouse=True)
def empty_ring():
    tracing.reset_ring()
    yield
    tracing.reset_ring()


def test_request_timeline_is_ordered_and_every_exit_leaves_one_record(
        engine):
    import time

    for i, p in enumerate(_prompts(3, seed=1)):
        engine.add_request(f"ok{i}", p, SamplingParams(max_tokens=5))
    engine.add_request("gone", _prompts(1, seed=2)[0],
                       SamplingParams(max_tokens=50))
    engine.add_request("late", _prompts(1, seed=3)[0],
                       SamplingParams(max_tokens=5),
                       deadline=time.time() - 1.0)
    engine.step()
    engine.abort("gone")
    _run(engine)
    recs = {r["request_id"]: r for r in _dicts("engine.request")}
    assert len(tracing.records("engine.request")) == 5 == len(recs)
    for i in range(3):
        r = recs[f"ok{i}"]
        assert (r["arrival_ns"] <= r["admitted_ns"] <= r["dispatched_ns"]
                <= r["first_token_ns"] <= r["finish_ns"])
        assert r["finish_reason"] == "length" and r["output_tokens"] == 5
        assert r["prompt_tokens"] == 9 + 7 * i and r["preemptions"] == 0
    assert recs["gone"]["finish_reason"] == "aborted"
    assert recs["late"]["finish_reason"] == "expired"
    assert recs["late"]["dispatched_ns"] is None
    assert recs["late"]["arrival_ns"] <= recs["late"]["finish_ns"]


def test_prefill_records_count_real_and_padded_tokens(engine):
    shared = _prompts(1, lo=24, seed=4)[0]
    prompts = _prompts(3, seed=5) + [shared + [7]]
    for i, p in enumerate(prompts):
        engine.add_request(f"a{i}", p, SamplingParams(max_tokens=3))
    _run(engine)
    # the same 24 tokens again: three full pages come from the cache
    engine.add_request("twin", shared + [9], SamplingParams(max_tokens=3))
    _run(engine)
    reqs = _dicts("engine.request")
    prefills = [d for d in _dicts("engine.dispatch")
                if d["kind"] == "prefill"]
    assert sum(r["cached_tokens"] for r in reqs) == 24
    real = sum(q for d in prefills for _, q, _ in d["rows"])
    assert real == sum(r["prompt_tokens"] - r["cached_tokens"]
                       for r in reqs)
    for d in prefills:
        # a wave computes its real rows and no other
        rb = d["rows_padded"]
        assert rb == len(d["rows"]) <= engine._wave_rb
        assert d["tokens_padded"] % rb == 0
        bucket = d["tokens_padded"] // rb
        assert bucket in ENGINE_CFG["prefill_buckets"]
        assert all(q <= bucket and q <= ctx for _, q, ctx in d["rows"])
    # the twin's row attends to its cached prefix too
    (row,) = [r for d in prefills for r in d["rows"] if r[0] == "twin"]
    assert row[1:] == (1, 25)
    assert d["step_dispatched"] <= d["step_harvested"]
    assert d["dispatch_ns"] <= d["fetch_start_ns"] <= d["fetch_end_ns"]


# ------------------------------- a wave computes its real rows only
WIDE_CFG = dict(ENGINE_CFG, max_batch=8, prefill_buckets=(16, 32))


def _waves(n, engine, tag, max_tokens=3, seed=11):
    """Send n prompts of one length bucket at once and run dry: the
    (rows the program computed, real rows) of each prefill dispatch,
    and every request's tokens."""
    tracing.reset_ring()
    for i, p in enumerate(_prompts(n, lo=20, step=1, seed=seed)):
        engine.add_request(f"{tag}-{i}", p,
                           SamplingParams(max_tokens=max_tokens))
    out = {}
    for _ in range(600):
        if not engine.has_work():
            break
        for d in engine.step():
            out.setdefault(d.request_id, []).extend(d.new_token_ids)
    return [(d["rows_padded"], len(d["rows"]))
            for d in _dicts("engine.dispatch")
            if d["kind"] == "prefill"], out


@pytest.fixture(scope="module")
def wide():
    """max_batch 8: waves of at most 4 rows. (An engine of its own: the
    cases read which programs their traffic built.)"""
    return new_engine(**WIDE_CFG)


@pytest.fixture(scope="module")
def wide_spec():
    """`wide` with speculative decoding on and a draft for every slot
    (two tokens the model will not have chosen: all are rejected)."""
    engine = new_engine(**WIDE_CFG, spec_lookahead=3)
    engine._prompt_lookup_draft = lambda req, max_len: [511, 510][:max_len]
    return engine


@pytest.mark.parametrize("n, waves, spec", [
    (1, [1], False), (2, [2], False), (3, [3], False), (4, [4], False),
    (5, [4, 1], False), (6, [4, 2], False), (7, [4, 3], False),
    (8, [4, 4], False), (3, [3], True)])
def test_a_prefill_wave_computes_its_requests_and_no_padding_row(
        wide, wide_spec, n, waves, spec):
    """One request computes one row; a burst beyond the wave size goes
    in waves of the wave size and a remainder of its own size. A
    speculative verify is the same row loop: it computes the slots that
    have a draft, and the tokens are plain greedy decode's."""
    wide = wide_spec if spec else wide
    assert wide._wave_rb == 4
    before = wide.stats()
    # a seed of its own: no page of another case's prompts is cached
    got, out = _waves(n, wide, f"w{n}", seed=100 + n)
    assert got == [(w, w) for w in waves]
    assert len(out) == n and all(len(t) == 3 for t in out.values())
    after = wide.stats()
    assert (after["prefill_padded_tokens_total"]
            - before["prefill_padded_tokens_total"]) == 32 * n
    assert (after["prefill_tokens_total"]
            - before["prefill_tokens_total"]) == sum(
                20 + i for i in range(n))
    # every row count is ONE program: the wave-sized one of the bucket
    assert {k for k in wide.compute.programs if k[0] == "prefill"} == {
        ("prefill", 32, 4, 0)}
    verifies = [d for d in _dicts("engine.dispatch") if d["kind"] == "spec"]
    assert bool(verifies) == spec
    for d in verifies:
        # draft + pending token = 3 positions, in the bucket of 16
        assert 1 <= d["rows_padded"] == len(d["rows"]) <= n
        assert d["tokens_padded"] == d["rows_padded"] * 16
        assert all(q == 3 for _, q, _ in d["rows"])
    if spec:
        assert ("verify", 16, 4) in wide.compute.programs
        assert after["spec_drafted_total"] > after["spec_accepted_total"] == 0
        plain = tiny_engine(**WIDE_CFG)
        assert _waves(n, plain, f"w{n}", seed=100 + n)[1] == out


@pytest.mark.parametrize("chunk", [0, 16])
def test_greedy_tokens_are_the_same_alone_and_inside_a_full_wave(chunk):
    """Both schedulers pass through the row loop: a request's tokens do
    not depend on how many rows shared its wave."""
    engine = tiny_engine(**WIDE_CFG, prefill_chunk_tokens=chunk)
    rows, full = _waves(4, engine, "g", max_tokens=6)
    # whole prompts go four to a wave; a budget of 16 tokens a step is
    # shared by up to three rows
    assert max(real for _, real in rows) == (3 if chunk else 4)
    # alone, on a fresh engine (nothing cached): one row every dispatch
    engine = tiny_engine(**WIDE_CFG, prefill_chunk_tokens=chunk)
    rows, alone = _waves(1, engine, "g", max_tokens=6)
    assert set(rows) == {(1, 1)}
    assert full["g-0"] == alone["g-0"] and len(alone["g-0"]) == 6


@pytest.fixture(scope="module")
def warmed():
    """A warmed engine, the number warmup() returned, and a count of
    the programs JAX itself builds from then on. (An engine of its own:
    what a first use builds is the claim.)"""
    import jax.monitoring

    engine = new_engine(**WIDE_CFG)
    n = engine.warmup()
    compiles = [0]

    def on_duration(name, secs, **_):
        if name.endswith("/backend_compile_duration"):
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return engine, n, compiles


def test_warmup_builds_one_program_per_length_bucket_and_prefix_variant(
        warmed):
    engine, n, _ = warmed
    lengths = WIDE_CFG["prefill_buckets"]
    assert n == len(lengths) * 2 + 1
    assert set(engine.compute.programs) == {
        ("prefill", sb, engine._wave_rb, cp) for sb in lengths
        for cp in (0, engine.max_pages_per_seq)} | {
            ("decode",) + engine._decode_shape_key()}
    assert engine.stats()["programs_built_total"] == n
    # a warm-up dispatch has no real row: nothing was written or counted
    assert engine.stats()["prefill_padded_tokens_total"] == 0


@pytest.mark.parametrize("n", range(1, WIDE_CFG["max_batch"] + 1))
def test_no_program_is_built_under_traffic_after_warmup(warmed, n):
    """Every group size from one request to a full batch, fresh prompts
    and then a prefix hit: no `engine.program_built` record, and JAX
    compiles nothing."""
    engine, _, compiles = warmed
    built = engine.stats()["programs_built_total"]
    compiled = compiles[0]
    got, out = _waves(n, engine, f"t{n}", seed=20 + n)
    assert len(out) == n and sum(real for _, real in got) == n
    # the same prompts again: their full pages come from the cache, so
    # the waves take the programs with a prefix part
    again, _ = _waves(n, engine, f"u{n}", seed=20 + n)
    assert sum(real for _, real in again) == n
    assert not tracing.records("engine.program_built")
    assert engine.stats()["programs_built_total"] == built
    assert compiles[0] == compiled


def test_decode_rows_carry_the_context_they_attend_to(engine):
    prompts = _prompts(2, seed=6)
    for i, p in enumerate(prompts):
        engine.add_request(f"d{i}", p, SamplingParams(max_tokens=6))
    _run(engine)
    plen = {f"d{i}": len(p) for i, p in enumerate(prompts)}
    seen = {rid: [] for rid in plen}
    decodes = [d for d in _dicts("engine.dispatch") if d["kind"] == "decode"]
    assert decodes
    for d in decodes:
        assert d["rows_padded"] == ENGINE_CFG["max_batch"]
        assert d["tokens_padded"] == d["rows_padded"] * d["k"]
        for rid, q, ctx in d["rows"]:
            assert q == d["k"] == 1
            seen[rid].append(ctx)
    for rid, ctxs in seen.items():
        # the first decode step attends to the prompt and the token the
        # prefill sampled; each later one to one token more. A chunk
        # dispatched ahead of a stop is recorded too, so at least the 5
        # steps whose tokens were kept
        assert ctxs[:5] == [plen[rid] + 1 + j for j in range(5)]


def test_step_phases_fit_inside_the_step(engine):
    for i, p in enumerate(_prompts(3, seed=7)):
        engine.add_request(f"s{i}", p, SamplingParams(max_tokens=4))
    _run(engine)
    steps = _dicts("engine.step")
    assert [s["seq"] for s in steps] == list(
        range(steps[0]["seq"], steps[0]["seq"] + len(steps)))
    phases = ("intake_ns", "admit_ns", "dispatch_prefill_ns",
              "dispatch_decode_ns", "fetch_ns", "harvest_ns")
    for s in steps:
        assert all(s[p] >= 0 for p in phases)
        assert sum(s[p] for p in phases) <= s["end_ns"] - s["start_ns"]
    assert any(s["dispatch_prefill_ns"] > 0 for s in steps)
    assert any(s["fetch_ns"] > 0 for s in steps)
    assert steps[-1]["running"] == 0 and steps[-1]["waiting"] == 0


def test_flightrecords_reads_a_runs_tail_from_its_records(
        engine, tmp_path, capsys):
    """benchmarks/flightrecords.py: `dump` keeps the rings beside the
    benchmark's request records, `read` names the request behind the
    `ttft_ms` tail with its parts and the programs by shape."""
    from benchmarks import flightrecords

    prompts = _prompts(3, seed=9)
    for i, p in enumerate(prompts):
        engine.add_request(f"f{i}", p, SamplingParams(max_tokens=4))
    _run(engine)
    recs = {r["request_id"]: r for r in _dicts("engine.request")}
    t0 = min(r["arrival_ns"] for r in recs.values())
    rel = lambda ns: (ns - t0) / 1e9  # noqa: E731
    requests = [{"rid": rid, "due_s": rel(r["arrival_ns"]), "counted": True,
                 "prompt_tokens": r["prompt_tokens"],
                 "sent_s": rel(r["arrival_ns"]),
                 "first_s": rel(r["first_token_ns"])}
                for rid, r in recs.items()]
    requests.append({"rid": "never", "due_s": 0.0, "counted": True,
                     "prompt_tokens": 5, "sent_s": None, "first_s": None})
    path = tmp_path / "run.json"
    path.write_text(json.dumps(flightrecords.dump(requests, t0, 3600.0)))
    flightrecords.read(str(path))
    out = capsys.readouterr().out
    slowest = max(recs, key=lambda rid: recs[rid]["first_token_ns"]
                  - recs[rid]["arrival_ns"])
    lines = out.splitlines()
    assert "3 counted" in lines[0]
    parts = recs[slowest]
    assert lines[1].split()[0] == slowest
    assert f"exact {parts['parts_exact']} due" in lines[1]
    assert f"prefill {parts['prefill_device_ns'] / 1e6:5.1f}" in lines[1]
    assert f"program ('decode', {ENGINE_CFG['max_batch']}, " in out
    assert sum("program ('prefill', " in ln for ln in lines) == len(
        {(d["rows_padded"], d["tokens_padded"])
         for d in _dicts("engine.dispatch") if d["kind"] == "prefill"})
    assert "thread gaps in the window (s, ms): []" in out


def test_preemption_is_counted_and_gets_a_new_dispatch_time():
    with scarce(tiny_engine(**ENGINE_CFG), 11) as eng:
        first_dispatch = {}
        for i, p in enumerate(_prompts(2, lo=17, step=0, seed=8)):
            eng.add_request(f"p{i}", p, SamplingParams(max_tokens=40))
        for _ in range(900):
            if not eng.has_work():
                break
            eng.step()
            for req in eng.running:
                first_dispatch.setdefault(req.request_id, req.dispatched_ns)
        assert eng.stats()["preempted_total"] >= 1
        recs = {r["request_id"]: r for r in _dicts("engine.request")}
        assert sum(r["preemptions"] for r in recs.values()) \
            == eng.stats()["preempted_total"]
        for rid, r in recs.items():
            # folded output tokens are not prompt tokens
            assert r["prompt_tokens"] == 17 and r["output_tokens"] == 40
            if r["preemptions"]:
                assert r["dispatched_ns"] > first_dispatch[rid]


def test_ring_holds_its_capacity_and_counts_what_it_drops():
    cap = tracing.CAPACITY["train.step"]
    assert sum(tracing.CAPACITY.values()) == 65536
    for i in range(cap + 10):
        tracing.record("train.step", (i, i, i + 1))
    held = tracing.records("train.step")
    assert len(held) == cap and held[0][0] == 10 and held[-1][0] == cap + 9
    assert tracing.dropped("train.step") == 10
    assert tracing.dropped("engine.step") == 0
    assert tracing.appended("train.step") == cap + 10
    assert [r[0] for r in tracing.records("train.step", since=cap + 7)] \
        == [cap + 7, cap + 8, cap + 9]
    assert tracing.records("train.step", since=cap + 10) == []


def test_a_region_lies_on_the_profilers_clock(tmp_path):
    """jax's profiler counts the recorder's clock from the start of its
    session: annotation start minus region start is one constant."""
    import glob
    import time

    import jax

    try:
        from jax.profiler import ProfileData
    except ImportError as e:
        pytest.skip(f"jax.profiler.ProfileData cannot be imported: {e}")
    starts = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            with tracing.region(f"rtpu.test.region{i}") as r:
                time.sleep(0.002)
            starts.append(r.start_ns)
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("rtpu.test.region"):
                    seen[e.name] = e.start_ns
    assert sorted(seen) == [f"rtpu.test.region{i}" for i in range(3)]
    offsets = [starts[i] - seen[f"rtpu.test.region{i}"] for i in range(3)]
    assert max(offsets) - min(offsets) < 1_000_000, offsets


def test_stats_totals_and_llm_metrics_equal_the_records_sums(engine):
    from ray_tpu.serve.llm.server import EngineDriverMixin
    from ray_tpu.util import metrics

    before = engine.stats()
    driver = EngineDriverMixin()
    driver.engine = engine
    driver._init_driver()
    driver._publish_llm_metrics(before)
    counters0 = metrics.snapshot("rtpu_llm_")
    for i, p in enumerate(_prompts(3, seed=9)):
        engine.add_request(f"m{i}", p, SamplingParams(max_tokens=4))
    _run(engine)
    after = engine.stats()
    moved = {k: after[k] - before[k] for k in after if k.endswith("_total")}
    dispatches, reqs = _dicts("engine.dispatch"), _dicts("engine.request")
    prefills = [d for d in dispatches if d["kind"] == "prefill"]
    decodes = [d for d in dispatches if d["kind"] == "decode"]
    assert moved["steps_total"] == len(tracing.records("engine.step"))
    assert moved["prefill_dispatches_total"] == len(prefills)
    assert moved["decode_dispatches_total"] == len(decodes)
    assert moved["prefill_tokens_total"] == sum(
        q for d in prefills for _, q, _ in d["rows"])
    assert moved["prefill_padded_tokens_total"] == sum(
        d["tokens_padded"] for d in prefills)
    assert moved["decode_rows_total"] == sum(len(d["rows"]) for d in decodes)
    assert moved["decode_ctx_tokens_total"] == sum(
        c for d in decodes for _, _, c in d["rows"])
    assert moved["queue_wait_s_total"] == pytest.approx(sum(
        r["dispatched_ns"] - r["arrival_ns"] for r in reqs) / 1e9)
    assert moved["programs_built_total"] == len(
        tracing.records("engine.program_built"))
    driver._publish_llm_metrics(after)
    counters = metrics.snapshot("rtpu_llm_")
    for key, delta in moved.items():
        name = f"rtpu_llm_{key}"
        if name in counters:
            assert counters[name] - counters0.get(name, 0) \
                == pytest.approx(delta), name
    # the two device parts of a first token's wait are observed for the
    # requests whose programs the host waited for, and no others
    exact = sum(bool(r["parts_exact"]) for r in reqs)
    for hist, n in (("queue_wait", 3), ("ttft", 3), ("tpot", 3),
                    ("device_wait", exact), ("prefill_device", exact)):
        name = f"rtpu_llm_{hist}_seconds_count"
        assert counters.get(name, 0) - counters0.get(name, 0) == n, name
    # the device's timeline by the engine's stamps: the totals are the
    # records' sums, and with the host's part the two histograms' sums
    # are the wait for the first token that `ttft - queue_wait` is
    assert moved["device_busy_s_total"] == pytest.approx(sum(
        d["device_end_ns"] - d["device_start_ns"] for d in dispatches) / 1e9)
    assert moved["device_idle_s_total"] == pytest.approx(sum(
        s["device_idle_ns"] for s in _dicts("engine.step")) / 1e9)
    assert moved["harvests_late_total"] == sum(
        not d["end_exact"] for d in dispatches)
    if exact == 3:
        sums = {h: counters[f"rtpu_llm_{h}_seconds_sum"]
                - counters0.get(f"rtpu_llm_{h}_seconds_sum", 0)
                for h in ("queue_wait", "ttft", "device_wait",
                          "prefill_device")}
        assert (sums["device_wait"] + sums["prefill_device"] + sum(
            r["harvest_host_ns"] for r in reqs) / 1e9) == pytest.approx(
                sums["ttft"] - sums["queue_wait"])


def test_a_step_makes_a_bounded_number_of_clock_reads_and_appends(
        engine, monkeypatch):
    for i, p in enumerate(_prompts(4, lo=30, step=0, seed=10)):
        engine.add_request(f"g{i}", p, SamplingParams(max_tokens=30))
    for _ in range(4):      # past admission and the prefill wave
        engine.step()
    assert len(engine.running) == 4 and not engine.waiting
    reads, appends = [0], [0]
    clock, record = tracing.now_ns, tracing.record

    def counting_clock():
        reads[0] += 1
        return clock()

    def counting_record(kind, rec):
        appends[0] += 1
        record(kind, rec)

    monkeypatch.setattr(tracing, "now_ns", counting_clock)
    monkeypatch.setattr(tracing, "record", counting_record)
    steps = 10
    for _ in range(steps):
        engine.step()
    monkeypatch.undo()
    # a decode step: six regions (step, intake, admit, dispatch_decode,
    # fetch, harvest) of two reads each, one read at the dispatch and one
    # when its program is enqueued (the device stamps are arithmetic on
    # these and the fetch's two); one append for the step and one for the
    # dispatch it harvests
    assert reads[0] == 14 * steps
    assert appends[0] == 2 * steps
    _run(engine)


# -------------------- the device's timeline from the engine's own stamps
STUB_CFG = dict(page_size=8, num_pages=64, max_model_len=128, max_batch=4,
                prefill_buckets=(16, 32, 64, 128))


def _stub(cls=StubEngine, ready=False, **over):
    """The scheduler over compute seams that return handles whose
    readiness the case sets (benchmarks/recorder_cost.py), with a floor
    at which the tiny buckets split (70 tokens = 64 + 16)."""
    engine = cls(EngineConfig(**{**STUB_CFG, **over}))
    engine.ready = ready
    engine._pass_cost = PassCost(4, 0.0)
    return engine


def _by_seq():
    return sorted(_dicts("engine.dispatch"), key=lambda d: d["seq"])


@pytest.mark.parametrize("case, prompts, over, passes", [
    ("one pass", [20], {}, [1]),
    ("split by plan_passes", [70], {}, [2]),
    ("a wave of several rows", [20, 21, 22], {}, [1, 1, 1]),
    ("a wave and a split prompt", [20, 70, 21], {}, [1, 2, 1]),
    ("preempted", [17, 17], dict(num_pages=12, max_model_len=64,
                                 max_batch=2, prefill_buckets=(16, 32, 64)),
     None)])
def test_the_three_parts_sum_to_the_wait_for_the_first_token(
        case, prompts, over, passes):
    engine = _stub(**over)
    for i, n in enumerate(prompts):
        # a token of its own: no page is shared through the prefix cache
        engine.add_request(f"r{i}", [i + 1] * n,
                           SamplingParams(max_tokens=40))
    _run(engine, max_steps=900)
    reqs = {r["request_id"]: r for r in _dicts("engine.request")}
    assert len(reqs) == len(prompts)
    prefills = [d for d in _by_seq() if d["kind"] == "prefill"]
    for rid, r in reqs.items():
        assert (r["device_wait_ns"] + r["prefill_device_ns"]
                + r["harvest_host_ns"]
                == r["first_token_ns"] - r["dispatched_ns"]), case
        assert r["parts_exact"] is True
    if passes is None:
        assert sum(r["preemptions"] for r in reqs.values()) >= 1
        return
    for i, n_passes in enumerate(passes):
        r = reqs[f"r{i}"]
        mine = [d for d in prefills
                if any(row[0] == f"r{i}" for row in d["rows"])]
        assert len(mine) == n_passes
        # its own programs, whole: a wave's rows all waited for the wave
        assert r["prefill_device_ns"] == sum(
            d["device_end_ns"] - d["device_start_ns"] for d in mine)
        assert r["device_wait_ns"] >= 0 and r["harvest_host_ns"] >= 0
        assert (r["dispatched_ns"] + r["device_wait_ns"]
                + r["prefill_device_ns"]) == mine[-1]["device_end_ns"]


@pytest.mark.parametrize("ready", [False, True])
def test_a_handle_ready_before_its_fetch_gives_an_upper_bound(ready):
    from ray_tpu.serve.llm.server import EngineDriverMixin
    from ray_tpu.util import metrics

    engine = _stub(ready=ready)
    driver = EngineDriverMixin()
    driver.engine = engine
    driver._init_driver()
    driver._publish_llm_metrics(engine.stats())
    counters0 = metrics.snapshot("rtpu_llm_")
    engine.add_request("r", [1] * 70, SamplingParams(max_tokens=6))
    _run(engine)
    dispatches, steps = _by_seq(), _dicts("engine.step")
    assert len(dispatches) >= 4
    for d in dispatches:
        assert d["end_exact"] is (not ready)
        assert d["device_end_ns"] == (d["fetch_start_ns"] if ready
                                      else d["fetch_end_ns"])
        assert d["dispatch_ns"] <= d["enqueued_ns"] <= d["device_start_ns"]
    (req,) = _dicts("engine.request")
    assert req["parts_exact"] is (not ready)
    blocked = sum(s["fetch_blocked"] for s in steps)
    assert blocked == (0 if ready else len(dispatches))
    assert all(s["fetch_blocked"] <= 2 for s in steps)
    # the host that comes late is counted, whatever the program's kind,
    # and its request's parts, which are bounds, reach no histogram
    late = len(dispatches) if ready else 0
    assert engine.stats()["harvests_late_total"] == late
    driver._publish_llm_metrics(engine.stats())
    counters = metrics.snapshot("rtpu_llm_")
    moved = {k: counters.get(f"rtpu_llm_{k}", 0)
             - counters0.get(f"rtpu_llm_{k}", 0)
             for k in ("harvests_late_total", "ttft_seconds_count",
                       "device_wait_seconds_count",
                       "prefill_device_seconds_count")}
    assert moved == {"harvests_late_total": late, "ttft_seconds_count": 1,
                     "device_wait_seconds_count": 0 if ready else 1,
                     "prefill_device_seconds_count": 0 if ready else 1}


class _EveryThird(StubEngine):
    """Every third program had finished before the host came for it."""
    ready = property(lambda self: self._dispatch_seq % 3 == 0,
                     lambda self, value: None)


@pytest.mark.parametrize("cls", [StubEngine, _EveryThird])
def test_stamped_programs_follow_one_another_and_the_gaps_are_the_idle_time(
        cls):
    import time

    engine = _stub(cls)
    before = engine.stats()
    for i in range(3):
        # the pipeline runs dry between two requests: the device idles
        engine.add_request(f"r{i}", [i + 1] * (20 + 50 * (i % 2)),
                           SamplingParams(max_tokens=5))
        _run(engine)
        time.sleep(0.002)
    dispatches = _by_seq()
    gaps = 0
    for prev, d in zip(dispatches, dispatches[1:]):
        assert d["device_start_ns"] >= prev["device_end_ns"]
        assert d["device_start_ns"] == max(d["enqueued_ns"],
                                           prev["device_end_ns"])
        assert d["device_end_ns"] >= d["device_start_ns"]
        gaps += max(0, d["enqueued_ns"] - prev["device_end_ns"])
    assert gaps >= 2 * 2_000_000
    assert gaps == sum(s["device_idle_ns"] for s in _dicts("engine.step"))
    after = engine.stats()
    assert after["device_idle_s_total"] - before["device_idle_s_total"] \
        == pytest.approx(gaps / 1e9)
    assert after["device_busy_s_total"] - before["device_busy_s_total"] \
        == pytest.approx(sum(d["device_end_ns"] - d["device_start_ns"]
                             for d in dispatches) / 1e9)
    if cls is _EveryThird:
        assert {d["end_exact"] for d in dispatches} == {True, False}


def test_an_engine_whose_handles_cannot_say_stamps_nothing():
    """The pipelined engine's seam: every new field None, nothing drawn
    on the device's lane, nothing counted."""
    from ray_tpu.serve.llm.pp import PipelinedEngine

    class NoStamps(StubEngine):
        _handle_ready = staticmethod(PipelinedEngine._handle_ready)

    assert PipelinedEngine._handle_ready is not LLMEngine._handle_ready
    engine = _stub(NoStamps)
    engine.add_request("r", [1] * 70, SamplingParams(max_tokens=6))
    _run(engine)
    stamps = ("enqueued_ns", "device_start_ns", "device_end_ns", "end_exact")
    parts = ("device_wait_ns", "prefill_device_ns", "harvest_host_ns",
             "parts_exact")
    dispatches = _dicts("engine.dispatch")
    assert dispatches and all(d[f] is None for d in dispatches
                              for f in stamps)
    (req,) = _dicts("engine.request")
    assert req["first_token_ns"] and all(req[f] is None for f in parts)
    assert all(s["fetch_blocked"] == 0 == s["device_idle_ns"]
               for s in _dicts("engine.step"))
    assert engine.stats()["device_busy_s_total"] == 0
    assert not [e for e in tracing.chrome_trace([])
                if e["cat"] == "rtpu.device"]


# ------------------------------------------- an engine that is left
class _Pending(Handle):
    """A handle whose copy to the host says when it was waited for, as
    `np.asarray` of a device array waits."""

    __slots__ = ()
    fetched: list = []
    broken: set = set()

    def __array__(self, dtype=None, copy=None):
        _Pending.fetched.append(self)
        if len(_Pending.fetched) in _Pending.broken:
            raise RuntimeError("the buffer was deleted")
        return self.tokens


class _Left(StubEngine):
    """The stub with the real engine's fetch (`np.asarray`)."""

    _fetch_tokens = LLMEngine._fetch_tokens

    def _compute_prefill(self, sb, rb, *_):
        return _Pending(np.ones((rb,), np.int32), self.ready)

    def _compute_decode(self, k_steps, *_):
        return _Pending(np.ones((k_steps, self.config.max_batch), np.int32),
                        self.ready)


def _three_in_flight():
    """A prompt of two passes and two of one, three steps on: three
    dispatches in flight, a prefill pass and two decode programs behind
    it."""
    _Pending.fetched, _Pending.broken = [], set()
    engine = _stub(_Left, pipeline_depth=4)
    for rid, n in (("a", 70), ("b", 20), ("c", 40)):
        engine.add_request(rid, [1] * n, SamplingParams(max_tokens=6))
    for _ in range(3):
        engine.step()
    handles = [rec["toks"] for rec in engine._inflight]
    assert len(handles) == 3, [rec["kind"] for rec in engine._inflight]
    _Pending.fetched = []
    return engine, handles


def _drop(engine):
    del engine
    gc.collect()


def _close_twice(engine):
    engine.close()
    engine.close()


def _close_then_drop(engine):
    engine.close()
    _drop(engine)


def _abort_then_close(engine):
    engine.abort("a")
    engine.abort("b")
    engine.close()


@pytest.mark.parametrize("leave", [
    _drop, LLMEngine.close, _close_twice, _close_then_drop,
    _abort_then_close, lambda engine: engine._leave()],
    ids=["dropped", "closed", "closed twice", "closed then dropped",
         "aborted then closed", "the exit hook"])
def test_an_engine_that_is_left_fetches_every_dispatch_in_flight(leave):
    """However it is left, each handle in flight is waited for once,
    oldest first, and nothing is left pending."""
    engine, handles = _three_in_flight()
    inflight = engine._inflight
    leave(engine)
    del engine
    assert _Pending.fetched == handles and not inflight


def test_a_closed_engine_dispatches_nothing_more():
    engine, _ = _three_in_flight()
    engine.close()
    assert engine.stats()["inflight"] == 0
    with pytest.raises(RuntimeError, match="closed engine"):
        engine.step()
    assert not _Pending.fetched[3:]


def test_a_handle_that_cannot_be_fetched_does_not_stop_the_drain():
    engine, handles = _three_in_flight()
    _Pending.broken = {2}
    engine.close()                      # silent
    assert _Pending.fetched == handles


def test_close_waits_for_the_step_that_is_running():
    """`close` from another thread (a server's shutdown while the driver's
    executor is inside `step`) takes its turn behind the step."""
    import threading

    engine, handles = _three_in_flight()
    in_step, go = threading.Event(), threading.Event()
    fetch = engine._fetch_tokens

    def slow_fetch(handle):
        in_step.set()
        assert go.wait(10)
        return fetch(handle)

    engine._fetch_tokens = slow_fetch
    stepper = threading.Thread(target=engine.step)
    stepper.start()
    assert in_step.wait(10)
    closer = threading.Thread(target=engine.close)
    closer.start()
    closer.join(0.2)
    assert closer.is_alive() and not _Pending.fetched
    go.set()
    stepper.join(10)
    closer.join(10)
    assert not closer.is_alive() and not engine._inflight
    assert _Pending.fetched[0] is handles[0]


def test_a_servers_shutdown_closes_its_engine():
    import asyncio

    from ray_tpu.serve.llm.server import EngineDriverMixin

    engine, handles = _three_in_flight()
    driver = EngineDriverMixin()
    driver.engine = engine
    driver._init_driver()
    asyncio.run(driver.shutdown())
    assert _Pending.fetched == handles and engine._closed


def test_a_replica_that_is_stopped_asks_its_callable_to_leave():
    import asyncio

    from ray_tpu.serve.replica import ReplicaActor

    class Hosted:
        left = 0

        async def shutdown(self):
            Hosted.left += 1

    replica = ReplicaActor.__new__(ReplicaActor)
    replica._config = type("C", (), {"graceful_shutdown_timeout_s": 1.0})
    replica._ongoing, replica._user_callable = 0, Hosted()
    asyncio.run(replica.prepare_for_shutdown())
    replica._user_callable = object()           # no hook: nothing to ask
    asyncio.run(replica.prepare_for_shutdown())
    assert Hosted.left == 1


def test_a_batch_that_ends_early_leaves_its_engine():
    from ray_tpu.serve.llm import batch

    engine, handles = _three_in_flight()
    batch._ENGINE_CACHE["left"] = engine
    batch._drop_engine(engine)
    assert "left" not in batch._ENGINE_CACHE
    assert _Pending.fetched == handles and engine._closed


def test_the_drain_at_exit_runs_before_handlers_registered_earlier():
    """The interpreter's exit with dispatches in flight: the engine's hook
    was registered after JAX's own (the constructor imports JAX), so it
    runs before them. A handler registered before the engine was built
    stands for JAX's."""
    import subprocess
    import sys

    code = r'''
import atexit, sys
sys.path.insert(0, "tests"); sys.path.insert(0, ".")
import jax
atexit.register(lambda: print("an earlier handler", flush=True))
import test_engine_tracing as t
engine, handles = t._three_in_flight()
t._Pending.__array__ = lambda self, dtype=None, copy=None: (
    print("fetched", handles.index(self), flush=True), self.tokens)[1]
'''
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split("\n")[:4] == [
        "fetched 0", "fetched 1", "fetched 2", "an earlier handler"]


# the `engine.dispatch` fields a model family writes (its dispatch facts:
# serve/llm/stage.py: model_family), for its tiny preset: in every record,
# and in the records of one kind beside those
_MOE = {"moe_assignments", "moe_experts_touched", "moe_expert_tokens_max"}
_SALA = {"lin_layers", "lin_state_bytes_row", "sparse_layers",
         "sparse_tokens_read", "sparse_kernels_scored"}
_MLA = {"mla_layers", "latent_bytes_token", "moe_assignments_routed"}
FAMILY_FIELDS = {
    "tiny-moe": (_MOE, {}),
    "tiny-jamba": ({"ssm_layers", "ssm_state_bytes_row"}, {}),
    "tiny-sala": (_SALA, {"prefill": {"pass_index", "final"}}),
    "tiny-sdar": (_MOE, {"block": {"block_passes", "block_tokens_fixed",
                                   "block_len"}}),
    "tiny-kimi": (_MOE | _MLA, {"prefill": {"mla_ctx_chunks"}}),
    "tiny-gigachat": (_MOE | _MLA | {"gdn_layers", "gdn_state_bytes_row"},
                      {"prefill": {"mla_ctx_chunks"}}),
    "tiny-laguna": (_MOE | {"window_layers", "full_layers", "window_heads",
                            "full_heads", "window_tokens_read",
                            "full_tokens_read", "window_tokens_held",
                            "full_tokens_held"}, {}),
    "tiny-zaya": (_MOE | {"cca_layers", "cca_tail_bytes_row"}, {}),
}


# every tiny preset that a test file renews an engine of
PRESETS = ("tiny", "tiny-mellum", *sorted(FAMILY_FIELDS))
# stats() that are read off a clock, and the one that counts what the
# programs cost to build: a renewed engine has them built, which is the point
NOT_THE_TRAFFICS = {"queue_wait_s_total", "device_busy_s_total",
                    "device_idle_s_total", "harvests_late_total",
                    "programs_built_total"}


@pytest.fixture(scope="module")
def preset_engine(request):
    """A new engine of one tiny preset for the two cases that preset has
    below (pytest runs a module-scoped parameter's cases together, in the
    order they are written: the first finds the engine as it was built)."""
    preset = request.param
    engine = new_engine(
        preset, dtype="float32", page_size=8 if preset == "tiny-jamba" else 16,
        num_pages=96, max_model_len=256, max_batch=4, prefill_buckets=(64,),
        seed=3)
    yield engine
    engine.close()


def _served(engine, seed, lens=(5, 41, 64), max_tokens=6):
    """The same traffic every time it is asked with one seed (a prompt that
    fills the largest bucket, a repeated prompt for the prefix cache, a row
    that draws) -> (tokens by request, the pool, what stats() says of it)."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 200, n).tolist() for n in lens]
    prompts.append(prompts[1])
    out = {}
    for i, prompt in enumerate(prompts):
        engine.add_request(f"r{i}", prompt, SamplingParams(
            max_tokens=max_tokens, temperature=1.0 if i == 2 else 0.0,
            top_k=8, seed=17))
    for _ in range(600):
        if not engine.has_work():
            break
        for d in engine.step():
            out.setdefault(d.request_id, []).extend(d.new_token_ids)
    assert not engine.has_work()
    pool = engine.kv_pages
    pool = pool if isinstance(pool, dict) else {"kv_pages": pool}
    return (out, {k: np.asarray(v) for k, v in pool.items()},
            {k: v for k, v in engine.stats().items()
             if k not in NOT_THE_TRAFFICS})


@pytest.mark.parametrize("preset_engine", PRESETS, indirect=True)
def test_a_renewed_engine_serves_what_a_new_one_serves(preset_engine):
    """What the test files stand on (tests/_engines.py): an engine built
    here serves a traffic; then another traffic leaves it pages in the
    prefix cache, carries in its slots and counters; it is renewed and
    serves the first again: the same tokens (a seeded draw among them), the
    same pool to the bit, the same stats(). One with work is refused."""
    engine = preset_engine
    assert engine.stats()["programs_built_total"] == 0       # new
    tokens, pool, stats = _served(engine, seed=1)
    assert all(len(t) == 6 for t in tokens.values()) and len(tokens) == 4
    _served(engine, seed=2, lens=(9, 64, 33), max_tokens=9)
    engine.add_request("open", [1, 2, 3], SamplingParams(max_tokens=4))
    with pytest.raises(RuntimeError, match="only an idle engine"):
        renewed(engine)
    _run(engine)
    again = _served(renewed(engine), seed=1)
    assert again[0] == tokens
    assert again[2] == stats
    for part, pages in pool.items():
        np.testing.assert_array_equal(again[1][part], pages, err_msg=part)


@pytest.mark.parametrize("preset_engine", sorted(FAMILY_FIELDS),
                         indirect=True)
def test_a_familys_records_hold_its_fields_and_no_other_familys(
        preset_engine):
    """Every `engine.dispatch` record of every family is `FIELDS` long;
    between the engine's own fields and the stamps (and behind them) a
    family's fields are set in its records and every other family's are None; and every
    counter and pool size of `stats()` is published with a help string."""
    from ray_tpu.serve.llm.server import EngineDriverMixin
    from ray_tpu.util import metrics

    engine = renewed(preset_engine)
    preset = engine.config.model
    rng = np.random.default_rng(4)
    # shorter than a block, one bucket, and (where a prefill resumes) more
    # than the largest bucket holds
    lens = (3, 41, 64) if preset == "tiny-jamba" else (3, 41, 100)
    for i, n in enumerate(lens):
        engine.add_request(f"f{i}", rng.integers(1, 200, n).tolist(),
                           SamplingParams(max_tokens=6))
    _run(engine)
    fields = tracing.FIELDS["engine.dispatch"]
    family = set(fields[fields.index("moe_assignments"):
                        fields.index("enqueued_ns")]
                 + fields[fields.index("end_exact") + 1:
                          fields.index("drawn")])
    every, by_kind = FAMILY_FIELDS[preset]
    kinds = set()
    for rec in tracing.records("engine.dispatch"):
        assert len(rec) == len(fields)
        r = dict(zip(fields, rec))
        kinds.add(r["kind"])
        assert {f for f in family if r[f] is not None} == (
            every | by_kind.get(r["kind"], set())), r
    assert kinds == {"prefill", "block" if preset == "tiny-sdar"
                     else "decode"}
    stats = engine.stats()
    driver = EngineDriverMixin()
    driver.engine = engine
    driver._init_driver()
    driver._publish_llm_metrics(stats)
    # (`expired_total` is the admission plane's to publish, as a shed)
    published = [k for k in stats if k != "expired_total" and (
        k.endswith(("_total", "_pool_bytes")) or k == "ssm_slots")]
    assert len(published) > 20
    for key in published:
        assert metrics._registry[f"rtpu_llm_{key}"].description, key


def test_trainer_step_leaves_one_record_a_call():
    import jax

    from ray_tpu.models.llama import LlamaModel, get_config
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.train_lib import ShardedTrainer

    cfg = get_config("tiny", scan_layers=True)
    mesh = create_mesh(MeshConfig(dp=1, fsdp=1, sp=1, tp=1),
                       devices=jax.devices()[:1])
    trainer = ShardedTrainer(LlamaModel(cfg), mesh)
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 17), dtype=np.int32)}
    state = trainer.init(jax.random.PRNGKey(0), batch)
    for _ in range(3):
        state, _ = trainer.step(state, batch)
    recs = _dicts("train.step")
    assert [r["seq"] for r in recs] == [1, 2, 3]
    assert all(r["start_ns"] <= r["end_ns"] for r in recs)
    assert recs[0]["end_ns"] <= recs[1]["start_ns"]


def test_chrome_trace_renders_the_ring(engine):
    for i, p in enumerate(_prompts(2, seed=11)):
        engine.add_request(f"c{i}", p, SamplingParams(max_tokens=3))
    _run(engine)
    events = tracing.chrome_trace([])
    json.dumps(events)
    by_cat = {}
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0 and e["pid"] == "rtpu.ring"
        by_cat.setdefault(e["cat"], []).append(e)
    assert len(by_cat["engine.request"]) == 2
    assert len(by_cat["engine.step"]) == len(tracing.records("engine.step"))
    assert len(by_cat["engine.dispatch"]) == len(
        tracing.records("engine.dispatch"))
    step = by_cat["engine.step"][0]
    assert step["ts"] == pytest.approx(step["args"]["start_ns"] / 1e3)
    # the lane of the device: every dispatch again, from its stamped start
    # to its stamped end, one after another
    lane = by_cat["rtpu.device"]
    assert {e["tid"] for e in lane} == {"rtpu.device"}
    assert [e["name"] for e in lane] == [
        e["name"] for e in by_cat["engine.dispatch"]]
    for before, after in zip(lane, lane[1:]):
        assert before["ts"] + before["dur"] <= after["ts"] + 1.0
