"""The trace reduction: on a hand-made trace, and on a trace recorded from
the chip (tests/data/*.trace.json.gz, trimmed to a few hundred KB)."""

import glob
import os

import pytest

from chipbench import tracered as t

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _hand_made():
    ops = [("while", 0, 100), ("fusion.1", 10, 20), ("kernel", 40, 30),
           ("fusion.2", 150, 50)]
    host = [("chipbench.window", 0, 300), ("chipbench.engine.step", 90, 80),
            ("chipbench.inner", 120, 20), ("chipbench.gen.wait", 200, 100)]
    modules = [("jit_run_decode(1)", 0, 100), ("jit_run_decode(1)", 140, 70)]
    return t.Trace(ops={0: ops}, modules={0: modules}, host=host,
                   window=(0, 300))


def test_busy_is_the_union_not_the_sum():
    tr = _hand_made()
    assert t.busy_ns(tr.ops[0]) == 150          # [0,100) + [150,200)
    assert t.union_intervals(tr.ops[0]) == [(0, 100), (150, 200)]


def test_self_time_takes_children_out_of_a_parent():
    by = t.time_by_name(_hand_made().ops[0])
    assert by == {"while": 50, "fusion.1": 20, "kernel": 30, "fusion.2": 50}


def test_idle_gaps():
    tr = _hand_made()
    assert t.idle_gaps(tr.ops[0], tr.window) == [(100, 150), (200, 300)]


def test_attribution_by_hand_exact():
    tr = _hand_made()
    got = t.attribute_gaps([(100, 150), (200, 300)], tr.host, tr.modules[0])
    assert got["chipbench.inner"] == 20
    assert got["chipbench.engine.step"] == 20
    assert got["(in program) jit_run_decode(1)"] == 10 + 10
    assert got["chipbench.gen.wait"] == 90
    assert sum(got.values()) == 150


def test_reduce_and_window_clip_and_json_round_trip(tmp_path):
    tr = _hand_made()
    tr.window = (50, 250)
    red = t.reduce_trace(tr)
    assert red.window_s == pytest.approx(200e-9)
    assert red.busy_s == pytest.approx(100e-9)   # [50,100) + [150,200)
    assert red.idle_pct == pytest.approx(50.0)
    path = str(tmp_path / "x.trace.json.gz")
    tr.save(path)
    again = t.Trace.load(path)
    assert again.ops == tr.ops and again.window == tr.window
    b = red.breakdown()
    assert len(b["device_ops"]) <= 10 and b["device_ops"][0][1] > 0
    assert t.durations_matching(tr.modules[0], r"^jit_run_decode") == [100, 70]


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.trace.json.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace_reduces(path):
    tr = t.Trace.load(path)
    red = t.reduce_trace(tr)
    assert red.window_s > 0 and 0 < red.busy_s <= red.window_s
    assert 0 <= red.idle_pct < 100
    # self times partition the busy time (per chip, summed here)
    total_self = sum(red.ops_by_name_s.values())
    chips = max(1, len(tr.ops))
    assert total_self == pytest.approx(red.busy_s * chips, rel=1e-6)
    # idle seconds are all attributed
    idle = red.window_s - red.busy_s
    assert sum(red.gaps_by_span_s.values()) == pytest.approx(idle, rel=1e-6)
    assert any(n.startswith("jit_") for evs in tr.modules.values()
               for n, _, _ in evs)


def test_a_recorded_trace_is_checked_in():
    assert RECORDED, "tests/data holds no trace recorded from the chip"
    for path in RECORDED:
        assert os.path.getsize(path) < 600 * 1024
