import pytest

from chipbench import stats
from chipbench.stats import RequestRecord


def test_percentile_interpolates_like_numpy():
    np = pytest.importorskip("numpy")
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 11.0]
    for q in (0, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([], 95) is None


def _timeline():
    # due, sent, first, finish, out_tokens
    ok = RequestRecord("a", due_s=1.0, counted=True, prompt_tokens=100,
                       max_tokens=11, sent_s=1.002, first_s=1.5,
                       finish_s=2.5, out_tokens=11, finish_reason="length")
    # chunked delivery: 0,0,0,big gaps are averaged per request
    one = RequestRecord("b", due_s=2.0, counted=True, prompt_tokens=50,
                        max_tokens=1, sent_s=2.0, first_s=2.2, finish_s=2.2,
                        out_tokens=1, finish_reason="length")
    never = RequestRecord("c", due_s=3.0, counted=True, prompt_tokens=70,
                          max_tokens=20, sent_s=3.1, first_s=3.4,
                          out_tokens=5)
    ramp = RequestRecord("r", due_s=-1.0, counted=False, prompt_tokens=10,
                         max_tokens=4, sent_s=-1.0, first_s=-0.5,
                         finish_s=0.5, out_tokens=4, finish_reason="length")
    late = RequestRecord("t", due_s=9.0, counted=False, prompt_tokens=10,
                         max_tokens=4, sent_s=9.0, first_s=9.5,
                         finish_s=10.5, out_tokens=4, finish_reason="length")
    return [ok, one, never, ramp, late]


def test_ttft_counts_from_due_and_tpot_is_per_request():
    ok, one, never, *_ = _timeline()
    assert ok.ttft_ms == pytest.approx(500.0)       # 1.5 - due 1.0
    assert ok.late_ms == pytest.approx(2.0)
    assert ok.tpot_ms == pytest.approx(100.0)       # 1.0 s / 10 gaps
    assert one.ttft_ms == pytest.approx(200.0)
    assert one.tpot_ms is None                      # one token: no gap


def test_a_request_that_never_finishes_has_no_sample_and_fails():
    recs = _timeline()
    never = recs[2]
    assert not never.ok and never.ttft_ms is None and never.tpot_ms is None
    assert stats.field_values(recs, "ttft_ms") == [500.0, pytest.approx(200.0)]
    assert len(stats.field_values(recs, "ttft_ms", counted_only=False)) == 4


def _backlog_timeline():
    def rec(rid, first, prompt):
        return RequestRecord(rid, due_s=-5.0, counted=True,
                             prompt_tokens=prompt, max_tokens=50,
                             sent_s=-5.0, first_s=first)
    recs = [rec("a", -1.0, 100), rec("b", 0.5, 200), rec("c", 0.5, 300),
            rec("d", 4.0, 400), rec("e", 10.7, 500), rec("f", 12.0, 600),
            rec("g", None, 700)]
    events = [(-1.0, 1), (0.5, 2), (1.0, 3), (4.0, 1), (9.9, 7), (10.7, 1),
              (11.0, 5), (12.0, 1)]
    return recs, events


def test_processed_tokens_count_work_done_inside_the_window():
    recs, events = _backlog_timeline()
    # (0, 10]: prefills of b, c (0.5) and d (4.0); tokens at 0.5 .. 9.9
    assert stats.processed_tokens(recs, events, 0.0, 10.0) \
        == 200 + 300 + 400 + 2 + 3 + 1 + 7
