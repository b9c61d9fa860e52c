"""Percentiles and attainment over every request due in the window."""
import math

import pytest

from benchmarks.chip import stats

SLO = {"ttft_base_s": 1.0, "ttft_per_token_s": 0.001, "tpot_s": 0.1}


def test_percentile_is_linear_interpolation():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(list(range(11)), 90) == pytest.approx(9.0)
    assert math.isnan(stats.percentile([], 90))


def test_limits_scale_with_prompt():
    assert stats.ttft_limit(SLO, 1000) == pytest.approx(2.0)


def test_finished_request_within_limits_is_ok():
    o = stats.outcome(1, due=0.0, prompt_len=1000, first=1.5, finish=2.5,
                      tokens=11, cut=10.0, slo=SLO)
    assert o.ttft == 1.5 and o.tpot == pytest.approx(0.1) and o.ok


def test_late_first_token_misses():
    o = stats.outcome(1, 0.0, 1000, first=2.5, finish=3.0, tokens=6,
                      cut=10.0, slo=SLO)
    assert not o.ok


def test_stalled_request_misses_and_keeps_its_times():
    o = stats.outcome(1, due=1.0, prompt_len=10, first=1.2, finish=None,
                      tokens=5, cut=9.2, slo=SLO)
    assert not o.finished and not o.ok
    assert o.ttft == pytest.approx(0.2)
    assert o.tpot == pytest.approx(2.0)      # (cut - first) / 4 gaps


def test_request_streaming_within_limits_at_the_cut_is_judged_so_far():
    o = stats.outcome(1, due=1.0, prompt_len=10, first=1.5, finish=None,
                      tokens=41, cut=5.5, slo=SLO)
    assert not o.finished and o.ok
    assert o.tpot == pytest.approx(0.1)


def test_request_that_never_produced_a_token_counts_to_the_cut():
    o = stats.outcome(1, due=2.0, prompt_len=10, first=None, finish=None,
                      tokens=0, cut=12.0, slo=SLO)
    assert o.ttft == pytest.approx(10.0) and o.tpot is None and not o.ok


def test_end_to_end_counts_misses_in_every_number():
    ok = [stats.outcome(i, 0.0, 10, 0.1, 1.0, 10, 5.0, SLO)
          for i in range(9)]
    stuck = stats.outcome(9, 0.0, 10, None, None, 0, 5.0, SLO)
    e = stats.end_to_end(ok + [stuck], tokens_in_window=400, seconds=4.0)
    assert e["slo_attainment"] == pytest.approx(0.9)
    # the stuck request's TTFT (5 s, to the cut) is in the tail: p90 of
    # nine 0.1 s and one 5 s lies 0.1 of the way from 0.1 to 5
    assert e["ttft_p90_s"] == pytest.approx(0.1 + 0.1 * 4.9)
    assert e["output_tok_per_s"] == 100.0
