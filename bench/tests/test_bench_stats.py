"""Percentile and window arithmetic of a run."""
import math

import pytest

import bench_tiny  # noqa: F401
import stats


def test_nearest_rank():
    vals = list(range(1, 101))
    assert stats.nearest_rank(vals, 95) == 95
    assert stats.nearest_rank(vals, 50) == 50
    assert stats.nearest_rank([7.0], 95) == 7.0
    assert math.isnan(stats.nearest_rank([], 95))


def test_unfinished_requests_rank_above_every_finished_one():
    done = [float(i) for i in range(1, 20)]          # 19 finished
    assert stats.censored_percentile(done, [], 95) == 19.0
    # one request with no first token, waiting 5 ms so far: it ranks
    # last and the p95 of 20 samples is the 19th, a finished one
    assert stats.censored_percentile(done, [5.0], 95) == 19.0
    # two unfinished: the 19th of 21 is unfinished, and reads no less
    # than the slowest finished one
    assert stats.censored_percentile(done, [5.0, 6.0], 95) == 19.0
    assert stats.censored_percentile(done, [50.0, 60.0], 95) == 50.0


def req(due, tokens):
    return {"due": due, "tokens": tokens}


def test_ttft_counts_only_requests_due_in_the_window():
    rs = [req(0.5, [0.9]),                  # due in the lead-in
          req(1.0, [1.25, 1.5]),            # 250 ms
          req(2.0, []),                     # no first token: 1000 ms so far
          req(2.5, [3.5]),                  # first token after the window
          req(3.0, [3.1])]                  # due at the end: outside
    done, waiting = stats.ttft_ms(rs, 1.0, 3.0)
    assert done == pytest.approx([250.0])
    assert waiting == pytest.approx([1000.0, 500.0])


def test_inter_token_gaps_inside_the_window_pooled():
    rs = [req(0.0, [0.5, 1.0, 1.2, 1.5, 3.5]), req(1.0, [1.1, 1.4])]
    gaps = sorted(stats.inter_token_ms(rs, 1.0, 3.0))
    assert gaps == pytest.approx([200.0, 300.0, 300.0])


def test_tokens_in_window():
    rs = [req(0.0, [0.5, 1.0, 2.0, 3.0, 3.01]), req(1.0, [2.5])]
    assert stats.tokens_in(rs, 1.0, 3.0) == 4
