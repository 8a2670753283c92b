"""Window accounting: tokens count where they were harvested, requests
that cross an edge count for their gaps, first tokens from the due time."""
import math

import bench_tiny  # noqa: F401
from bench.lib import stats


def rec(uid, due, harvests):
    r = stats.Record(uid, prompt_len=4, budget=99, due=due)
    r.harvests = list(harvests)
    return r


def test_tokens_inside_the_window_only():
    rs = [rec(0, 0.0, [(0.5, 8), (1.5, 8), (2.5, 8)]),
          rec(1, 1.0, [(1.0, 8), (3.0, 8)])]
    assert stats.tokens_in(rs, 1.0, 3.0) == 8 + 8 + 8
    assert stats.tokens_in(rs, 0.0, 10.0) == 40


def test_straddling_requests_keep_their_gaps():
    # harvests before the window and after it do not count; the gap is
    # (last - first inside) / tokens after the first harvest inside
    a = rec(0, 0.0, [(0.5, 8), (1.5, 8), (2.0, 4), (3.5, 8)])
    b = rec(1, 0.0, [(2.9, 8)])           # one harvest inside: no gap
    gaps = stats.token_gaps([a, b], 1.0, 3.0)
    assert len(gaps) == 1
    assert math.isclose(gaps[0], (2.0 - 1.5) / 4)


def test_active_requests_got_a_token_inside():
    rs = [rec(0, 1.0, [(1.25, 8)]), rec(1, 2.0, []), rec(2, 0.5, [(0.9, 8)])]
    assert [r.uid for r in stats.active_in(rs, 1.0, 3.0)] == [0]


def test_percentile_is_linear():
    assert stats.percentile([1, 2, 3, 4, 5], 90) == 4.6
    assert math.isnan(stats.percentile([], 90))


def test_batches_count_for_their_run_inside_the_window():
    # runs: [0.5, 1.5] (sent at 0.5), [1.5, 2.5] (sent at 1.0, waits),
    # [2.5, 4.5] (crosses the close at 3.5)
    batches = [(0.5, 1.5, 10.0), (1.0, 2.5, 10.0), (2.0, 4.5, 20.0)]
    assert math.isclose(stats.batch_work_in(batches, 1.0, 3.5),
                        5.0 + 10.0 + 10.0)
    assert stats.batch_work_in(batches, 5.0, 6.0) == 0.0
