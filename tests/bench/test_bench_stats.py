"""Percentiles, spreads and the end-to-end reduction of a window."""
import math

import numpy as np

from bench import harness, loadgen, stats


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert math.isnan(stats.percentile([], 95))
    assert stats.percentile([1, 2, math.inf], 95) == math.inf


def test_spread_is_iqr_over_median():
    vals = [10, 11, 12, 13, 14, 15]
    q1, med, q3 = 10.75, 12.5, 14.25
    assert abs(stats.spread(vals) - (q3 - q1) / med) < 1e-12


def test_union_length():
    assert stats.union_length([(0, 1), (0.5, 2), (3, 4)]) == 3.0
    assert stats.union_length([]) == 0.0


class _Req:
    def __init__(self, n, error=None):
        self.out_tokens = [0] * n
        self.done = True
        self.error = error


def _rec(index, due, times, error=None):
    it = loadgen.Item(index, due, np.zeros(4, np.int32), len(times) or 4)
    r = harness.Rec(it, due=due, sent=due, times=list(times))
    r.req = _Req(len(times), error)
    r.error = error
    return r


def test_failed_request_counts_as_missing():
    # 19 requests with a first token after 0.1 s, one failed: with 20
    # requests the 95th percentile is the 19th smallest, so one failure
    # does not reach it -- two do.
    recs = [_rec(i, 0.0, [0.1, 0.2]) for i in range(19)]
    recs.append(_rec(19, 0.0, [], error="deadline"))
    w = harness.Window(recs, 0.0, 10.0, 70.0, 0)
    e2e = harness.end_to_end(w)
    assert abs(e2e["ttft_p95_ms"] - 100.0) < 1e-9
    # the mean takes the failed one in at the drain's end: 70 s
    assert abs(e2e["ttft_mean_ms"] - (19 * 100.0 + 70e3) / 20) < 1e-6
    recs[0] = _rec(0, 0.0, [], error="QueueFullError")
    e2e = harness.end_to_end(harness.Window(recs, 0.0, 10.0, 70.0, 0))
    # a missing request waited at least until the drain gave up
    assert e2e["ttft_p95_ms"] == 70.0 * 1e3
    assert abs(e2e["ttft_p50_ms"] - 100.0) < 1e-9


def test_window_rates_and_gaps():
    recs = [_rec(0, 0.0, [1.0, 1.5, 2.5]), _rec(1, 1.0, [3.0, 11.0])]
    w = harness.Window(recs, 0.0, 10.0, 12.0, 0)
    e2e = harness.end_to_end(w)
    # tokens emitted inside the window only
    assert e2e["output_tok_per_s"] == 4 / 10.0
    # gaps whose later token came inside the window: 0.5, 1.0
    assert abs(e2e["itl_p99_ms"] - 1000.0) < 1e-9
