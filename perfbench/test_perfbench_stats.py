"""End-to-end arithmetic on hand-made timestamps."""
import math

import pytest

from perfbench import stats


def _req(uid, due, times, tier="8/8", max_new=None):
    r = stats.Served(uid=uid, tier=tier, due=due, prompt_len=10,
                     max_new=max_new or len(times))
    r.token_times = list(times)
    r.tokens = list(range(len(times)))
    return r


def test_percentile_matches_linear_interpolation():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 95) == pytest.approx(4.8)
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([1.0, math.inf], 95) == math.inf


def test_ttft_tpot_and_tokens_per_second():
    reqs = [_req(0, 0.0, [0.5, 0.6, 0.7]),          # due before the window
            _req(1, 1.0, [1.2, 1.4, 1.6, 1.8]),      # ttft 200 ms, tpot 200
            _req(2, 2.0, [2.1, 2.2, 2.3]),           # ttft 100 ms, tpot 100
            _req(3, 2.5, [], max_new=3)]             # never answered
    ttft = stats.ttfts_ms(reqs, 1.0, 3.0)
    assert ttft[:2] == pytest.approx([200.0, 100.0]) and ttft[2] == math.inf
    assert stats.tpots_ms(reqs, 1.0, 3.0) == pytest.approx([200.0, 100.0])
    assert stats.output_tok_s(reqs, 1.0, 3.0) == pytest.approx(7 / 2.0)


def test_a_stall_in_the_window_moves_the_tail():
    def window(stall):
        reqs = []
        for i in range(40):
            due = 1.0 + 0.1 * i
            first = due + 0.05 + (stall if 10 <= i < 14 else 0.0)
            reqs.append(_req(i, due, [first + 0.02 * k for k in range(9)]))
        return stats.end_to_end(reqs, 1.0, 10.0)

    calm, stalled = window(0.0), window(0.8)
    assert calm["ttft_p95_ms"] == pytest.approx(50.0)
    assert stalled["ttft_p95_ms"] > 500.0
    assert stalled["ttft_p50_ms"] == pytest.approx(50.0)
    assert calm["tpot_p95_ms"] == pytest.approx(20.0)


def test_spread_is_iqr_over_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0, 6.0]) == pytest.approx(
        (5.25 - 1.75) / 3.5)
