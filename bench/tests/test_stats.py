import numpy as np

from bench import stats


def test_ttft_counts_every_arrival_and_the_missing():
    due = [0.0, 1.0, 2.0, 3.0, 9.0]
    first = [0.5, 1.2, None, 3.1, None]
    # window [1, 5): due 1, 2, 3; the one due at 2 never got a token by 6
    tt = stats.ttfts(due, first, (1.0, 5.0), t_end=6.0)
    assert np.allclose(sorted(tt), [0.1, 0.2, 4.0])
    assert np.isclose(stats.percentile(tt, 50), 0.2)
    assert np.isclose(stats.percentile(tt, 90), 0.2 + 0.8 * 3.8)


def test_out_tok_s_over_the_whole_window():
    times = [[0.5, 1.5, 2.5], [3.9, 4.0, 6.0], []]
    # tokens at 1.5, 2.5, 3.9, 4.0 inside [1, 5]: 4 tokens over 4 s
    assert stats.tokens_per_s(times, (1.0, 5.0)) == 1.0


def test_itl_gaps_inside_the_window():
    times = [[0.5, 1.5, 2.0, 6.0], [1.0, 1.1]]
    gaps = stats.inter_token_gaps(times, (1.0, 5.0))
    assert np.allclose(sorted(gaps), [0.1, 0.5])


def test_end_to_end_names():
    out = stats.end_to_end([1.0], [1.5], [[1.5, 1.6]], (0.0, 10.0), 10.0)
    assert set(out) == {"out_tok_s", "ttft_p50_s", "ttft_p90_s", "itl_p95_ms"}
    assert np.isclose(out["itl_p95_ms"], 100.0)
