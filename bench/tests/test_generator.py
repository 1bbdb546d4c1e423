import math

import numpy as np

from bench import generator

MIX = {"rate_rps": 4.0, "warm_s": 5, "drain_s": 0,
       "classes": [
           {"share": 0.9,
            "prompt": {"dist": "lognormal", "median": 384, "sigma": 1.0,
                       "min": 16, "max": 4096},
            "output": {"dist": "lognormal", "median": 160, "sigma": 0.8,
                       "min": 8, "max": 512}},
           {"share": 0.1,
            "prompt": {"dist": "uniform", "min": 2048, "max": 6144},
            "output": {"dist": "uniform", "min": 32, "max": 256}}]}


def sizes(arr):
    return sorted((len(a.prompt), a.max_new_tokens) for a in arr)


def test_same_seed_same_schedule():
    a = generator.arrivals(MIX, 50, 2**31 + 7, 1000)
    b = generator.arrivals(MIX, 50, 2**31 + 7, 1000)
    assert [(x.due_s, x.prompt, x.max_new_tokens) for x in a] == \
           [(x.due_s, x.prompt, x.max_new_tokens) for x in b]


def test_seeds_share_sizes_and_gaps_in_another_order():
    a = generator.arrivals(MIX, 50, 1, 1000)
    b = generator.arrivals(MIX, 50, 2, 1000)
    assert sizes(a) == sizes(b)
    ga = np.diff([x.due_s for x in a])
    gb = np.diff([x.due_s for x in b])
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    # the same gaps but the last, which the horizon leaves out
    assert np.isclose(ga.sum() + a[0].due_s, gb.sum() + b[0].due_s)
    assert np.allclose(sorted(np.r_[a[0].due_s, ga]), sorted(np.r_[b[0].due_s, gb]))


def test_stated_distributions():
    arr = generator.arrivals(MIX, 500, 3, 1000)
    n = len(arr)
    assert n == math.ceil(4.0 * 505)
    rate = n / arr[-1].due_s
    assert abs(rate - 4.0) / 4.0 < 0.05
    chat_p = sorted(len(a.prompt) for a in arr)
    # only documents (10%, uniform 2048-6144) pass 4096: half of them
    over = sum(len(a.prompt) > 4096 for a in arr) / n
    assert abs(over - 0.05) < 0.005
    assert all(16 <= len(a.prompt) <= 6144 for a in arr)
    assert all(8 <= a.max_new_tokens <= 512 for a in arr)
    q = generator.quantiles(MIX["classes"][0]["prompt"], 1001)
    assert q[500] == 384                         # the stated median
    assert q.min() >= 16 and q.max() <= 4096
    assert chat_p[0] >= 16


def test_token_ids_and_due_times():
    arr = generator.arrivals(MIX, 20, 9, 50)
    assert arr[0].due_s > 0
    assert all(a.due_s < b.due_s for a, b in zip(arr, arr[1:]))
    assert all(1 <= t < 50 for a in arr for t in a.prompt)


def test_negative_seed_refused():
    import pytest
    with pytest.raises(SystemExit):
        generator.arrivals(MIX, 5, -1, 100)
