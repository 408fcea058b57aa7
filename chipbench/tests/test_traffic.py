import math
from collections import Counter

import numpy as np

import traffic

MIX = {"prompt": {"median": 256, "sigma": 0.7, "min": 64, "max": 1024},
       "output": {"median": 512, "sigma": 0.5, "min": 128, "max": 1536}}
BIG_SEED = 2**31 + 12345


def test_same_seed_same_schedule():
    a = traffic.schedule(MIX, 0.7, 200, BIG_SEED)
    b = traffic.schedule(MIX, 0.7, 200, BIG_SEED)
    assert a == b
    assert (traffic.token_ids(BIG_SEED, "req3", 100, 32064)
            == traffic.token_ids(BIG_SEED, "req3", 100, 32064)).all()


def test_seeds_reorder_the_same_work_at_the_same_times():
    a = traffic.schedule(MIX, 0.7, 200, 1)
    b = traffic.schedule(MIX, 0.7, 200, 2)
    assert a != b
    for field in ("prompt_len", "output_len"):
        assert Counter(getattr(x, field) for x in a) == \
            Counter(getattr(x, field) for x in b)
    # every seed sees the same arrival times
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert [x.prompt_len for x in a] != [x.prompt_len for x in b]


def test_quantiles_match_parameters():
    n = 4001
    for spec in MIX.values():
        lens = traffic.lognormal_lengths(n, spec)
        assert lens.min() >= spec["min"] and lens.max() <= spec["max"]
        assert abs(np.median(lens) - spec["median"]) <= 1
        # the 84th percentile of a lognormal is median * exp(sigma),
        # where the clip does not reach
        want = spec["median"] * math.exp(spec["sigma"])
        if want < spec["max"]:
            assert abs(np.quantile(lens, 0.8413) / want - 1) < 0.01
    gaps = traffic.exponential_gaps(n, 0.5)
    assert abs(gaps.mean() * 0.5 - 1) < 0.01
    assert abs(np.median(gaps) * 0.5 - math.log(2)) < 1e-3


def test_first_arrival_at_zero_and_rate():
    s = traffic.schedule(MIX, 2.0, 1000, 5)
    assert s[0].due_s == 0.0
    assert all(b.due_s > a.due_s for a, b in zip(s, s[1:]))
    assert abs(s[-1].due_s / 999 * 2.0 - 1) < 0.02


def test_token_ids_in_vocabulary():
    ids = traffic.token_ids(7, "prompt0", 10000, 512)
    assert ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < 512
