"""The traffic generator: a seed fixes the run, and every seed gets the same
sizes and arrivals in another order."""
import collections

import numpy as np

from perfbench import traffic, tiny

MIX = {
    "tiers": {"8/8": 0.3, "2/2": 0.7},
    "prompt_tokens": {"median": 512, "sigma": 0.7, "min": 128, "max": 2048},
    "output_tokens": {"median": 128, "sigma": 0.7, "min": 32, "max": 512},
    "engine": {"max_batch": 16, "max_len": 2560, "prompt_bucket": 512,
               "decode_chunk": 8},
    "block": 20,
    "lead_in_s": 30,
}


def _plan(seed, rate=5.0, horizon=60.0):
    return traffic.generate(MIX, rate, seed, horizon, 49155)


def test_same_seed_same_requests():
    a, b = _plan(2**33 + 7), _plan(2**33 + 7)
    assert [(p.due_s, p.max_new, p.tier) for p in a] == \
        [(p.due_s, p.max_new, p.tier) for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))


def test_seeds_differ_in_order_not_in_sizes():
    a, b = _plan(1)[:20], _plan(2)[:20]        # one block each
    assert [p.max_new for p in a] != [p.max_new for p in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    for key in (lambda p: len(p.prompt), lambda p: p.max_new,
                lambda p: p.tier):
        assert collections.Counter(map(key, a)) == \
            collections.Counter(map(key, b))
    gaps = lambda plan: sorted(np.diff([0.0] + [p.due_s for p in plan]))
    np.testing.assert_allclose(gaps(a), gaps(b))


def test_lengths_shares_and_rate():
    plan = _plan(5, rate=5.0, horizon=200.0)
    outs = np.asarray([p.max_new for p in plan])
    lens = np.asarray([len(p.prompt) for p in plan])
    assert outs.min() >= 32 and outs.max() <= 512
    assert len(set(outs % 8)) == 8              # chunks end anywhere
    assert lens.min() >= 128 and lens.max() <= 2048
    assert lens.max() > 1900 and outs.max() > 480   # the tails are there
    assert 400 <= np.median(lens) <= 640
    assert abs(sum(p.tier == "8/8" for p in plan) / len(plan) - 0.3) < 0.02
    assert abs(len(plan) / 200.0 - 5.0) < 0.5
    assert all(0 <= p.prompt.min() and p.prompt.max() < 49155 for p in plan)


def test_tier_list_largest_remainder():
    assert collections.Counter(traffic.tier_list({"a": 0.3, "b": 0.7}, 11)) \
        == {"a": 3, "b": 8}


def test_warm_shapes_of_the_mixes():
    assert traffic.prefill_buckets(MIX) == [512, 1024, 1536, 2048]
    assert traffic.prefill_buckets(tiny.MIX) == [16, 32, 48]


def test_backlog_is_due_at_once_then_the_rate():
    mix = dict(MIX, backlog=20)
    plan = traffic.generate(mix, 0.5, 9, 400.0, 49155)
    assert [p.due_s for p in plan[:20]] == [0.0] * 20
    assert plan[20].due_s > 0
    assert abs((len(plan) - 20) / plan[-1].due_s - 0.5) < 0.05
    rest = lambda seed: sorted(np.diff([p.due_s for p in traffic.generate(
        mix, 0.5, seed, 80.0, 49155)[20:40]]))
    np.testing.assert_allclose(rest(1), rest(2))
