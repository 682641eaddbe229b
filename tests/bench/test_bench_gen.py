"""Traffic and input generation: deterministic per seed, different across
seeds, the same work for every seed."""

import collections

import gen
import numpy as np

CHAT = {"arrivals": "poisson", "rate_per_s": 3.0,
        "prompt_buckets": [[256, 0.3], [512, 0.3], [1024, 0.25], [1536, 0.15]],
        "output_buckets": [[64, 0.4], [128, 0.4], [256, 0.2]],
        "tiers": [["interactive", 0.25], ["batch", 0.45], ["best_effort", 0.3]]}
BIG = 2**31 + 12345


def _key(reqs):
    return [(r.due, r.prompt_len, r.output_len, r.tier) for r in reqs]


def test_open_loop_deterministic_and_seeded():
    a, b = gen.open_loop(CHAT, BIG, 40), gen.open_loop(CHAT, BIG, 40)
    c = gen.open_loop(CHAT, BIG + 1, 40)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    assert len(a) == len(c) == 120


def test_every_seed_gets_the_same_work():
    a, c = gen.open_loop(CHAT, 7, 40), gen.open_loop(CHAT, BIG, 40)
    for field in ("prompt_len", "output_len", "tier"):
        assert (collections.Counter(getattr(r, field) for r in a)
                == collections.Counter(getattr(r, field) for r in c))
    gaps = lambda rs: sorted(np.round(np.diff([r.due for r in rs] + [40.0]), 9))
    assert gaps(a) == gaps(c)
    assert all(0 <= r.due < 40 for r in a)
    assert collections.Counter(r.prompt_len for r in a)[256] == 36


def test_bursty_arrivals_deterministic():
    mix = dict(CHAT, arrivals="bursty", bursty={"mean_gap_s": 0.5})
    a, b = gen.open_loop(mix, 3, 40), gen.open_loop(mix, 3, 40)
    assert _key(a) == _key(b) and len(a) > 0
    assert _key(a) != _key(gen.open_loop(mix, 4, 40))


def test_prompts_and_batches():
    r = gen.Request(5, 0.0, 16, 4, "batch")
    p = gen.prompt_tokens(r, BIG, 100)
    assert p.dtype == np.int32 and p.shape == (16,) and p.min() >= 2
    assert np.array_equal(p, gen.prompt_tokens(r, BIG, 100))
    assert not np.array_equal(p, gen.prompt_tokens(r, BIG + 1, 100))
    data = {"classes": 4, "separation": 6.0, "trend": 0.5, "spike_rate": 0.01,
            "spike_size": 8.0, "missing_rate": 0.01}
    x = gen.neubot_batches(data, 256, 8, 2, BIG)
    y = gen.neubot_batches(data, 256, 8, 2, BIG)
    assert x[0].dtype == np.float32 and x[0].shape == (256, 8)
    assert np.array_equal(x[1], y[1], equal_nan=True)
    assert not np.array_equal(x[0], x[1], equal_nan=True)
    assert np.isnan(x[0]).any()


def test_apportion():
    assert gen.apportion(10, [0.3, 0.3, 0.25, 0.15]) == [3, 3, 3, 1]
    assert gen.apportion(20, [0.3, 0.3, 0.25, 0.15]) == [6, 6, 5, 3]
    assert sum(gen.apportion(7, [1, 1, 1])) == 7
