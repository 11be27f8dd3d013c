"""The traffic generator: seeded, and the same work for every seed."""

import numpy as np
import pytest

from bench.generator import Traffic, quantiles

CHAT = {"arrivals": "open", "rate_rps": 5.0,
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                   "min": 32, "max": 1024, "pool": 64},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                   "min": 16, "max": 512}}
BATCH = {"arrivals": "backlog",
         "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                    "min": 64, "max": 768, "pool": 32},
         "output": {"dist": "loguniform", "min": 128, "max": 512}}


def _take(t, n):
    return [t.pop() for _ in range(n)]


@pytest.mark.parametrize("mix", [CHAT, BATCH], ids=["open", "backlog"])
def test_same_seed_same_inputs(mix):
    a = _take(Traffic(mix, 49152, 2**31 + 77, 30), 200)
    b = _take(Traffic(mix, 49152, 2**31 + 77, 30), 200)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]


@pytest.mark.parametrize("mix", [CHAT, BATCH], ids=["open", "backlog"])
def test_seeds_share_sizes_not_order(mix):
    """Another seed: the same sequence of lengths and arrival times, other
    tokens.  The seed does not reorder the work, so it cannot move which
    long answers fall inside the window."""
    t1, t2 = Traffic(mix, 49152, 1, 30), Traffic(mix, 49152, 2**40 + 3, 30)
    n = 3 * t1.block
    a, b = _take(t1, n), _take(t2, n)
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.due for r in a] == [r.due for r in b]
    assert [len(p) for p in t1.pool] == [len(p) for p in t2.pool]
    assert not np.array_equal(t1.pool[0][:16], t2.pool[0][:16])
    # Blocks past the first keep the same sizes in another order.
    blocks = [[r.max_new_tokens for r in a[i * t1.block:(i + 1) * t1.block]]
              for i in range(3)]
    assert sorted(blocks[0]) == sorted(blocks[1])
    assert blocks[0] != blocks[1]
    if mix["arrivals"] == "backlog":
        assert all(r.due == 0 for r in a + b)


def test_open_loop_rate_and_lengths():
    t = Traffic(CHAT, 49152, 5, 30)
    reqs = _take(t, 3 * t.block)
    assert t.block == 150
    gaps = np.diff([0.0] + [r.due for r in reqs])
    assert gaps.mean() == pytest.approx(1 / 5.0, rel=0.05)
    assert min(len(r.prompt) for r in reqs) >= 32
    assert max(len(r.prompt) for r in reqs) <= 1024
    assert all(16 <= r.max_new_tokens <= 512 for r in reqs)
    assert all(0 <= int(r.prompt.min()) and int(r.prompt.max()) < 49152
               for r in reqs)


def test_quantiles_follow_the_distribution():
    q = quantiles({"dist": "lognormal", "median": 256, "sigma": 0.8,
                   "min": 1, "max": 10**6}, 1001)
    assert q[500] == 256
    q = quantiles({"dist": "loguniform", "min": 128, "max": 512}, 1000)
    assert q.min() >= 128 and q.max() <= 512
    assert np.median(q) == pytest.approx(256, rel=0.01)
