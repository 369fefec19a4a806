"""The traffic generator and the window's end-to-end arithmetic."""
from collections import Counter

import numpy as np
import pytest

from bench import spec, traffic
from bench.window import Program, Record, end_to_end

BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("mix_name", ["longdoc", "chat"])
def test_every_seed_serves_the_same_work_in_the_same_order(mix_name):
    mix = spec.load_traffic(mix_name)
    k = mix["block"]
    n = 2 * k
    a = traffic.generate(mix, 1000, BIG_SEED, n)
    b = traffic.generate(mix, 1000, 7, n)
    work = lambda rs: [(len(r.prompt), r.max_new, r.arrival_s, r.prefix)
                       for r in rs]
    assert work(a) == work(b)
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # each block is the same stratified sample of lengths
    for size in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert Counter(map(size, a[:k])) == Counter(map(size, a[k:]))
    for r in a:
        assert len(r.prompt) + r.max_new <= mix["max_len"]
        assert 0 <= r.prompt.min() and r.prompt.max() < 1000
    again = traffic.generate(mix, 1000, BIG_SEED, n)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.arrival_s == y.arrival_s for x, y in zip(a, again))


def test_open_loop_arrivals_and_shared_prefixes():
    mix = spec.load_traffic("chat")
    k = mix["block"]
    reqs = traffic.generate(mix, 1000, 3, 2 * k)
    t = [r.arrival_s for r in reqs]
    assert t == sorted(t) and t[0] > 0
    # each block's gaps are the same stratified sample of the exponential
    assert t[2 * k - 1] - t[k - 1] == pytest.approx(t[k - 1])
    assert t[k - 1] == pytest.approx(
        traffic.exponential_quantiles(mix["rate"], k).sum())
    # every system prompt is used equally often in a block, and is a prefix
    counts = Counter(r.prefix for r in reqs[:k])
    assert set(counts.values()) == {k // mix["shared"]["count"]}
    plen = mix["shared"]["length"]
    for r in reqs:
        same = [q for q in reqs if q.prefix == r.prefix]
        assert all(np.array_equal(q.prompt[:plen], r.prompt[:plen])
                   for q in same)


def test_seed_words_cover_large_seeds():
    assert traffic.seed_words(5) == [5]
    assert traffic.seed_words(2 ** 32 + 3) == [3, 1]


def _progs(readies, tokens):
    return [Program("serve", 0.0, n, [], [], ready=r)
            for r, n in zip(readies, tokens)]


def test_tokens_per_s_counts_what_was_ready_by_the_end():
    progs = _progs([1.0, 2.0, 11.0], [8, 8, 8])
    out = end_to_end([], progs, t0=0.0, end=10.0)
    assert out["tokens_per_s"] == pytest.approx(16 / 10)


def test_a_censored_request_raises_ttft():
    progs = _progs([1.0, 2.0, 3.0, 4.0], [1, 1, 1, 1])
    served = [Record(i, due=0.0, prompt_len=8, max_new=4, first_prog=i)
              for i in range(4)]
    base = end_to_end(served, progs, 0.0, 10.0)["ttft_p95_s"]
    stuck = Record(9, due=1.0, prompt_len=8, max_new=4)   # no first token
    late = Record(10, due=2.0, prompt_len=8, max_new=4,
                  first_prog=None)
    out = end_to_end(served + [stuck, late], progs, 0.0, 10.0)
    assert out["ttft_p95_s"] > base
    assert out["ttft_p95_s"] == pytest.approx(
        np.percentile([1, 2, 3, 4, 9, 8], 95))
    assert out["attempted"] == 6
    # a request due after the window's end is not attempted
    after = Record(11, due=10.5, prompt_len=8, max_new=4)
    assert end_to_end(served + [after], progs, 0.0, 10.0)["attempted"] == 4


def test_tpot_leaves_out_the_first_token():
    progs = _progs([2.0, 3.0, 6.0], [1, 4, 4])
    r = Record(0, due=0.0, prompt_len=8, max_new=9, first_prog=0,
               last_prog=2, tokens=np.arange(9))
    out = end_to_end([r], progs, 0.0, 10.0)
    # 8 tokens after the first, delivered between t=2 and t=6
    assert out["tpot_p95_ms"] == pytest.approx((6.0 - 2.0) / 8 * 1e3)
    assert out["ttft_p95_s"] == pytest.approx(2.0)
    # finished after the window's end: no tpot sample
    progs[2].ready = 12.0
    assert end_to_end([r], progs, 0.0, 10.0)["tpot_p95_ms"] is None
