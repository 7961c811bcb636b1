"""The traffic generator: the same seed gives the same requests; another
seed gives the same count and the same multiset of lengths in another order
and pairing; every stretch of the run sees the whole length law."""

import collections

import numpy as np

import traffic


def test_same_seed_same_requests():
    mix = traffic.load_mix("chat-steady")
    a = traffic.make_requests(mix, 3_000_000_017, 45, 32768)
    b = traffic.make_requests(mix, 3_000_000_017, 45, 32768)
    assert a["due_s"] == b["due_s"] and a["max_new"] == b["max_new"]
    assert all(np.array_equal(x, y)
               for x, y in zip(a["prompts"], b["prompts"]))


def test_other_seed_same_multiset_and_count():
    for name in ("chat-steady", "offline-batch"):
        mix = traffic.load_mix(name)
        a = traffic.make_requests(mix, 1, 45, 32768)
        b = traffic.make_requests(mix, 2_147_483_659, 45, 32768)
        assert len(a["prompts"]) == len(b["prompts"]) == \
            traffic.request_count(mix, 45)
        for key in (lambda r: [len(p) for p in r["prompts"]],
                    lambda r: r["max_new"]):
            assert collections.Counter(key(a)) == collections.Counter(key(b))
        assert [len(p) for p in a["prompts"]] != \
            [len(p) for p in b["prompts"]]


def test_lengths_follow_the_law_and_its_limits():
    mix = traffic.load_mix("chat-steady")
    n = 400
    lens = traffic.quantile_lengths(mix["prompt_tokens"], n)
    assert lens.min() >= 32 and lens.max() <= 3072
    assert abs(np.median(lens) - 384) <= 4
    assert (lens[:-1] <= lens[1:]).all()


def test_stratified_order_spreads_the_law():
    rng = np.random.default_rng(5)
    order = traffic.stratified_order(160, 16, rng)
    assert sorted(order.tolist()) == list(range(160))
    for j in range(0, 160, 16):            # one rank from each tenth-ish
        assert sorted(r // 10 for r in order[j:j + 16]) == list(range(16))


def test_arrivals_open_loop_and_closed():
    rng = np.random.default_rng(1)
    t = traffic.arrival_times(
        {"process": "open_loop", "rate_per_s": 2.0, "per_slice": 8},
        90, 45, rng)
    assert len(t) == 90 and (np.diff(t) >= 0).all()
    assert 0 <= t[0] and t[-1] <= 45
    # every slice of the window holds its share (90 in 12 slices: 7 or 8)
    counts = np.histogram(t, bins=np.linspace(0, 45, 13))[0]
    assert set(counts.tolist()) <= {7, 8}
    z = traffic.arrival_times({"process": "all_at_zero",
                               "requests_per_window_s": 1}, 5, 45, rng)
    assert (z == 0).all()


def test_train_batches_from_seed():
    mix = traffic.load_mix("pretrain-1k")
    a = next(traffic.train_batches(mix, 7, 50304, 8))["input_ids"]
    b = next(traffic.train_batches(mix, 7, 50304, 8))["input_ids"]
    assert a.shape == (8, 1024) and a.dtype == np.int32
    assert np.array_equal(a, b) and a.max() < 50304 and a.min() >= 0
