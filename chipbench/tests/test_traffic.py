"""The traffic generator: schedules and sizes from the seed."""
import numpy as np

from lib import spec, traffic

BIG = 2 ** 31 + 12345


def _mix(name):
    return spec.traffic(name)


def test_open_loop_schedule_is_the_seeds_and_fills_the_window():
    mix = _mix("complete")
    n = traffic.open_loop_count(mix, 30)
    assert n == int(mix["arrivals"]["rate_per_s"] * 30)
    a = traffic.requests(mix, BIG, n, 1000)
    b = traffic.requests(mix, BIG, n, 1000)
    assert [r.arrival for r in a] == [r.arrival for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    t = np.array([r.arrival for r in a])
    assert t[0] == 0 and np.all(np.diff(t) > 0) and t[-1] < 30


def test_every_seed_asks_the_same_work_in_another_order():
    mix = _mix("complete")
    a = traffic.requests(mix, 1, 120, 1000)
    b = traffic.requests(mix, BIG, 120, 1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # the gaps are one set of quantiles in two orders; the n arrivals use
    # n - 1 of the n gaps, so the two schedules share all but one
    ga, gb = np.diff([r.arrival for r in a]), np.diff([r.arrival
                                                       for r in b])
    shared = np.intersect1d(np.round(ga, 9), np.round(gb, 9))
    assert len(shared) >= len(ga) - 1
    assert abs(ga.sum() - gb.sum()) < max(ga.max(), gb.max())


def test_sizes_follow_the_mix():
    mix = _mix("complete")
    lens = traffic.quantiles(mix["prompt_len"], 1000)
    assert lens.min() >= 256 and lens.max() <= 3968
    assert abs(np.median(lens) - 1500) <= 10
    new = traffic.quantiles(mix["max_new"], 1000)
    assert new.min() == 2 and new.max() == 128
    assert abs(np.median(new) - 13) <= 1
    # prompt plus output stays inside the published 4096-token window
    assert lens.max() + traffic.max_new_limit(mix) <= 4096
    gen = _mix("batch-gen")
    lens = traffic.quantiles(gen["prompt_len"], 1000)
    assert lens.min() == 32 and lens.max() == 448
    assert abs(np.median(lens) - 128) <= 2


def test_a_budget_gives_each_request_the_rest_of_its_total():
    gen = _mix("batch-gen")
    reqs = traffic.requests(gen, BIG, 300, 1000)
    assert all(len(r.prompt) + r.max_new == 512 for r in reqs)
    assert traffic.max_new_limit(gen) == 512 - 32
    assert max(r.max_new for r in reqs) <= traffic.max_new_limit(gen)


def test_training_rows_differ_every_step():
    rows = traffic.TrainRows(_mix("train-16x512"), BIG, 30522)
    a, b = rows.next(), rows.next()
    assert a["tokens"].shape == (16, 512)
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert not np.array_equal(a["tokens"], b["tokens"])
