"""The open-loop schedule is a pure function of the seed, and every seed
offers the same amount and mix of work."""

from collections import Counter

from benchmarks.generators import open_loop

MIX = {"update": 0.9, "create": 0.05, "delete": 0.05}


def test_same_seed_same_schedule():
    a = open_loop.schedule(2**31 + 17, 240, MIX, 37.0, 1000)
    b = open_loop.schedule(2**31 + 17, 240, MIX, 37.0, 1000)
    assert a == b


def test_seeds_differ_in_order_not_in_work():
    a = open_loop.schedule(1, 240, MIX, 37.0, 1000)
    b = open_loop.schedule(2, 240, MIX, 37.0, 1000)
    assert a != b
    assert len(a) == len(b) == 240 * 37
    assert Counter(k for _d, k, _t, _p, _b in a) == Counter(k for _d, k, _t, _p, _b in b)
    kinds = Counter(k for _d, k, _t, _p, _b in a)
    assert kinds["create"] == kinds["delete"] == round(0.05 * len(a))


def test_dues_sorted_inside_the_length_and_tenants_in_range():
    s = open_loop.schedule(5, 100, MIX, 10.0, 7)
    dues = [d for d, _k, _t, _p, _b in s]
    assert dues == sorted(dues) and 0.0 <= dues[0] and dues[-1] <= 10.0
    assert {t for _d, _k, t, _p, _b in s} <= set(range(7))
