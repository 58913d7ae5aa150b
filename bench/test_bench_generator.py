"""The open-loop schedule repeats exactly for a seed, arrives as a Poisson
process, and every seed gets the same work in another order."""
from __future__ import annotations

import itertools
import math
from collections import Counter

import pytest

from bench import generator, spec

TRAFFIC = {"rate_per_s": 37.5, "operand_seeds": 4,
           "weights": {"a": 2, "b": 2, "c": 1, "d": 1}}
BIG = 2**31 + 987654321


def test_same_seed_same_schedule():
    assert generator.schedule(TRAFFIC, BIG, 20) == generator.schedule(TRAFFIC, BIG, 20)


def test_other_seed_same_work_other_order():
    a = generator.schedule(TRAFFIC, BIG, 20)
    b = generator.schedule(TRAFFIC, BIG + 1, 20)
    assert a != b
    assert len(a) == len(b) == 750
    # the same multiset of gaps: each schedule shows all but the one across
    # its turn, and the one after its last arrival
    gaps = lambda s: Counter(round(y.due_s - x.due_s, 6) for x, y in zip(s, s[1:]))  # noqa: E731
    assert sum((gaps(a) & gaps(b)).values()) >= len(a) - 3
    count = lambda s: {k: sum(x.payload == k for x in s) for k in TRAFFIC["weights"]}  # noqa: E731
    assert count(a) == count(b) == {"a": 250, "b": 250, "c": 125, "d": 125}


def test_arrivals_fall_in_the_window_in_order():
    s = generator.schedule(TRAFFIC, BIG, 20)
    dues = [x.due_s for x in s]
    assert dues == sorted(dues)
    assert 0 <= dues[0] and dues[-1] < 20


def test_operand_seeds_are_few_balanced_and_int32():
    s = generator.schedule(TRAFFIC, BIG, 20)
    seeds = generator.operand_seeds(BIG, 4)
    assert len(set(seeds)) == 4 and all(0 <= x < 2**31 for x in seeds)
    for k in TRAFFIC["weights"]:
        per = [sum(x.payload == k and x.operand_seed == o for x in s) for o in seeds]
        assert max(per) - min(per) <= 1


def test_arrivals_cluster_as_a_poisson_process():
    """Counts in one-second bins vary as a Poisson count's do (variance about
    the mean), gaps are exponential (as many above the mean as a Poisson
    process puts there, e**-1), and payloads come in runs as chance makes them."""
    s = generator.schedule(dict(TRAFFIC, rate_per_s=50), BIG, 200)
    bins = Counter(int(x.due_s) for x in s)
    counts = [bins.get(i, 0) for i in range(200)]
    mean = sum(counts) / len(counts)
    var = sum((c - mean) ** 2 for c in counts) / (len(counts) - 1)
    assert 0.75 < var / mean < 1.3
    gaps = [y.due_s - x.due_s for x, y in zip(s, s[1:])]
    above = sum(g > 200 / len(s) for g in gaps) / len(gaps)
    assert abs(above - math.exp(-1)) < 0.03
    longest = max(len(list(g)) for _, g in itertools.groupby(x.payload for x in s))
    assert longest >= 6


def test_every_seed_turns_the_one_draw():
    """A seed's payloads are the draw's, turned round: the same cycle."""
    draw = [k for _, k in generator.poisson(TRAFFIC, 20)]
    for seed in (BIG, BIG + 1, 7):
        kinds = [x.payload for x in generator.schedule(TRAFFIC, seed, 20)]
        assert any(kinds == draw[i:] + draw[:i] for i in range(len(draw)))


def test_apportion_is_exact():
    assert generator.apportion(7, {"x": 1, "y": 1, "z": 1}) == {"x": 3, "y": 2, "z": 2}
    assert sum(generator.apportion(1001, {"x": 0.3, "y": 0.7}).values()) == 1001


@pytest.mark.parametrize("name", [c["traffic"] for c in spec.benchmark()["workloads"]])
def test_traffic_files_give_a_schedule(name):
    traffic = spec.traffic(name)
    s = generator.schedule(traffic, BIG, 20)
    assert len(s) == round(traffic["rate_per_s"] * 20)


def test_knee_is_the_highest_rate_that_keeps_up():
    from bench import sweep

    row = lambda rate, done, q: {"rate_per_s": rate, "done_in_window_per_s": done,  # noqa: E731
                                 "outstanding_at_quarters": q}
    rows = [row(20, 20.0, [3, 4, 2, 3]), row(25, 24.6, [5, 8, 6, 9]),
            row(28, 27.0, [6, 9, 15, 24]), row(31, 28.0, [20, 30, 40, 50])]
    assert sweep.knee(rows) == 25  # 28 keeps 0.96 of its offer, but its backlog grows
    assert sweep.knee(rows[3:]) is None
