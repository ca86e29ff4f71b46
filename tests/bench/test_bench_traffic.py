"""The traffic generator: seeded, the same work for every seed."""

import itertools
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import traffic  # noqa: E402

BIG = 2 ** 31 + 12345


def take(it, n):
    return list(itertools.islice(it, n))


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_selection_mix_repeats_for_a_seed(seed):
    mix = traffic.load("closed-novel")
    a = take(traffic.requests(mix, seed, n_indexed=5, n_all=6), 60)
    b = take(traffic.requests(mix, seed, n_indexed=5, n_all=6), 60)
    assert a == b


def test_every_seed_gets_the_same_work_in_another_order():
    mix = traffic.load("closed-novel")
    a = take(traffic.requests(mix, 1, n_indexed=5, n_all=6), 18)
    b = take(traffic.requests(mix, BIG, n_indexed=5, n_all=6), 18)
    assert a != b
    for reqs in (a, b):
        for s in range(0, 18, 6):
            assert sorted(r["family"] for r in reqs[s:s + 6]) == list(range(6))
        assert all(0.9 <= r["scale"] < 1.1 and r["scale"] != 1.0
                   and r["deadline_s"] is None for r in reqs)


def test_a_mix_of_index_hits_asks_for_the_indexed_families():
    mix = dict(traffic.load("closed-novel"), families="indexed",
               census_scale=None)
    a = take(traffic.requests(mix, BIG, n_indexed=5, n_all=6), 10)
    assert sorted(r["family"] for r in a) == sorted(list(range(5)) * 2)
    assert all(r["scale"] is None for r in a)


def test_campaign_requests_scale_every_workload():
    mix = traffic.load("closed-campaigns")
    a = take(traffic.requests(mix, BIG, n_workloads=5), 4)
    assert a == take(traffic.requests(mix, BIG, n_workloads=5), 4)
    assert all(len(r["scales"]) == 5 for r in a)
    assert all(0.9 <= s < 1.1 for r in a for s in r["scales"])
    assert a != take(traffic.requests(mix, 3, n_workloads=5), 4)


@pytest.mark.parametrize("mix", [{"kind": "open"},
                                 {"kind": "campaign", "loop": "open"},
                                 {"kind": "campaign", "clients": 4}])
def test_a_mix_the_drivers_cannot_run_is_refused(mix):
    with pytest.raises(ValueError):
        traffic.requests(mix, 0, n_workloads=5)
