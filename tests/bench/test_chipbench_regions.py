"""The region generator: the same seed gives the same reads, every seed the
same mix of patterns, and every region inside the variable."""

import itertools

import pytest

from bench import regions
from bench.harness import find_mix

SHAPE = (512, 1024, 1024)


def take(seed, n=200, weights=None):
    weights = weights or find_mix("fig6-closed-loop")["weights"]
    return list(itertools.islice(regions.reads(SHAPE, weights, seed), n))


def test_same_seed_same_reads():
    assert take(2**31 + 17) == take(2**31 + 17)
    assert take(1) != take(2)


def test_every_deck_holds_each_pattern_once():
    reads = take(5, n=100)
    for i in range(0, 100, 5):
        assert sorted(p for p, _, _ in reads[i:i + 5]) == sorted(
            find_mix("fig6-closed-loop")["weights"])


def test_regions_inside_and_sized():
    for p, lo, hi in take(9, n=500):
        assert all(0 <= a < b <= n for a, b, n in zip(lo, hi, SHAPE))
        size = [b - a for a, b in zip(lo, hi)]
        want = {"sub_area": [256, 512, 512], "plane_yz": [1, 1024, 1024],
                "plane_xz": [512, 1, 1024], "plane_xy": [512, 1024, 1],
                "line_z": [1, 1, 1024]}[p]
        assert size == want


def test_bad_weights_refused():
    with pytest.raises(ValueError):
        next(regions.reads(SHAPE, {"no_such": 1}, 0))
    with pytest.raises(ValueError):
        next(regions.reads(SHAPE, {"line_z": 0}, 0))
