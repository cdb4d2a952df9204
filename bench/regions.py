"""Region reads of a 3-D mesh variable: the read patterns of the paper's
Fig. 6 (arXiv:2107.07108), with their positions drawn from the seed.

Copied from the program's ``core/read_patterns.py`` (axes ``(X, Y, Z)``,
slabs ``thickness`` thick), so that the traffic stays as it is when the
program changes.  Reads come in decks: each deck holds every pattern as
often as its weight says, in an order shuffled from the seed, so that every
seed sends the same mix of sizes and only the order and positions differ.
"""

from __future__ import annotations

import numpy as np

PATTERNS = ("whole_domain", "sub_area", "plane_yz", "plane_xz", "plane_xy",
            "line_z")


def region(pattern: str, shape, rng: np.random.Generator,
           thickness: int = 1) -> tuple:
    """``(lo, hi)`` of one read of ``pattern``, placed by ``rng``."""
    X, Y, Z = shape
    t = thickness
    if pattern == "whole_domain":
        return (0, 0, 0), (X, Y, Z)
    if pattern == "sub_area":
        lo = tuple(int(rng.integers(0, n // 2)) for n in shape)
        return lo, tuple(a + n // 2 for a, n in zip(lo, shape))
    if pattern == "plane_yz":
        x = int(rng.integers(0, X - t + 1))
        return (x, 0, 0), (x + t, Y, Z)
    if pattern == "plane_xz":
        y = int(rng.integers(0, Y - t + 1))
        return (0, y, 0), (X, y + t, Z)
    if pattern == "plane_xy":
        z = int(rng.integers(0, Z - t + 1))
        return (0, 0, z), (X, Y, z + t)
    if pattern == "line_z":
        x = int(rng.integers(0, X - t + 1))
        y = int(rng.integers(0, Y - t + 1))
        return (x, y, 0), (x + t, y + t, Z)
    raise ValueError(f"unknown pattern {pattern!r}")


def reads(shape, weights: dict, seed: int, thickness: int = 1):
    """Endless ``(pattern, lo, hi)`` reads, deck after deck."""
    for p, w in weights.items():
        if p not in PATTERNS or int(w) != w or w < 0:
            raise ValueError(f"bad pattern weight {p!r}: {w!r}")
    deck = [p for p, w in weights.items() for _ in range(int(w))]
    if not deck:
        raise ValueError("a mix of no reads")
    rng = np.random.default_rng(seed)
    while True:
        for i in rng.permutation(len(deck)):
            p = deck[i]
            lo, hi = region(p, shape, rng, thickness)
            yield p, lo, hi
