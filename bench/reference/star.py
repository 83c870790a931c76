"""Stars K_{1,k-1}: one centre joined to k-1 leaves.

A star is fixed by its centre and an unordered set of k-1 of the
centre's neighbours, so the count is the sum over vertices v of
C(deg(v), k-1), in Python integers (exact at any size).
"""
from __future__ import annotations

from collections import Counter
from math import comb

from . import degrees


def is_star(pattern: dict) -> bool:
    k = int(pattern["n"])
    edges = [tuple(sorted(map(int, e))) for e in pattern["edges"]]
    if k < 2 or len(set(edges)) != k - 1 or len(edges) != k - 1:
        return False
    ends = Counter(v for e in edges for v in e)
    return max(ends.values()) == k - 1 and set(ends) == set(range(k))


def count(n: int, edges, pattern: dict) -> int:
    if not is_star(pattern):
        raise ValueError(f"not a star: {pattern}")
    leaves = int(pattern["n"]) - 1
    hist = Counter(degrees(n, edges).tolist())
    return sum(mult * comb(d, leaves) for d, mult in hist.items())
