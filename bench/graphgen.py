"""The resident graph of a configuration, made from `--seed`.

The generator is the Graph500 specification's Kronecker generator: each
of edgefactor * 2^SCALE edges picks, bit by bit, one quadrant of the
adjacency matrix with probabilities A, B, C and D = 1 - A - B - C, and
the vertex labels are then randomly permuted.  The edges are made
undirected, and self loops and repeated edges are dropped, since the
patterns are counted in a simple graph.

The topology comes from the configuration's own `topology_seed`, so
every run serves the same degree sequence and the same compiled shapes
(the widest degree bucket is the maximum degree, and it is baked into
each count program).  `--seed` draws the spec's label permutation, so
the arrays on the device differ from seed to seed while their shapes do
not.
"""
from __future__ import annotations

import numpy as np


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, seed: int) -> np.ndarray:
    """[n * edge_factor, 2] int64 Kronecker edge draws (repeated edges and
    self loops included, as the generator makes them)."""
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(seed)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for bit in range(scale):
        r = rng.random(m)
        go_right = r >= a + b
        go_down = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        src |= go_down.astype(np.int64) << bit
        dst |= go_right.astype(np.int64) << bit
    return np.stack([src, dst], axis=1)


def simple_edges(n: int, edges: np.ndarray) -> np.ndarray:
    """Undirected simple edges (lo, hi), lo < hi, each once, sorted."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    lo = np.minimum(e[:, 0], e[:, 1])
    hi = np.maximum(e[:, 0], e[:, 1])
    key = np.unique(lo * n + hi)
    return np.stack([key // n, key % n], axis=1)


def make_edges(gen: dict, seed: int) -> tuple[int, np.ndarray]:
    """(n, simple undirected edge array) of a configuration's graph with
    its vertex ids permuted by `seed`."""
    if gen["kind"] != "kronecker":
        raise ValueError(f"unknown generator {gen['kind']!r}")
    n = 1 << int(gen["scale"])
    raw = kronecker_edges(int(gen["scale"]), int(gen["edge_factor"]),
                     float(gen["a"]), float(gen["b"]), float(gen["c"]),
                     int(gen["topology_seed"]))
    perm = np.random.default_rng(seed_sequence(seed, "ids")).permutation(n)
    return n, simple_edges(n, perm[raw])


def seed_sequence(seed: int, stream: str) -> np.random.SeedSequence:
    """One independent random stream per use of `--seed` (any whole
    number, taken modulo 2**64)."""
    return np.random.SeedSequence(
        [int(seed) % (1 << 64), *stream.encode("utf-8")])
