"""Triangles K3.

Every edge is oriented from the endpoint of lower (degree, id) rank to
the higher one; each triangle then has exactly one vertex from which
both others are reached, and sum((U @ U) * U) counts each once.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from . import degrees


def is_triangle(pattern: dict) -> bool:
    edges = {tuple(sorted(map(int, e))) for e in pattern["edges"]}
    return (int(pattern["n"]) == 3 and len(pattern["edges"]) == 3
            and edges == {(0, 1), (0, 2), (1, 2)})


def count(n: int, edges, pattern: dict) -> int:
    if not is_triangle(pattern):
        raise ValueError(f"not a triangle: {pattern}")
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    rank = degrees(n, e).astype(np.int64) * n + np.arange(n)
    up = rank[e[:, 0]] < rank[e[:, 1]]
    src = np.where(up, e[:, 0], e[:, 1])
    dst = np.where(up, e[:, 1], e[:, 0])
    u = sp.csr_matrix((np.ones(len(e), np.int64), (src, dst)), shape=(n, n))
    return int((u @ u).multiply(u).sum())
