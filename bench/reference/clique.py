"""Cliques K_k.

Every edge is oriented from the endpoint of lower (degree, id) rank to
the higher one.  A k-clique then has exactly one order in which each of
its vertices reaches all later ones, so it is counted once: the
(j+1)-cliques are the j-cliques extended by an out-neighbour of their
last vertex that every earlier vertex also reaches.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from . import degrees


def is_clique(pattern: dict) -> bool:
    k = int(pattern["n"])
    edges = {tuple(sorted(map(int, e))) for e in pattern["edges"]}
    return (k >= 1 and len(pattern["edges"]) == k * (k - 1) // 2
            and edges == {(u, v) for u in range(k)
                          for v in range(u + 1, k)})


def count(n: int, edges, pattern: dict) -> int:
    if not is_clique(pattern):
        raise ValueError(f"not a clique: {pattern}")
    k = int(pattern["n"])
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if k <= 2:
        return n if k == 1 else len(e)
    rank = degrees(n, e).astype(np.int64) * n + np.arange(n)
    up = rank[e[:, 0]] < rank[e[:, 1]]
    src = np.where(up, e[:, 0], e[:, 1])
    dst = np.where(up, e[:, 1], e[:, 0])
    out = sp.csr_matrix((np.ones(len(e), np.int8), (src, dst)),
                        shape=(n, n))
    out.sort_indices()
    ptr = out.indptr.astype(np.int64)
    nbr = out.indices.astype(np.int64)
    keys = np.repeat(np.arange(n, dtype=np.int64), np.diff(ptr)) * n + nbr

    def reaches(u, v):
        at = np.minimum(np.searchsorted(keys, u * n + v), len(keys) - 1)
        return keys[at] == u * n + v

    rows = [src, dst]                      # the 2-cliques, in rank order
    for _ in range(k - 2):
        last = rows[-1]
        d = ptr[last + 1] - ptr[last]
        row = np.repeat(np.arange(len(last)), d)
        x = nbr[np.repeat(ptr[last], d) + np.arange(len(row))
                - np.repeat(np.cumsum(d) - d, d)]
        for r in rows[:-1]:
            keep = reaches(r[row], x)
            row, x = row[keep], x[keep]
        rows = [r[row] for r in rows] + [x]
    return len(rows[0])
