"""Plain references for the counts the cells ask for.

Each module here is named by a mix's `reference` key and exposes
`count(n, edges, pattern) -> int`: the exact number of subgraphs of the
simple undirected graph (`edges`: [m, 2] int64, lo < hi, each edge
once) isomorphic to `pattern` ({"n": k, "edges": [[u, v], ...]}).  It
imports nothing of the program under test and takes nothing it made.

`control` is the same count as an int32 accumulator would hold it: the
exact value wrapped to 32 bits.  The configurations state exact int64
counts; int32 accumulation is the cheaper representation a later change
could be tempted by, and this is what it would answer.
"""
from __future__ import annotations

import importlib

import numpy as np


def reference(name: str):
    """The reference module named by a mix's `reference` key."""
    if not name.isidentifier():
        raise ValueError(f"bad reference name {name!r}")
    return importlib.import_module(f"{__name__}.{name}")


def degrees(n: int, edges: np.ndarray) -> np.ndarray:
    return np.bincount(np.asarray(edges).reshape(-1), minlength=n)


def control(exact: int) -> int:
    """`exact` as a wrapping int32 accumulator holds it."""
    return (int(exact) + (1 << 31)) % (1 << 32) - (1 << 31)
