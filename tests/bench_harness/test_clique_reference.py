"""The plain k-clique reference (`bench/reference/clique.py`) against the
repository's brute-force oracle, on the CPU at tiny sizes.

    PYTHONPATH=src python -m pytest tests/bench_harness -q
"""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference  # noqa: E402
from graphgen import simple_edges  # noqa: E402

CLIQUES = {k: {"n": k, "edges": [[u, v] for u in range(k)
                                  for v in range(u + 1, k)]}
           for k in (3, 4, 5)}
STAR4 = {"n": 4, "edges": [[0, 1], [0, 2], [0, 3]]}
FOUR_CYCLE = {"n": 4, "edges": [[0, 1], [1, 2], [2, 3], [0, 3]]}


def tiny_er():
    from repro.graph.datasets import named_dataset

    g = named_dataset("tiny-er")
    return g.n, simple_edges(g.n, g.edge_array())


@pytest.mark.parametrize("k", sorted(CLIQUES))
def test_clique_reference_equals_the_oracle_on_tiny_er(k):
    """On tiny-er (which holds no K5) and on a dense 24-vertex graph."""
    from repro.core.oracle import count_embeddings_oracle
    from repro.core.pattern import Pattern
    from repro.graph.datasets import erdos_renyi

    dense = erdos_renyi(24, 160, seed=3, name="er24")
    pat = CLIQUES[k]
    for n, edges in (tiny_er(), (dense.n, dense.edge_array())):
        want = count_embeddings_oracle(
            n, edges, Pattern(pat["n"], tuple(map(tuple, pat["edges"]))))
        assert reference.reference("clique").count(n, edges, pat) == want
    assert want > 0


def test_clique_reference_refuses_another_pattern():
    n, edges = tiny_er()
    for pat in (STAR4, FOUR_CYCLE):
        assert not reference.reference("clique").is_clique(pat)
        with pytest.raises(ValueError):
            reference.reference("clique").count(n, edges, pat)
    assert reference.reference("clique").is_clique(CLIQUES[3])
