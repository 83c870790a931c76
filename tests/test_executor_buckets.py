"""Degree-bucketed frontier expansion (§Perf, graphpi cell) vs oracle."""
import numpy as np
import pytest

from repro.core.executor import (
    ExecutorConfig, Matcher, auto_buckets, count_embeddings,
)
from repro.core.oracle import count_embeddings_oracle
from repro.core.pattern import clique, cycle, house, star
from repro.core.plan import best_iep_k, build_plan
from repro.core.restrictions import generate_restriction_sets
from repro.core.schedule import generate_schedules
from repro.graph.datasets import erdos_renyi, rmat


@pytest.fixture(scope="module")
def graph():
    # small power-law graph: heavy-tailed degrees make bucketing matter
    return rmat(8, 6, seed=7, name="rmat8")


@pytest.fixture(scope="module")
def er():
    return erdos_renyi(128, 768, seed=3)


def _plan(pattern, iep=False):
    rs = generate_restriction_sets(pattern, max_sets=1)[0]
    order = generate_schedules(pattern)[0]
    k = best_iep_k(pattern, order, rs) if iep else 0
    return build_plan(pattern, order, rs, iep_k=k)


# house/star4 on the rmat graph dominate wall time → tagged slow
# (cycle4/clique3 keep bucketed-vs-oracle coverage in the default run)
PATTERNS = [pytest.param(house(), id="house", marks=pytest.mark.slow),
            pytest.param(cycle(4), id="cycle4", marks=pytest.mark.slow),
            pytest.param(clique(3), id="clique3"),
            pytest.param(star(4), id="star4", marks=pytest.mark.slow)]


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("iep", [False, True], ids=["enum", "iep"])
def test_bucketed_matches_oracle(graph, pattern, iep):
    plan = _plan(pattern, iep=iep)
    expect = count_embeddings_oracle(graph.n, graph.edge_array(), pattern)
    cfg = ExecutorConfig(capacity=1 << 12,
                         degree_buckets=auto_buckets(graph))
    got = Matcher(graph, plan, cfg).count()
    assert not got.overflowed
    assert got.count == expect


@pytest.mark.parametrize("buckets", [
    ((8, 1.0), (10**9, 0.5)),
    ((4, 1.0), (16, 0.5), (10**9, 0.25)),
    ((2, 0.5), (10**9, 1.0)),
], ids=["two", "three", "tiny-first"])
@pytest.mark.slow
def test_bucket_layout_invariance(er, buckets):
    """Any bucket layout must give the same exact count."""
    plan = _plan(house())
    base = count_embeddings(er, plan, ExecutorConfig(capacity=1 << 12))
    got = Matcher(er, plan,
                  ExecutorConfig(capacity=1 << 12,
                                 degree_buckets=buckets)).count()
    assert got.count == base.count
    assert not got.overflowed


@pytest.mark.slow
def test_bucket_overflow_escalates(er):
    """Deliberately tiny bucket fractions force capacity escalation; the
    count must stay exact."""
    plan = _plan(house())
    expect = count_embeddings_oracle(er.n, er.edge_array(), house())
    cfg = ExecutorConfig(capacity=1 << 9,
                         degree_buckets=((8, 1 / 32), (10**9, 1 / 32)))
    got = Matcher(er, plan, cfg).count()
    assert got.count == expect
    assert not got.overflowed


def test_auto_buckets_shape(graph):
    b = auto_buckets(graph)
    if b is not None:
        widths = [w for w, _ in b]
        assert widths == sorted(widths)
        assert widths[-1] >= graph.max_degree
        assert all(0 < f <= 1.0 for _, f in b)


# ------------------------------------------------------ window fill ----
def _window_recount(graph, plan, buckets, C):
    """(rows, valid entries, slots) over every bucket window a count
    launches, recounted on the host: the partial embeddings of each
    level enumerated in Python, each row's base the first lowest-degree
    predecessor, its valid entries that base's degree; a window launches
    when its bucket holds a row and streams cap x width slots."""
    deg = np.asarray(graph.degrees)
    adj = [set(graph.indices[graph.indptr[v]:graph.indptr[v + 1]].tolist())
           for v in range(graph.n)]
    W = int(deg.max())
    widths = [min(w, W) for w, _ in buckets]
    layout = list(zip(widths, [0] + widths[:-1],
                      [max(int(C * f), 8) for _, f in buckets]))

    def windows(dbs):
        out = np.zeros(3, dtype=np.int64)
        for k, (w, lo, cap) in enumerate(layout):
            sel = [d for d in dbs
                   if d > lo and (k == len(layout) - 1 or d <= w)]
            if sel:
                assert len(sel) <= cap          # no overflow: exact sums
                out += (len(sel), sum(sel), cap * w)
        return out

    def base(emb, preds):
        return emb[min(preds, key=lambda p: deg[emb[p]])]

    total = np.zeros(3, dtype=np.int64)
    frontier = [(v,) for v in range(graph.n)]
    for i in range(1, plan.depth):
        total += windows([deg[base(e, plan.preds[i])] for e in frontier])
        nxt = []
        for e in frontier:
            cand = set.intersection(*(adj[e[p]] for p in plan.preds[i]))
            for c in sorted(cand):
                if all(c > e[j] if d > 0 else c < e[j]
                       for j, d in plan.restr[i]) and \
                        all(c != e[j] for j in plan.neqs[i]):
                    nxt.append(e + (c,))
        frontier = nxt
    for U in (plan.iep.unions if plan.iep is not None else ()):
        total += windows([deg[base(e, U)] for e in frontier])
    return total


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["portable", "interpret"])
@pytest.mark.parametrize("pattern,iep", [(clique(3), False),
                                         (star(4), True)],
                         ids=["triangle", "star4-iep"])
def test_window_fill_equals_a_host_recount(pattern, iep, use_pallas):
    """The rows, valid entries and streamed slots the traced dispatches
    report equal a host recount of the bucket layout, on both paths."""
    from repro.obs import Tracer, get_tracer, set_tracer

    g = rmat(6, 4, seed=2, name="rmat6")
    plan = _plan(pattern, iep=iep)
    buckets = ((4, 0.5), (16, 0.25), (10**9, 0.125))
    cfg = ExecutorConfig(capacity=1 << 10, degree_buckets=buckets,
                         use_pallas=use_pallas)
    old = get_tracer()
    tracer = set_tracer(Tracer(enabled=True))
    try:
        got = Matcher(g, plan, cfg).count()
    finally:
        set_tracer(old)
    assert got.count == count_embeddings_oracle(g.n, g.edge_array(),
                                                pattern)
    disp = [s["attrs"] for s in tracer.spans()
            if s["name"] == "executor.dispatch"]
    assert disp and all(a["needed"] <= a["capacity"] for a in disp)
    sums = [sum(a[k] for a in disp)
            for k in ("rows_live", "entries_valid", "entries_streamed")]
    assert sums == _window_recount(g, plan, buckets, cfg.capacity).tolist()
    for a in disp:
        assert [sum(w[k] for w in a["windows"]) for k in (1, 2, 3)] == [
            a["rows_live"], a["entries_valid"], a["entries_streamed"]]
