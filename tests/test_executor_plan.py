"""Span planning of the outer loop (core/executor.py `need_parts`,
`Matcher.planned_spans`): a fresh count cuts its root spans from a host
prediction of each span's `needed`, and the device's `needed` still
decides — bisection and escalation keep every count exact."""
import os
import sys

import numpy as np
import pytest

from repro.configs.graphpi import get_pattern
from repro.core.config_search import search_configuration
from repro.core.executor import (
    CountState, ExecutorConfig, Matcher, auto_buckets, device_graph,
    window_steps,
)
from repro.core.oracle import count_embeddings_oracle
from repro.core.pattern import clique, cycle, star
from repro.core.perf_model import GraphStats
from repro.core.plan import best_iep_k, build_plan
from repro.graph.csr import GraphCSR
from repro.graph.datasets import erdos_renyi, rmat
from repro.obs import Tracer, get_tracer, set_tracer
from repro.query.canon import canonical_form

BUCKETS = ((8, 0.5), (32, 0.25), (10**9, 0.125))
CFG = ExecutorConfig(capacity=1 << 9, degree_buckets=BUCKETS)
# a star's roots fill the buckets themselves: narrow caps make them bind
STAR_CFG = ExecutorConfig(capacity=1 << 9,
                          degree_buckets=((4, 0.5), (16, 1 / 16),
                                          (10**9, 1 / 64)))


@pytest.fixture(scope="module")
def graph():
    # power-law: the hubs' spans overflow fixed-width chunks
    return rmat(7, 6, seed=5, name="rmat7")


def _searched(pattern, graph, use_iep=False):
    """The plan the engine searches for `pattern` (query/cache.py), with
    the graph's statistics counted on the host."""
    canon = canonical_form(pattern)
    stats = GraphStats(n_vertices=graph.n, n_edges=graph.m,
                       tri_cnt=graph.triangle_count_numpy())
    best = search_configuration(canon, stats, use_iep=use_iep).best
    return canon, build_plan(canon, best.order, best.res_set,
                             iep_k=best.iep_k)


def _case(name, graph):
    """(pattern, plan, config) of the triangle, the 4-clique, or star4
    with its leaves folded by IEP (depth 1, one root a row)."""
    if name in ("triangle", "clique4"):
        return (*_searched(clique(4 if name == "clique4" else 3), graph),
                CFG)
    canon, plan = _searched(star(4), graph)
    k = best_iep_k(canon, plan.order, plan.res_set)
    plan = build_plan(canon, plan.order, plan.res_set, iep_k=k)
    assert plan.depth == 1
    return canon, plan, STAR_CFG


def _fixed(graph, cap, chunk):
    return [(s, min(s + chunk, graph.n), cap)
            for s in range(0, graph.n, chunk)]


def _traced_count(matcher, **kw):
    """(result, dispatch span attrs, count span attrs) of one count."""
    old = get_tracer()
    tr = set_tracer(Tracer(enabled=True))
    try:
        _, out = matcher.count_partial(**kw)
    finally:
        set_tracer(old)
    spans = tr.spans()
    return (out,
            [s["attrs"] for s in spans if s["name"] == "executor.dispatch"],
            [s["attrs"] for s in spans if s["name"] == "executor.count"])


@pytest.mark.parametrize("name", ["triangle", "star4-iep", "clique4"])
def test_planned_dispatches_need_what_was_predicted(graph, name):
    """Triangle, IEP star and 4-clique (whose level-2 frontier and
    level-3 windows the host enumerates) on a power-law graph with three
    buckets: every planned dispatch reads back the `needed` the host
    predicted, none overflows, and fixed spans of the same width would
    have."""
    canon, plan, cfg = _case(name, graph)
    chunk = 64
    m = Matcher(graph, plan, cfg)
    out, disp, (count_span,) = _traced_count(m, chunk=chunk)
    assert out.count == count_embeddings_oracle(
        graph.n, graph.edge_array(), canon)
    assert [(a["v0_start"], a["v0_end"]) for a in disp] == [
        s[:2] for s in reversed(m.planned_spans(cfg.capacity, chunk))]
    assert count_span["planned_spans"] == len(disp)
    assert all(a["needed"] == a["predicted"] <= a["capacity"] for a in disp)
    # the prediction is not just the v0 width
    assert any(a["predicted"] > min(chunk, a["capacity"]) for a in disp)
    fixed = _fixed(graph, cfg.capacity, chunk)
    assert any(m.predicted(s, e, c, chunk) > c for s, e, c in fixed)


@pytest.mark.parametrize("pattern", [cycle(4), clique(4)],
                         ids=["cycle4", "clique4"])
def test_deeper_plans_need_at_least_the_prediction(graph, pattern):
    """Past the levels the host expands (the cycle's level-2 candidates
    outnumber the slots of one dispatch) the device's `needed` is only
    bounded below by the prediction: a dispatch predicted within
    capacity needs at least that, one predicted over it overflows."""
    canon, plan = _searched(pattern, graph)
    assert plan.depth == 4
    out, disp, _ = _traced_count(Matcher(graph, plan, CFG), chunk=32)
    assert out.count == count_embeddings_oracle(
        graph.n, graph.edge_array(), canon)
    for a in disp:
        if a["predicted"] <= a["capacity"]:
            assert a["needed"] >= a["predicted"]
        else:
            assert a["needed"] > a["capacity"]


@pytest.fixture(scope="module")
def small():
    return rmat(5, 4, seed=5, name="rmat5")


@pytest.mark.parametrize("chunk", [8, 32])
@pytest.mark.parametrize("name", ["triangle", "star4-iep", "clique4",
                                  *(f"P{i}" for i in range(1, 7))])
def test_planned_counts_equal_fixed_spans_and_oracle(graph, small, name,
                                                     chunk):
    """Planned and fixed spans give bit-identical raw totals, and the
    count equals the oracle's: the triangle, the IEP star and the
    4-clique on the bucketed power-law graph (where the plans differ at
    the wider chunk), P1-P6 on a small one."""
    if name in ("triangle", "star4-iep", "clique4"):
        g, chunk = graph, chunk * 4
        canon, plan, cfg = _case(name, g)
    else:
        g, cfg = small, ExecutorConfig(capacity=1 << 8)
        canon, plan = _searched(get_pattern(name), g, use_iep=True)
    m = Matcher(g, plan, cfg)
    planned, out = m.count_partial(chunk=chunk)
    fixed, ref = m.count_partial(
        CountState(spans=_fixed(g, cfg.capacity, chunk), chunk=chunk))
    assert planned.total == fixed.total
    assert out.count == ref.count == count_embeddings_oracle(
        g.n, g.edge_array(), canon)
    assert not out.overflowed
    if g is graph and chunk == 128:
        assert m.planned_spans(cfg.capacity, chunk) != _fixed(
            g, cfg.capacity, chunk)


@pytest.mark.parametrize("name", ["triangle", "P1", "P3"])
def test_planned_spans_are_fixed_spans_where_those_fit(name):
    """Where no fixed `chunk`-wide span is predicted to overflow, the plan
    is those spans, and the count dispatches what they dispatch, in the
    same order (P3's deeper levels still overflow and bisect)."""
    g = erdos_renyi(64, 256, seed=7, name="er64")
    cfg = ExecutorConfig(capacity=1 << 12)
    _, plan = _searched(get_pattern(name), g)
    m = Matcher(g, plan, cfg)
    fixed = _fixed(g, cfg.capacity, 8)
    assert all(m.predicted(s, e, c, 8) <= c for s, e, c in fixed)
    assert m.planned_spans(cfg.capacity, 8) == fixed
    _, planned, _ = _traced_count(m, chunk=8)
    _, ref, _ = _traced_count(
        Matcher(g, plan, cfg), state=CountState(spans=fixed, chunk=8))
    assert [(a["v0_start"], a["v0_end"], a["capacity"]) for a in planned] \
        == [(a["v0_start"], a["v0_end"], a["capacity"]) for a in ref]


def test_preempted_planned_count_resumes_to_the_same_count(graph):
    """A max_dispatches=1 resume loop over the planned stack equals one
    uninterrupted count, dispatch for dispatch."""
    _, plan = _searched(clique(3), graph)
    whole = Matcher(graph, plan, CFG)
    _, ref = whole.count_partial(chunk=64)
    ref_state = whole.planned_spans(CFG.capacity, 64)
    m = Matcher(graph, plan, CFG)
    state, out = m.count_partial(chunk=64, max_dispatches=1)
    steps = 1
    while out is None:
        state, out = m.count_partial(state, max_dispatches=1)
        steps += 1
    assert out.count == ref.count
    assert steps == state.dispatches == len(ref_state)


def test_rebind_keeps_counts_exact(graph):
    """After a same-shape rebind the count is still exact: with the new
    host graph the plan is rebuilt from it; with a stale host graph the
    prediction is wrong and the device's `needed` still decides."""
    reverse = np.arange(graph.n)[::-1]
    moved = GraphCSR.from_edges(graph.n, reverse[graph.edge_array()],
                                name="rmat7-rev")
    assert moved.max_degree == graph.max_degree
    canon, plan = _searched(clique(3), graph)
    want = count_embeddings_oracle(moved.n, moved.edge_array(), canon)
    for with_graph in (True, False):
        m = Matcher(graph, plan, CFG)
        before = m.planned_spans(CFG.capacity, 64)
        m.rebind(device_graph(moved), graph=moved if with_graph else None)
        assert m.count(chunk=64).count == want
        if with_graph:
            assert m.planned_spans(CFG.capacity, 64) != before


def _s13(seed):
    """The graph500-s13 cell's graph (labels of `seed`) and the engine's
    capacity and bucket layout, as the served stack builds them."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from graphgen import make_edges
    import server

    with open(os.path.join(bench, "configs", "graph500-s13.json")) as f:
        config = json.load(f)
    n, edges = make_edges(config["generator"], seed)
    g = server.build_graph(n, edges, config["name"])
    cap = int(config["engine"]["capacity"])
    return g, cap, ExecutorConfig(capacity=cap, degree_buckets=auto_buckets(g))


@pytest.fixture(scope="module")
def s13():
    return _s13(7100000011)


def test_graph500_s13_triangle_plans_seven_spans(s13):
    """The premise of planning in the graph500-s13 cell, on the host: the
    searched triangle plan over the cell's graph, capacity and buckets
    cuts 7 spans, none predicted over capacity (fixed spans take 19
    dispatches there, 9 of them overflowing)."""
    g, cap, cfg = s13
    _, plan = _searched(clique(3), g)
    assert plan.preds == ((), (0,), (0, 1))
    m = Matcher(g, plan, cfg)
    spans = m.planned_spans(cap, cap)
    assert [s[:2] for s in spans] == [
        (0, 132), (132, 220), (220, 294), (294, 361), (361, 1031),
        (1031, 2831), (2831, 8192)]
    assert all(m.predicted(s, e, c, cap) <= c for s, e, c in spans)
    assert m.predicted(0, g.n, cap, cap) > cap


def test_graph500_s13_clique4_plans_every_level(s13):
    """The premise of the graph500-s13 4-clique cell, on the host: the
    host plan of the searched 4-clique plan covers all three window
    steps, its level-2 frontier is the graph's 1,181,673 triangles, and
    it cuts 150 spans, none predicted over capacity (the triangle's
    stays 7).  Levels 1-2 alone cut 7 spans, which the device bisects."""
    g, cap, cfg = s13
    _, plan = _searched(clique(4), g)
    assert plan.preds == ((), (0,), (0, 1), (0, 1, 2))
    m = Matcher(g, plan, cfg)
    assert m.predicted_steps() == window_steps(plan) == 3
    frontiers = [int(pre[-1]) for b, pre in m._need_sums() if b is None]
    assert frontiers == [g.m, 1181673]
    spans = m.planned_spans(cap, cap)
    assert len(spans) == 150
    assert all(m.predicted(s, e, c, cap) <= c for s, e, c in spans)
    tri = Matcher(g, _searched(clique(3), g)[1], cfg)
    assert len(tri.planned_spans(cap, cap)) == 7


@pytest.mark.parametrize("seed", [3000000010, 3000000011])
def test_graph500_s13_cut_does_not_move_with_the_seed(seed):
    """The cell's labels come from `--seed`; the degree relabel orders
    equal degrees by refined colour, so the 4-clique still cuts 150 spans
    and the triangle 7 (with ties left in input order, these seeds cut
    149 and 151)."""
    g, cap, cfg = _s13(seed)
    for k, want in ((4, 150), (3, 7)):
        m = Matcher(g, _searched(clique(k), g)[1], cfg)
        assert len(m.planned_spans(cap, cap)) == want


def test_degree_relabel_ignores_the_input_ids():
    """Refined colours follow the vertices through any relabelling of
    the input; where they tell every vertex apart (er64), the relabelled
    CSR is the same array whatever ids the edges came with."""
    from repro.graph.csr import refined_colours

    for g in (rmat(7, 6, seed=5, name="rmat7"),
              erdos_renyi(64, 200, seed=1, name="er64")):
        e = g.edge_array()
        base = refined_colours(g.n, e[:, 0], e[:, 1])
        layouts = set()
        for s in range(4):
            p = np.random.default_rng(s).permutation(g.n)
            pe = np.sort(p[e], axis=1)
            assert np.array_equal(
                refined_colours(g.n, pe[:, 0], pe[:, 1])[p], base)
            h = GraphCSR.from_edges(g.n, pe, relabel_by_degree=True)
            assert h.m == g.m
            layouts.add(h.fingerprint)
        if len(np.unique(base)) == g.n:
            assert len(layouts) == 1
