"""Fused Pallas level-expansion kernel: parity at every layer.

1. Kernel vs the pure-jnp oracle (kernels/ref.py) on random CSR-layout
   windows — the kernel gathers every predecessor neighborhood from the
   flat array INSIDE the grid (scalar-prefetched offsets + per-row DMA
   of the aligned tiles that cover the row),
   so these tests feed (flat, starts, lens), never a stacked [P, B, W]
   array.  Mask and count modes, ragged/empty rows, all three comparison
   kinds (restriction >, restriction <, injectivity !=), and the signed
   count (`neg_from`) that carries the IEP prefix corrections.
2. Executor counts with the fused kernel (use_pallas=True — interpret
   lowering on CPU) vs the portable binary-search path vs the brute
   oracle, for every oracle pattern and the paper's P1-P6, enum and IEP
   modes, with and without degree buckets, including capacity-overflow
   escalation and graphs with empty neighborhoods.  Counts must be
   bit-identical.
"""
import numpy as np
import pytest

from repro.configs.graphpi import get_pattern
from repro.core.executor import ExecutorConfig, count_embeddings
from repro.core.oracle import count_embeddings_oracle
from repro.core.pattern import clique, cycle, house, rectangle, star, triangle
from repro.core.plan import best_iep_k, build_plan
from repro.core.restrictions import generate_restriction_sets
from repro.core.schedule import generate_schedules
from repro.graph.datasets import erdos_renyi, rmat
from repro.kernels import ops, ref

# house/cycle5 are the slowest whole-executor parity cases → tagged slow;
# the remaining patterns keep fused-vs-portable coverage in the default run
PATTERNS = [pytest.param(p, id=p.name,
                         marks=pytest.mark.slow
                         if p.name in ("house", "cycle5") else [])
            for p in (triangle(), rectangle(), house(), clique(4), cycle(5),
                      star(4))]


# ------------------------------------------------------------- kernel ----
def _csr_windows(seed, B=24, D=37, P=3, L=50, vmax=200, empty_frac=0.1):
    """Random CSR-layout test data: a flat pool of strictly-increasing
    rows (one per (p, b), lengths 0..L — including empty neighborhoods)
    plus the (starts, lens) offset arrays the kernel prefetches."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, L + 1, size=(P, B)).astype(np.int32)
    lens[rng.random((P, B)) < empty_frac] = 0
    rows = []
    starts = np.zeros((P, B), np.int32)
    off = 0
    for p in range(P):
        for b in range(B):
            starts[p, b] = off
            row = np.sort(rng.choice(vmax, size=lens[p, b], replace=False))
            rows.append(row.astype(np.int32))
            off += lens[p, b]
    flat = np.concatenate(rows) if rows else np.zeros(0, np.int32)
    cand = rng.integers(0, vmax, size=(B, D)).astype(np.int32)
    cand_valid = rng.random((B, D)) < 0.8
    extra = rng.integers(0, vmax, size=(B, 3)).astype(np.int32)
    return cand, flat, starts, lens, extra, cand_valid


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("count", [False, True], ids=["mask", "count"])
def test_level_expand_matches_ref(seed, count):
    cand, flat, starts, lens, extra, valid = _csr_windows(seed)
    dirs = (1, -1, 0)
    got = ops.level_expand(cand, flat, starts, lens, extra, valid,
                           dirs=dirs, count=count, interpret=True)
    want = ref.level_expand_ref(cand, flat, starts, lens, extra, valid,
                                dirs=dirs, count=count, window=50)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("neg_from", [0, 20, 37])
def test_level_expand_signed_count_matches_ref(seed, neg_from):
    """The fused IEP tail: columns ≥ neg_from subtract (the prefix
    corrections ride along as negatively-weighted candidates)."""
    cand, flat, starts, lens, _, valid = _csr_windows(seed)
    got = ops.level_expand(cand, flat, starts, lens, None, valid,
                           count=True, neg_from=neg_from, interpret=True)
    want = ref.level_expand_ref(cand, flat, starts, lens, None, valid,
                                count=True, neg_from=neg_from, window=50)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_level_expand_no_extras_single_pred():
    cand, flat, starts, lens, _, valid = _csr_windows(3, P=1)
    got = ops.level_expand(cand, flat, starts, lens, None, valid,
                           interpret=True)
    want = ref.level_expand_ref(cand, flat, starts, lens, None, valid,
                                window=50)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_level_expand_all_rows_empty():
    """Empty neighborhoods: no tile is fetched at all, nothing matches."""
    cand, flat, starts, lens, _, valid = _csr_windows(5, P=2)
    lens[:] = 0
    got = ops.level_expand(cand, flat, starts, lens, None, valid,
                           count=True, interpret=True)
    assert not np.asarray(got).any()
    got_m = ops.level_expand(cand, flat, starts, lens, None, valid,
                             interpret=True)
    assert not np.asarray(got_m).any()


def test_level_expand_block_shape_invariance():
    """Block layout must not change results (grid/accumulator/tile-walk
    logic) — including tiles wider than most rows and rows that straddle
    several narrow tiles."""
    cand, flat, starts, lens, extra, valid = _csr_windows(
        4, B=16, D=40, P=2, L=70)
    dirs = (1, 0, 0)
    base = np.asarray(ref.level_expand_ref(
        cand, flat, starts, lens, extra, valid, dirs=dirs, window=70))
    for bb, bl in [(8, 128), (4, 32), (16, 256)]:
        got = ops.level_expand(cand, flat, starts, lens, extra, valid,
                               dirs=dirs, block_b=bb, block_l=bl,
                               interpret=True)
        np.testing.assert_array_equal(np.asarray(got), base)


# ----------------------------------------------------------- executor ----
def _plan(pattern, iep):
    order = generate_schedules(pattern)[0]
    rs = generate_restriction_sets(pattern, max_sets=1)[0]
    k = best_iep_k(pattern, order, rs) if iep else 0
    if iep and k < 1:
        return None
    return build_plan(pattern, order, rs, iep_k=k)


@pytest.fixture(scope="module")
def er():
    return erdos_renyi(48, 220, seed=5)


@pytest.fixture(scope="module")
def pl_graph():
    # power-law graph: skewed windows + sentinel padding + real buckets
    return rmat(7, 5, seed=9, name="rmat7")


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("iep", [False, True], ids=["enum", "iep"])
def test_fused_matches_portable_and_oracle(er, pattern, iep):
    plan = _plan(pattern, iep)
    if plan is None:
        pytest.skip("no sound IEP folding for this configuration")
    want = count_embeddings_oracle(er.n, er.edge_array(), pattern)
    portable = count_embeddings(
        er, plan, ExecutorConfig(capacity=1 << 10, use_pallas=False))
    fused = count_embeddings(
        er, plan, ExecutorConfig(capacity=1 << 10, use_pallas=True))
    assert portable.count == want
    assert fused.count == want                 # bit-identical, not approx
    assert fused.overflowed == portable.overflowed


# P3/P5/P6 are the big interpret-mode patterns → slow tier (tier1 --all)
@pytest.mark.parametrize("pname", [
    pytest.param(p, marks=pytest.mark.slow if p in ("P3", "P5", "P6")
                 else [])
    for p in ("P1", "P2", "P3", "P4", "P5", "P6")])
def test_fused_iep_tail_matches_portable_P1_P6(er, pname):
    """The satellite parity matrix: kernel-fused IEP cardinalities vs
    the portable (separate binary-search sweep) path on the paper's
    P1-P6, bit-identical counts.  Patterns without a sound foldable
    tail fall back to enum — still exercised for parity."""
    pattern = get_pattern(pname)
    plan = _plan(pattern, iep=True) or _plan(pattern, iep=False)
    want = count_embeddings_oracle(er.n, er.edge_array(), pattern)
    portable = count_embeddings(
        er, plan, ExecutorConfig(capacity=1 << 10, use_pallas=False))
    fused = count_embeddings(
        er, plan, ExecutorConfig(capacity=1 << 10, use_pallas=True))
    assert portable.count == want
    assert fused.count == want
    assert fused.overflowed == portable.overflowed
    assert fused.max_needed == portable.max_needed


def test_fused_iep_overflow_escalation_parity(er):
    """Truncation/overflow edge: a capacity too small for the frontier
    forces the bisection + escalation driver; the fused-IEP path must
    report the same exact count and overflow state as the portable
    path (counts stay exact through escalation)."""
    pattern = star(4)
    plan = _plan(pattern, iep=True)
    assert plan is not None
    portable = count_embeddings(
        er, plan, ExecutorConfig(capacity=128, use_pallas=False))
    fused = count_embeddings(
        er, plan, ExecutorConfig(capacity=128, use_pallas=True))
    want = count_embeddings_oracle(er.n, er.edge_array(), pattern)
    assert portable.count == fused.count == want
    assert portable.overflowed == fused.overflowed
    assert portable.max_needed == fused.max_needed


def test_fused_iep_empty_neighborhoods():
    """Graphs with isolated vertices: zero-length predecessor rows must
    contribute nothing (their window DMAs are skipped entirely)."""
    rng = np.random.default_rng(11)
    edges = rng.integers(0, 30, size=(60, 2))     # vertices 30..39 isolated
    from repro.graph.csr import GraphCSR

    g = GraphCSR.from_edges(40, edges, name="isolated")
    assert (g.degrees == 0).any()
    pattern = star(4)
    plan = _plan(pattern, iep=True)
    want = count_embeddings_oracle(g.n, g.edge_array(), pattern)
    portable = count_embeddings(
        g, plan, ExecutorConfig(capacity=1 << 10, use_pallas=False))
    fused = count_embeddings(
        g, plan, ExecutorConfig(capacity=1 << 10, use_pallas=True))
    assert portable.count == fused.count == want


@pytest.mark.parametrize("pattern", [
    pytest.param(house(), id="house", marks=pytest.mark.slow),
    pytest.param(clique(4), id="clique4"),
])
def test_fused_bucketed_matches_oracle(pl_graph, pattern):
    plan = _plan(pattern, iep=False)
    want = count_embeddings_oracle(pl_graph.n, pl_graph.edge_array(), pattern)
    got = count_embeddings(
        pl_graph, plan,
        ExecutorConfig(capacity=1 << 10, use_pallas=True,
                       degree_buckets=((8, 1.0), (10**9, 0.5))))
    assert got.count == want


@pytest.mark.slow
def test_fused_iep_bucketed_matches_portable(pl_graph):
    """Degree-bucketed + IEP + fused kernel: every (union, bucket)
    cardinality is one fused pass; counts must stay bit-identical."""
    pattern = star(4)
    plan = _plan(pattern, iep=True)
    assert plan is not None
    cfg = dict(capacity=1 << 10,
               degree_buckets=((8, 1.0), (10**9, 0.5)))
    portable = count_embeddings(
        pl_graph, plan, ExecutorConfig(use_pallas=False, **cfg))
    fused = count_embeddings(
        pl_graph, plan, ExecutorConfig(use_pallas=True, **cfg))
    want = count_embeddings_oracle(pl_graph.n, pl_graph.edge_array(), pattern)
    assert portable.count == fused.count == want
