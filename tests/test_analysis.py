"""Static analysis layer: soundness proofs over the pattern library,
seeded violations of every rule class, the repo-invariant lint on the
live tree, kernel contract checks, and the PlanStore fsck/verify
integration (ISSUE 6 acceptance).

The hypothesis property tests skip cleanly when hypothesis is absent
(optional dev dependency); `test_random_patterns_fallback` is the
`slow`-marked deterministic stand-in that covers the same invariant.
"""
import dataclasses
import json
import os
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import (
    ERROR, Finding, error_count, format_findings, has_errors,
    verify_configuration, verify_plan, verify_restriction_set,
    verify_schedule,
)
from repro.analysis.kernel_contracts import (
    LevelExpandSpec, abstract_eval_spec, check_graph_contract, check_spec,
)
from repro.analysis.lint import lint_source, lint_tree
from repro.configs.graphpi import PATTERNS, get_pattern
from repro.core.executor import ExecutorConfig, compute_stats
from repro.core.pattern import Pattern
from repro.core.plan import best_iep_k, build_plan
from repro.core.restrictions import generate_restriction_sets
from repro.core.schedule import generate_schedules
from repro.graph.datasets import erdos_renyi
from repro.query import PlanStore, QueryEngine, QueryRequest
from repro.query.store import SCHEMA_VERSION

CFG = ExecutorConfig(capacity=1 << 12)
REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def tiny_graph():
    return erdos_renyi(64, 256, seed=7, name="er64")


@pytest.fixture(scope="module")
def tiny_stats(tiny_graph):
    return compute_stats(tiny_graph, CFG)


# ------------------------------------------------------------- soundness
@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_generated_sets_verify_clean(name):
    """Every restriction set the planner can emit for P1-P6 proves sound."""
    pat = get_pattern(name)
    for rs in generate_restriction_sets(pat):
        assert not verify_restriction_set(pat, rs), (name, rs)


@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_built_plans_verify_clean(name):
    pat = get_pattern(name)
    rs = generate_restriction_sets(pat)[0]
    order = generate_schedules(pat)[0]
    for k in (0, best_iep_k(pat, order, rs)):
        plan = build_plan(pat, order, rs, iep_k=k)
        findings = verify_plan(plan)
        assert not has_errors(findings), format_findings(findings)


def test_incomplete_set_flagged():
    tri = get_pattern("triangle")
    findings = verify_restriction_set(tri, ((0, 1),))
    rules = {f.rule for f in findings}
    # all three independent proofs fail for a half-complete set
    assert {"restriction-survivors", "restriction-order-count",
            "restriction-partition"} <= rules


def test_malformed_and_contradictory_pairs_flagged():
    tri = get_pattern("triangle")
    assert has_errors(verify_restriction_set(tri, ((0, 7),)))
    assert has_errors(verify_restriction_set(tri, ((1, 1),)))
    f = verify_restriction_set(tri, ((0, 1), (1, 0)))
    assert any(x.rule == "restriction-range" for x in f)


def test_disconnected_schedule_flagged():
    path3 = Pattern(3, ((0, 1), (1, 2)), name="path3")
    f = verify_schedule(path3, (0, 2, 1))   # vertex 2 has no earlier nbr
    assert any(x.rule == "schedule-connected" for x in f)
    f = verify_schedule(path3, (0, 0, 1))
    assert any(x.rule == "schedule-permutation" for x in f)


def test_naive_mode_empty_set_is_clean():
    """Naive records carry no restrictions (count divided by |Aut| at
    execution); the verifier must not demand completeness of them."""
    pat = get_pattern("P1")
    order = generate_schedules(pat)[0]
    plan = build_plan(pat, order, ())
    assert not has_errors(verify_plan(plan, mode="naive"))
    assert has_errors(verify_plan(plan, mode="graphpi"))


def _flip(rs, i):
    return tuple((b, a) if j == i else (a, b)
                 for j, (a, b) in enumerate(rs))


@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_flipped_restriction_in_plan_always_flagged(name):
    """A flipped pair inside a PERSISTED plan always drifts from the
    rebuild (the positional dir sign changes), even when the flipped set
    happens to be a valid complete set in its own right."""
    pat = get_pattern(name)
    rs = generate_restriction_sets(pat)[0]
    order = generate_schedules(pat)[0]
    plan = build_plan(pat, order, rs)
    for i in range(len(rs)):
        mutated = dataclasses.replace(plan, res_set=_flip(rs, i))
        assert has_errors(verify_plan(mutated)), (name, i)


def _iep_case():
    """First (pattern, order, res_set, k>=1) the planner yields."""
    for name in ("rectangle", "P1", "P2", "P3"):
        pat = get_pattern(name)
        rs = generate_restriction_sets(pat)[0]
        for order in generate_schedules(pat):
            k = best_iep_k(pat, order, rs)
            if k >= 1:
                return pat, order, rs, k
    raise AssertionError("no IEP-foldable configuration found")


def test_divisor_and_positional_tampering_flagged():
    pat, order, rs, k = _iep_case()
    plan = build_plan(pat, order, rs, iep_k=k)
    assert not has_errors(verify_plan(plan))

    wrong_div = dataclasses.replace(plan, iep_divisor=plan.iep_divisor * 2)
    assert any(f.rule == "iep-multiplicity"
               for f in verify_plan(wrong_div))

    # a positional restriction pointing at a LATER position can never be
    # checked where it is scheduled
    restr = list(plan.restr)
    restr[1] = ((2, +1),)
    bad_pos = dataclasses.replace(plan, restr=tuple(restr))
    assert any(f.rule in ("restriction-checkable", "plan-derived-drift")
               for f in verify_plan(bad_pos))


# ------------------------------------------- property test (+ fallback)
def _random_pattern(rng) -> Pattern:
    n = int(rng.integers(4, 7))
    edges = set()
    for i in range(1, n):
        edges.add((int(rng.integers(0, i)), i))
    for _ in range(int(rng.integers(0, 5))):
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        if u != v:
            edges.add((min(u, v), max(u, v)))
    return Pattern(n, tuple(sorted(edges)), name=f"rand{n}")


def _assert_pattern_invariants(pat):
    sets = generate_restriction_sets(pat, max_sets=4)
    assert sets
    order = generate_schedules(pat)[0]
    for rs in sets:
        assert not verify_restriction_set(pat, rs), (pat, rs)
        plan = build_plan(pat, order, rs)
        assert not has_errors(verify_plan(plan))
        for i in range(len(rs)):
            mutated = dataclasses.replace(plan, res_set=_flip(rs, i))
            assert has_errors(verify_plan(mutated)), (pat, rs, i)


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @st.composite
    def _hyp_patterns(draw):
        n = draw(st.integers(min_value=4, max_value=6))
        edges = set()
        for i in range(1, n):
            edges.add((draw(st.integers(0, i - 1)), i))
        for (u, v) in draw(st.lists(
                st.tuples(st.integers(0, n - 1), st.integers(0, n - 1)),
                max_size=4)):
            if u != v:
                edges.add((min(u, v), max(u, v)))
        return Pattern(n, tuple(sorted(edges)), name=f"rand{n}")

    @settings(max_examples=20, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(_hyp_patterns())
    def test_random_patterns_property(pattern):
        _assert_pattern_invariants(pattern)

except ImportError:
    @pytest.mark.slow
    def test_random_patterns_fallback():
        """Deterministic stand-in for the hypothesis property test."""
        rng = np.random.default_rng(0)
        for _ in range(25):
            _assert_pattern_invariants(_random_pattern(rng))


# ------------------------------------------------------------------ lint
def test_lint_clean_on_live_tree():
    findings = lint_tree(REPO_ROOT)
    assert not has_errors(findings), format_findings(findings)


def test_lint_scheduler_rules():
    src = ("import time\nimport jax\nimport random\n"
           "def pick():\n"
           "    return jax.numpy.zeros(1), time.time(), random.random()\n")
    rules = {f.rule for f in lint_source(src, "serve/scheduler.py")}
    assert {"scheduler-no-jax", "scheduler-determinism"} <= rules
    # the same module is fine anywhere else on the no-jax front
    rules_elsewhere = {f.rule for f in lint_source(src, "query/engine.py")}
    assert "scheduler-no-jax" not in rules_elsewhere


def test_lint_no_raw_timing():
    src = "import time\ndef t():\n    return time.perf_counter()\n"
    # serve/ and query/ must route timing through repro.obs ...
    for rel in ("serve/scheduler.py", "query/engine.py",
                "src/repro/serve/gateway.py"):
        f = lint_source(src, rel)
        assert {x.rule for x in f} == {"no-raw-timing"}, rel
    # ... in every spelling
    f = lint_source("from time import monotonic, sleep\n",
                    "serve/gateway.py")
    assert [x.rule for x in f] == ["no-raw-timing"]   # sleep not flagged
    # obs/ is the sanctioned home; other layers keep their own clocks
    assert not lint_source(src, "src/repro/obs/trace.py")
    assert not lint_source(src, "core/config_search.py")


def test_lint_no_stale_fingerprint():
    src = ("class Engine:\n"
           "    def __init__(self, graph, stats):\n"
           "        self.fp = graph.fingerprint\n"          # attr store
           "    def rekey(self, graph, stats):\n"
           "        self._key = graph_fingerprint(graph, stats)\n")
    for rel in ("serve/gateway.py", "query/engine.py",
                "src/repro/query/cache.py"):
        f = [x for x in lint_source(src, rel)
             if x.rule == "no-stale-fingerprint"]
        assert len(f) == 2, rel
    # locals don't outlive a round — reading fingerprints at the use
    # site is exactly what the rule steers toward
    ok = ("def f(graph, stats):\n"
          "    fp = graph.fingerprint\n"
          "    return graph_fingerprint(graph, stats), fp\n")
    assert not lint_source(ok, "serve/gateway.py")
    # epoch objects are the sanctioned long-lived identity
    epoch = ("class Engine:\n"
             "    def bump(self, live, stats):\n"
             "        self._epoch = EpochStamp.for_live(live, stats)\n")
    assert not lint_source(epoch, "query/engine.py")
    # outside serve/query the engine's lifecycle rules don't apply
    assert not lint_source(src, "core/executor.py")


def test_lint_tracer_concretize():
    src = ("import jax\nfrom functools import partial\n"
           "@partial(jax.jit, static_argnames=('n',))\n"
           "def f(x, n):\n"
           "    k = int(x.shape[0])\n"        # static shape read: allowed
           "    return int(x[0]) + x.sum().item() + k\n")
    f = lint_source(src, "kernels/ops.py")
    assert len([x for x in f if x.rule == "no-tracer-concretize"]) == 2
    # kernel bodies are traced even without a jit decorator
    f = lint_source("def _f_body(r, o):\n    o[0] = float(r[0])\n",
                    "kernels/intersect.py")
    assert has_errors(f)
    # the same calls outside any traced body are not flagged
    assert not lint_source("def f(x):\n    return int(x)\n", "core/misc.py")


# ------------------------------------------------------- kernel contracts
def test_kernel_spec_clean_and_violations():
    ok = LevelExpandSpec(B=64, D=16, P=2, E=2, flat_len=512)
    assert not check_spec(ok)
    dma = dataclasses.replace(ok, block_l=384)     # not a power of two
    assert any(f.rule == "kernel-dma-window" for f in check_spec(dma))
    blk = dataclasses.replace(ok, block_b=6)
    assert any(f.rule == "kernel-block-shape" for f in check_spec(blk))
    of = dataclasses.replace(ok, flat_len=2**31 - 10)
    assert any(f.rule == "kernel-int32-offset" for f in check_spec(of))
    smem = dataclasses.replace(ok, P=5000)
    assert any(f.rule == "kernel-smem" for f in check_spec(smem))


def test_kernel_abstract_eval_clean():
    for spec in (
        LevelExpandSpec(B=64, D=16, P=2, E=2, flat_len=512),
        LevelExpandSpec(B=64, D=16, P=2, E=1, flat_len=512, count=True),
        LevelExpandSpec(B=64, D=20, P=2, flat_len=512, count=True,
                        neg_from=16),
    ):
        findings = abstract_eval_spec(spec)
        assert not has_errors(findings), format_findings(findings)


def test_kernel_graph_contract(tiny_graph):
    assert not has_errors(check_graph_contract(tiny_graph, CFG, deep=True))
    # shape-only probe: a graph too big for int32 CSR offsets is refused
    f = check_graph_contract((10**10, 2 * 10**9, 1000))
    assert any(x.rule == "kernel-int32-offset" for x in f)


# ------------------------------------------------- store verify + fsck
def workload():
    return [
        QueryRequest(get_pattern("P1")),
        QueryRequest(get_pattern("triangle")),
        QueryRequest(get_pattern("rectangle"), use_iep=True),
    ]


@pytest.fixture()
def warm_store(tmp_path, tiny_graph, tiny_stats):
    root = str(tmp_path / "plan-store")
    engine = QueryEngine(tiny_graph, cfg=CFG, store=PlanStore(root),
                         stats=tiny_stats)
    results = engine.serve(workload())
    return root, [r.count for r in results]


def _flip_record_pair(vdir):
    """Flip one restriction pair inside some persisted plan record;
    returns the tampered digest."""
    for fname in sorted(os.listdir(vdir)):
        if not fname.endswith(".json") or fname.startswith("stats-"):
            continue
        path = os.path.join(vdir, fname)
        with open(path) as f:
            rec = json.load(f)
        rs = rec["plan"]["res_set"]
        if rs:
            rs[0] = [rs[0][1], rs[0][0]]
            with open(path, "w") as f:
                json.dump(rec, f)
            return fname[: -len(".json")], rec
    raise AssertionError("no record with restrictions")


def test_load_rejects_unsound_record(warm_store):
    root, _ = warm_store
    store = PlanStore(root)
    digest, rec = _flip_record_pair(store.vdir)
    assert store._load_digest(digest) is None
    assert store.stats.verify_fails == 1
    assert store.stats.rejects.get("verify") == 1


def test_fsck_quarantines_and_untouched_replay(warm_store, tiny_graph,
                                               tiny_stats):
    root, counts = warm_store
    store = PlanStore(root)
    digest, _ = _flip_record_pair(store.vdir)

    report = store.fsck()
    assert report["checked"] == 3
    assert report["quarantined"] == 1
    assert digest in report["findings"]
    assert has_errors(report["findings"][digest])
    qjson = os.path.join(store.vdir, "quarantine", digest + ".json")
    assert os.path.exists(qjson)
    assert not os.path.exists(os.path.join(store.vdir, digest + ".json"))

    # a second fsck over the now-clean store finds nothing new
    again = PlanStore(root).fsck()
    assert again["quarantined"] == 0 and again["checked"] == 2

    # the workload still replays correctly: the two untouched records
    # come from disk, only the quarantined one re-searches (and its
    # write-behind heals the store)
    engine = QueryEngine(tiny_graph, cfg=CFG, store=PlanStore(root),
                         stats=tiny_stats)
    results = engine.serve(workload())
    assert [r.count for r in results] == counts      # counts unchanged
    assert engine.cache.stats.n_searches == 1        # only the quarantined

    # after healing, a fresh replica replays the whole workload cold-free
    healed = QueryEngine(tiny_graph, cfg=CFG, store=PlanStore(root),
                         stats=tiny_stats)
    results = healed.serve(workload())
    assert [r.count for r in results] == counts
    assert healed.cache.stats.n_searches == 0


def test_graph_stats_persist_and_reload(tmp_path, tiny_graph):
    root = str(tmp_path / "stats-store")
    e1 = QueryEngine(tiny_graph, cfg=CFG, store=PlanStore(root))
    spath = os.path.join(root, f"v{SCHEMA_VERSION}",
                         f"stats-{tiny_graph.fingerprint}.json")
    assert os.path.exists(spath)

    store2 = PlanStore(root)
    e2 = QueryEngine(tiny_graph, cfg=CFG, store=store2)
    assert e2.stats == e1.stats
    assert store2.stats.loads >= 1           # no recount happened

    # corrupt stats record: engine degrades to recompute, never raises
    with open(spath, "w") as f:
        f.write("{not json")
    store3 = PlanStore(root)
    e3 = QueryEngine(tiny_graph, cfg=CFG, store=store3)
    assert e3.stats == e1.stats
    assert store3.stats.rejects.get("stats_corrupt") == 1


def test_fsck_validates_stats_record(tmp_path, tiny_graph):
    root = str(tmp_path / "stats-fsck")
    store = PlanStore(root)
    stats = compute_stats(tiny_graph, CFG)
    assert store.save_graph_stats(tiny_graph.fingerprint, stats)
    clean = PlanStore(root).fsck()
    assert clean["stats_checked"] == 1 and clean["quarantined"] == 0

    spath = store._stats_path(tiny_graph.fingerprint)
    with open(spath) as f:
        rec = json.load(f)
    rec["graph_fingerprint"] = "deadbeef"
    with open(spath, "w") as f:
        json.dump(rec, f)
    report = PlanStore(root).fsck()
    assert report["stats_checked"] == 1 and report["quarantined"] == 1


# ------------------------------------------------------------------- CLI
def test_cli_lint_clean_tree_exits_zero():
    from repro.analysis.__main__ import main

    assert main(["--lint", "--root", str(REPO_ROOT)]) == 0


def test_cli_fsck_flags_tampered_store(warm_store, capsys):
    from repro.analysis.__main__ import main

    root, _ = warm_store
    _flip_record_pair(os.path.join(root, f"v{SCHEMA_VERSION}"))
    assert main(["--fsck", root]) == 1
    out = capsys.readouterr().out
    assert "quarantined" in out


def test_finding_severity_validated():
    with pytest.raises(ValueError):
        Finding("fatal", "rule", "loc", "msg")
    fs = [Finding(ERROR, "r", "l", "m")]
    assert has_errors(fs) and error_count(fs) == 1

# --------------------------------------- labeled patterns (ISSUE 8)
def _random_labeled_pattern(rng) -> Pattern:
    """Random connected 4-6-vertex pattern with a random label
    assignment (3 classes + occasional wildcard slots)."""
    base = _random_pattern(rng)
    labels = tuple(
        int(rng.integers(0, 3)) if rng.random() < 0.8 else None
        for _ in range(base.n)
    )
    return base.with_labels(labels)


def test_labeled_restrictions_kill_exactly_label_subgroup():
    """Randomized (fixed-seed) labeled patterns: symmetry breaking must
    operate on EXACTLY the label-preserving automorphism subgroup — the
    generated sets keep n!/|Aut_label| orders and eliminate every
    non-identity label-preserving automorphism, and the built plans
    re-prove sound end to end.  Label-aware plans must also never emit
    MORE restrictions than their unlabeled skeletons (a smaller group
    needs fewer-or-equal breakers)."""
    import math

    from repro.core.pattern import identity_perm
    from repro.core.restrictions import (
        count_orders_satisfying, surviving_perms,
    )

    rng = np.random.default_rng(42)
    symmetry_broken = 0
    for _ in range(25):
        pat = _random_labeled_pattern(rng)
        auts = pat.automorphisms()
        skel = pat.skeleton()
        assert set(auts) <= set(skel.automorphisms())
        sets = generate_restriction_sets(pat, max_sets=4)
        skel_sets = generate_restriction_sets(skel, max_sets=4)
        assert sets
        assert min(len(rs) for rs in sets) <= \
            min(len(rs) for rs in skel_sets), (pat, sets, skel_sets)
        order = generate_schedules(pat)[0]
        for rs in sets:
            assert surviving_perms(auts, rs) == [identity_perm(pat.n)]
            assert count_orders_satisfying(pat.n, rs) == \
                math.factorial(pat.n) // len(auts)
            assert not verify_restriction_set(pat, rs), (pat, rs)
            plan = build_plan(pat, order, rs)
            assert plan.vlabels is not None
            assert not has_errors(verify_plan(plan))
        if len(auts) < len(skel.automorphisms()):
            symmetry_broken += 1
    # the sweep must actually exercise label-broken symmetry, not just
    # patterns whose labels happen to preserve the full group
    assert symmetry_broken >= 8


def test_labels_killing_all_symmetry_yield_empty_restriction_set():
    """A path typed L0-L1-L2 has trivial Aut_label: the only sound
    restriction set is the empty one (every ordering kept)."""
    pat = Pattern(3, ((0, 1), (1, 2)), labels=(0, 1, 2))
    assert len(pat.automorphisms()) == 1
    sets = generate_restriction_sets(pat)
    assert sets == [()]
    assert not verify_restriction_set(pat, ())
    # the SKELETON still has the reversal symmetry and needs a breaker
    assert all(len(rs) >= 1
               for rs in generate_restriction_sets(pat.skeleton()))


def _save_labeled_record(store, stats, pattern):
    """Search + persist one labeled pattern the way the cache would;
    returns (key, digest)."""
    from repro.core.config_search import search_configuration
    from repro.query.cache import PlanCache
    from repro.query.canon import canonical_form

    canon = canonical_form(pattern)
    best = search_configuration(canon, stats).best
    plan = build_plan(canon, best.order, best.res_set, iep_k=best.iep_k)
    key = PlanCache.entry_key(canon, ("gfp", 64, 256, 1), CFG)
    digest = store.save(key, pattern=canon, config=best, plan=plan)
    assert digest is not None
    return key, digest


@pytest.mark.parametrize("labels,flip", [
    ((0, 1, 1), {0: 1, 1: 0}),          # triangle: swap both classes
    ((2, 0, 2), {0: 2, 2: 0}),          # triangle: structure-preserving
    ((0, 1, 2), {0: 3}),                # all-distinct: retype one role
    # NOTE: a flip that merely PERMUTES distinct label values on a fully
    # symmetric skeleton — e.g. (0,1,2) -> (0,2,1) on a triangle — is
    # label-ISOMORPHIC to the original (same canonical class, same
    # count) and is correctly accepted, so it is not a case here.
])
def test_flipped_label_tamper_always_flagged_by_fsck(tmp_path, tiny_stats,
                                                     labels, flip):
    """Satellite: flipping labels inside a persisted record — even a
    CONSISTENT flip across pattern, embedded plan pattern, and vlabels,
    which keeps every internal invariant green — must be rejected by the
    loader and flagged by fsck: the record's canonical key no longer
    matches the slot it is filed under."""
    store = PlanStore(str(tmp_path / "store"))
    pat = get_pattern("triangle").with_labels(labels)
    key, digest = _save_labeled_record(store, tiny_stats, pat)

    path = os.path.join(store.vdir, digest + ".json")
    with open(path) as f:
        rec = json.load(f)
    sub = lambda x: flip.get(x, x)                     # noqa: E731
    rec["pattern"]["labels"] = [sub(x) for x in rec["pattern"]["labels"]]
    rec["plan"]["pattern"]["labels"] = [
        sub(x) for x in rec["plan"]["pattern"]["labels"]]
    rec["plan"]["vlabels"] = [sub(x) for x in rec["plan"]["vlabels"]]
    with open(path, "w") as f:
        json.dump(rec, f)

    fresh = PlanStore(store.root)
    assert fresh.load(key) is None
    assert fresh.stats.rejects.get("key-pattern-mismatch") == 1

    report = PlanStore(store.root).fsck()
    assert digest in report["findings"]
    assert any(f.rule == "key-pattern-mismatch"
               for f in report["findings"][digest])
    assert report["quarantined"] == 1
    assert not os.path.exists(path)


def test_inconsistent_label_tamper_flagged(tmp_path, tiny_stats):
    """Flipping ONLY the plan's vlabels (pattern left alone) is internal
    drift — verify_plan's vlabels rebuild check catches it even before
    the key comparison."""
    store = PlanStore(str(tmp_path / "store"))
    pat = get_pattern("triangle").with_labels((0, 1, 2))
    key, digest = _save_labeled_record(store, tiny_stats, pat)

    path = os.path.join(store.vdir, digest + ".json")
    with open(path) as f:
        rec = json.load(f)
    rec["plan"]["vlabels"] = [rec["plan"]["vlabels"][i]
                              for i in (1, 0, 2)]
    with open(path, "w") as f:
        json.dump(rec, f)
    assert PlanStore(store.root).load(key) is None
    report = PlanStore(store.root).fsck()
    assert digest in report["findings"]
    assert has_errors(report["findings"][digest])


def test_lint_label_coverage():
    """Dropping the labels reference from an identity surface — or the
    surface itself — is a lint ERROR; the live tree stays clean (covered
    by test_lint_clean_on_live_tree)."""
    src = ("def canonical_key(p):\n"
           "    return str(p.n)\n"
           "def _wl_cells(p):\n"
           "    return [p.labels]\n")
    f = lint_source(src, "src/repro/query/canon.py")
    assert any(x.rule == "label-coverage" and "canonical_key" in x.message
               for x in f)
    assert not any("_wl_cells" in x.message for x in f)

    # surface renamed/removed entirely -> also flagged
    f2 = lint_source("x = 1\n", "src/repro/core/plan.py")
    assert any(x.rule == "label-coverage" and "plan_to_dict" in x.message
               for x in f2)

    # unrelated modules are exempt
    assert not any(x.rule == "label-coverage"
                   for x in lint_source("x = 1\n", "src/repro/obs/core.py"))
