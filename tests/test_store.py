"""Persistent plan store: serialization round-trips, header
invalidation, and the disk-warm restart guarantee (zero configuration
searches, zero fresh JIT traces — ISSUE 4 acceptance)."""
import json
import os

import pytest

from repro.configs.graphpi import get_pattern
from repro.core.config_search import (
    config_from_dict, config_to_dict, search_configuration,
)
from repro.core.executor import ExecutorConfig, compute_stats
from repro.core.plan import build_plan, plan_from_dict, plan_to_dict
from repro.graph.datasets import erdos_renyi
from repro.query import (
    PlanCache, PlanStore, QueryEngine, QueryRequest, relabeled_variant,
)
from repro.query.store import SCHEMA_VERSION, key_digest, repro_fingerprint

CFG = ExecutorConfig(capacity=1 << 12)
ROUND_TRIP_PATTERNS = ["triangle", "rectangle", "P1", "P2"]


@pytest.fixture(scope="module")
def tiny_graph():
    return erdos_renyi(64, 256, seed=7, name="er64")


@pytest.fixture(scope="module")
def tiny_stats(tiny_graph):
    return compute_stats(tiny_graph, CFG)


# The workload one "replica process" serves; the restart tests replay it
# byte-for-byte against a fresh engine over the same store.
def workload():
    return [
        QueryRequest(get_pattern("P1")),
        QueryRequest(get_pattern("triangle")),
        QueryRequest(get_pattern("rectangle"), use_iep=True),
    ]


@pytest.fixture(scope="module")
def warm_store(tmp_path_factory, tiny_graph, tiny_stats):
    """A store populated by one cold serving pass (write-behind)."""
    root = str(tmp_path_factory.mktemp("plan-store"))
    engine = QueryEngine(tiny_graph, cfg=CFG, store=PlanStore(root),
                         stats=tiny_stats)
    results = engine.serve(workload())
    assert engine.cache.stats.n_searches == len(workload())
    assert engine.cache.stats.export_fails == 0
    return root, [r.count for r in results]


# ------------------------------------------------------- dict round-trips
@pytest.mark.parametrize("name", ROUND_TRIP_PATTERNS)
@pytest.mark.parametrize("use_iep", [False, True])
def test_config_round_trip_exact(tiny_stats, name, use_iep):
    config = search_configuration(
        get_pattern(name), tiny_stats, use_iep=use_iep).best
    thawed = config_from_dict(json.loads(json.dumps(config_to_dict(config))))
    assert thawed == config        # dataclass equality, tuples and all


@pytest.mark.parametrize("name", ROUND_TRIP_PATTERNS)
@pytest.mark.parametrize("use_iep", [False, True])
def test_plan_round_trip_exact(tiny_stats, name, use_iep):
    pattern = get_pattern(name)
    config = search_configuration(pattern, tiny_stats, use_iep=use_iep).best
    plan = build_plan(pattern, config.order, config.res_set,
                      iep_k=config.iep_k)
    thawed = plan_from_dict(json.loads(json.dumps(plan_to_dict(plan))))
    assert thawed == plan
    if use_iep and config.iep_k > 0:
        assert thawed.iep is not None and thawed.iep.k == config.iep_k


def test_executor_fingerprint_string_stable():
    assert CFG.fingerprint() == CFG.fingerprint()
    assert ExecutorConfig(capacity=1 << 13).fingerprint() != CFG.fingerprint()
    assert ExecutorConfig(
        capacity=CFG.capacity, degree_buckets=((64, 1.0),),
    ).fingerprint() != CFG.fingerprint()
    # the resolved (not declared) pallas path is what the program bakes
    # in: auto must alias whichever explicit setting it resolves to
    assert ExecutorConfig(use_pallas=None).fingerprint() in (
        ExecutorConfig(use_pallas=False).fingerprint(),
        ExecutorConfig(use_pallas=True).fingerprint(),
    )


# ------------------------------------------------------------- store I/O
def test_store_save_load_round_trip(tmp_path, tiny_graph, tiny_stats):
    from repro.query.cache import graph_fingerprint

    store = PlanStore(str(tmp_path))
    pattern = get_pattern("P4")
    config = search_configuration(pattern, tiny_stats).best
    plan = build_plan(pattern, config.order, config.res_set)
    key = PlanCache.entry_key(
        pattern, graph_fingerprint(tiny_graph, tiny_stats), CFG)
    digest = store.save(key, pattern=pattern, config=config, plan=plan,
                        exec_bytes=b"not-a-real-executable",
                        search_seconds=0.25)
    assert digest == key_digest(key)
    assert len(store) == 1

    rec = store.load(key)
    assert rec is not None
    assert rec.config == config and rec.plan == plan
    assert rec.pattern == pattern
    assert rec.exec_bytes == b"not-a-real-executable"
    assert rec.mode == "graphpi" and rec.use_iep is False
    assert rec.search_seconds == 0.25
    # absent key is a miss, not an error
    other = PlanCache.entry_key(
        get_pattern("P2"), graph_fingerprint(tiny_graph, tiny_stats), CFG)
    assert store.load(other) is None
    assert store.stats.misses == 1


def _tamper(store, digest, **patch):
    path = os.path.join(store.vdir, digest + ".json")
    rec = json.load(open(path))
    rec.update(patch)
    with open(path, "w") as f:
        json.dump(rec, f)


def test_store_rejects_mismatched_headers(tmp_path, tiny_graph, tiny_stats):
    from repro.query.cache import graph_fingerprint

    store = PlanStore(str(tmp_path))
    pattern = get_pattern("triangle")
    config = search_configuration(pattern, tiny_stats).best
    plan = build_plan(pattern, config.order, config.res_set)
    key = PlanCache.entry_key(
        pattern, graph_fingerprint(tiny_graph, tiny_stats), CFG)
    digest = store.save(key, pattern=pattern, config=config, plan=plan)

    _tamper(store, digest, schema_version=SCHEMA_VERSION + 1)
    assert store.load(key) is None
    assert store.stats.rejects.get("schema_version") == 1

    _tamper(store, digest, schema_version=SCHEMA_VERSION, jax="0.0.1")
    assert store.load(key) is None
    assert store.stats.rejects.get("jax_version") == 1

    _tamper(store, digest, jax=__import__("jax").__version__,
            repro_fingerprint="stale-code-fingerprint")
    assert store.load(key) is None
    assert store.stats.rejects.get("repro_fingerprint") == 1

    # a truncated/corrupt record degrades to a cold start, never raises
    with open(os.path.join(store.vdir, digest + ".json"), "w") as f:
        f.write("{not json")
    assert store.load(key) is None
    assert store.stats.rejects.get("corrupt") == 1


def test_store_backend_mismatch_drops_executable_keeps_plan(
        tmp_path, tiny_graph, tiny_stats):
    from repro.query.cache import graph_fingerprint

    store = PlanStore(str(tmp_path))
    pattern = get_pattern("triangle")
    config = search_configuration(pattern, tiny_stats).best
    plan = build_plan(pattern, config.order, config.res_set)
    key = PlanCache.entry_key(
        pattern, graph_fingerprint(tiny_graph, tiny_stats), CFG)
    digest = store.save(key, pattern=pattern, config=config, plan=plan,
                        exec_bytes=b"cpu-compiled-blob")
    _tamper(store, digest, backend="tpu")
    rec = store.load(key)
    assert rec is not None                  # plans are device-independent
    assert rec.exec_bytes is None           # the executable is not
    assert store.stats.exec_drops == 1


def test_repro_fingerprint_is_stable():
    assert repro_fingerprint() == repro_fingerprint()
    assert len(repro_fingerprint()) == 64


# ------------------------------------------- disk-warm restart guarantee
def test_fresh_engine_replays_with_zero_searches_and_zero_compiles(
        warm_store, tiny_graph, tiny_stats):
    """ISSUE 4 acceptance: a restarted store-backed replica replays the
    prior workload with n_searches == 0 and n_compiles == 0."""
    root, cold_counts = warm_store
    engine = QueryEngine(tiny_graph, cfg=CFG, store=PlanStore(root),
                         stats=tiny_stats)
    results = engine.serve(workload())
    stats = engine.cache.stats
    assert [r.count for r in results] == cold_counts
    assert stats.n_searches == 0, stats.as_dict()
    assert stats.n_compiles == 0, stats.as_dict()
    assert stats.persist_hits == len(workload())
    assert stats.aot_loads == len(workload())
    assert all(r.search_seconds == 0.0 for r in results)


def test_executable_without_window_fill_falls_back_to_jit(
        warm_store, tiny_graph, tiny_stats, monkeypatch):
    """A stored executable of a count program from before its window
    fill output (two outputs) is refused on load, and the replica
    re-JITs and counts the same."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core.executor import Matcher

    root, cold_counts = warm_store
    load = PlanStore.load

    def two_output_record(self, key):
        rec = load(self, key)
        if rec is None:
            return None
        m = Matcher(tiny_graph, rec.plan, CFG)
        fn = m._fn(CFG.capacity)
        v0 = jnp.full((CFG.capacity,), tiny_graph.n, jnp.int32)
        with jax.enable_x64(True):
            blob = jax.export.export(jax.jit(lambda *a: fn(*a)[:2]))(
                *m._call_args(), v0).serialize()
        return dataclasses.replace(rec, exec_bytes=blob)

    monkeypatch.setattr(PlanStore, "load", two_output_record)
    engine = QueryEngine(tiny_graph, cfg=CFG, store=PlanStore(root),
                         stats=tiny_stats)
    with pytest.warns(RuntimeWarning, match="window fill"):
        results = engine.serve(workload())
    stats = engine.cache.stats
    assert [r.count for r in results] == cold_counts
    assert stats.aot_load_fails == len(workload()) and stats.aot_loads == 0
    assert stats.n_searches == 0
    assert stats.n_compiles == len(workload())


def test_warm_from_disk_preloads_then_serves_pure_hits(
        warm_store, tiny_graph, tiny_stats):
    root, cold_counts = warm_store
    engine = QueryEngine(tiny_graph, cfg=CFG, store=PlanStore(root),
                         stats=tiny_stats)
    assert engine.warm_from_disk() == len(workload())
    # replay + an isomorphic relabeling: every request is an in-memory hit
    reqs = workload() + [
        QueryRequest(relabeled_variant(get_pattern("P1"), seed=5))]
    results = engine.serve(reqs)
    stats = engine.cache.stats
    assert [r.count for r in results[:3]] == cold_counts
    assert results[3].count == cold_counts[0]
    assert stats.hits == len(reqs) and stats.misses == 0
    assert stats.n_searches == 0 and stats.n_compiles == 0
    # preloads are counted apart from load-through persist hits: no
    # request was served from disk here, every request was an in-memory
    # hit on a preloaded entry
    assert stats.preloads == len(workload()) and stats.persist_hits == 0


def test_preload_skips_incompatible_layouts(warm_store, tiny_graph,
                                            tiny_stats):
    root, _ = warm_store
    # a different executor capacity compiles different programs: nothing
    # in the store may preload into this engine
    engine = QueryEngine(tiny_graph, cfg=ExecutorConfig(capacity=1 << 11),
                         store=PlanStore(root), stats=tiny_stats)
    assert engine.warm_from_disk() == 0


def test_store_does_not_leak_across_graphs(warm_store, tiny_stats):
    root, _ = warm_store
    other = erdos_renyi(64, 256, seed=8, name="er64b")
    engine = QueryEngine(other, cfg=CFG, store=PlanStore(root))
    assert engine.warm_from_disk() == 0
    res = engine.submit(QueryRequest(get_pattern("P1")))
    assert not res.cache_hit
    assert engine.cache.stats.persist_hits == 0
    assert engine.cache.stats.n_searches == 1


# ------------------------------------------------------- eviction release
def test_lru_eviction_releases_matcher_memory(tiny_graph, tiny_stats):
    cache = PlanCache(max_entries=1)
    e1, _ = cache.get_or_build(get_pattern("triangle"), tiny_graph,
                               tiny_stats, cfg=CFG, warm=False)
    assert e1.matcher._arrays is not None
    cache.get_or_build(get_pattern("rectangle"), tiny_graph, tiny_stats,
                       cfg=CFG, warm=False)
    assert cache.stats.evictions == 1
    # the evicted matcher dropped its executables + device-array refs
    assert e1.matcher._arrays is None
    assert not e1.matcher._fns
    with pytest.raises(RuntimeError, match="released"):
        e1.matcher.count()


def test_zero_capacity_cache_keeps_returned_entry_usable(tiny_graph,
                                                         tiny_stats):
    # max_entries=0 immediately pops every entry, but the entry handed
    # back to the caller must stay live (eviction-release must not
    # apply to the entry being returned)
    cache = PlanCache(max_entries=0)
    entry, hit = cache.get_or_build(get_pattern("triangle"), tiny_graph,
                                    tiny_stats, cfg=CFG, warm=False)
    assert not hit and len(cache) == 0
    assert entry.count().count >= 0        # still executable


# ----------------------------------- schema v2: labels + v1 migration
def _searched(pattern, stats):
    """(canonical pattern, config, plan) the way the cache persists them."""
    from repro.query.canon import canonical_form

    canon = canonical_form(pattern)
    config = search_configuration(canon, stats).best
    plan = build_plan(canon, config.order, config.res_set,
                      iep_k=config.iep_k)
    return canon, config, plan


@pytest.fixture(scope="module")
def labeled_graph():
    from repro.graph.datasets import named_dataset

    return named_dataset("tiny-labeled")


@pytest.fixture(scope="module")
def labeled_stats(labeled_graph):
    return compute_stats(labeled_graph, CFG)


def test_labeled_and_skeleton_never_share_entry_or_record(
        tmp_path, labeled_graph, labeled_stats):
    """Key-separation satellite: a labeled pattern, a second label
    assignment of the same skeleton, and the bare skeleton are three
    distinct cache entries AND three distinct store records — on the
    SAME graph, executor config, and layout."""
    from repro.query.cache import graph_fingerprint

    gfp = graph_fingerprint(labeled_graph, labeled_stats)
    tri = get_pattern("triangle")
    variants = [tri, tri.with_labels((0, 1, 1)), tri.with_labels((0, 0, 1))]
    keys = [PlanCache.entry_key(p, gfp, CFG) for p in variants]
    assert len({k[0] for k in keys}) == 3          # canonical keys split
    assert len({key_digest(k) for k in keys}) == 3  # store slots split

    store = PlanStore(str(tmp_path / "store"))
    cache = PlanCache(store=store)
    for p in variants:
        cache.get_or_build(p, labeled_graph, labeled_stats, cfg=CFG,
                           warm=False)
    assert len(cache) == 3 and len(store) == 3
    assert cache.stats.n_searches == 3
    # re-querying any variant hits its own entry, never a sibling's
    for p in variants:
        _, hit = cache.get_or_build(p, labeled_graph, labeled_stats,
                                    cfg=CFG, warm=False)
        assert hit
    assert cache.stats.n_searches == 3


def test_labeled_record_round_trips_with_vlabels(tmp_path, labeled_graph,
                                                 labeled_stats):
    from repro.query.cache import graph_fingerprint

    store = PlanStore(str(tmp_path))
    canon, config, plan = _searched(
        get_pattern("rectangle").with_labels((0, 1, 0, None)),
        labeled_stats)
    assert plan.vlabels is not None
    key = PlanCache.entry_key(
        canon, graph_fingerprint(labeled_graph, labeled_stats), CFG)
    store.save(key, pattern=canon, config=config, plan=plan)
    rec = PlanStore(store.root).load(key)
    assert rec is not None
    assert rec.pattern == canon and rec.pattern.labels == canon.labels
    assert rec.plan == plan and rec.plan.vlabels == plan.vlabels


def test_v1_unlabeled_records_still_load(tmp_path, labeled_stats):
    """A v2 store opened over a v1 tree warm-loads unlabeled records in
    place (same digests), and a v2 rewrite of the same key shadows the
    legacy copy."""
    store = PlanStore(str(tmp_path))
    canon, config, plan = _searched(get_pattern("triangle"), labeled_stats)
    key = PlanCache.entry_key(canon, ("gfp", 64, 256, 1), CFG)
    digest = store.save(key, pattern=canon, config=config, plan=plan,
                        schema_version=1)
    assert os.path.exists(os.path.join(store.root, "v1", digest + ".json"))
    assert len(store) == 1

    fresh = PlanStore(store.root)
    rec = fresh.load(key)
    assert rec is not None and rec.header["schema_version"] == 1

    # re-saving at the current version shadows the v1 copy on load
    store.save(key, pattern=canon, config=config, plan=plan)
    rec2 = PlanStore(store.root).load(key)
    assert rec2 is not None
    assert rec2.header["schema_version"] == SCHEMA_VERSION
    # records() must not yield the same digest twice across versions
    digs = [r.digest for r in PlanStore(store.root).records()]
    assert digs.count(digest) == 1


def test_labeled_patterns_refuse_v1_downgrade(tmp_path, labeled_stats):
    store = PlanStore(str(tmp_path))
    canon, config, plan = _searched(
        get_pattern("triangle").with_labels((0, 1, 1)), labeled_stats)
    key = PlanCache.entry_key(canon, ("gfp", 64, 256, 1), CFG)
    with pytest.raises(ValueError, match="labels are a v2 field"):
        store.save(key, pattern=canon, config=config, plan=plan,
                   schema_version=1)


def test_forged_v1_labeled_record_rejected(tmp_path, labeled_stats):
    """A v1 record claiming label fields could not have been written by
    any v1 writer: the loader rejects it and fsck flags it."""
    store = PlanStore(str(tmp_path))
    canon, config, plan = _searched(get_pattern("triangle"), labeled_stats)
    key = PlanCache.entry_key(canon, ("gfp", 64, 256, 1), CFG)
    digest = store.save(key, pattern=canon, config=config, plan=plan,
                        schema_version=1)
    path = os.path.join(store.root, "v1", digest + ".json")
    rec = json.load(open(path))
    rec["pattern"]["labels"] = [0, 1, 1]
    with open(path, "w") as f:
        json.dump(rec, f)

    fresh = PlanStore(store.root)
    assert fresh.load(key) is None
    assert fresh.stats.rejects.get("v1-labeled") == 1
    report = PlanStore(store.root).fsck()
    assert any(f.rule == "record-version-labeled"
               for f in report["findings"][digest])
    assert report["quarantined"] == 1
    assert os.path.exists(
        os.path.join(store.root, "v1", "quarantine", digest + ".json"))


def test_labeled_engine_round_trip_through_store(tmp_path, labeled_graph,
                                                 labeled_stats):
    """End-to-end: a labeled query served, persisted, and replayed by a
    restarted replica with zero searches — and verified against the
    oracle through the engine's own verify path."""
    tri = get_pattern("triangle").with_labels((0, 1, 1))
    root = str(tmp_path / "plan-store")
    e1 = QueryEngine(labeled_graph, cfg=CFG, store=PlanStore(root),
                     stats=labeled_stats)
    r1 = e1.submit(QueryRequest(tri, verify=True))
    assert r1.verified and not r1.cache_hit

    e2 = QueryEngine(labeled_graph, cfg=CFG, store=PlanStore(root),
                     stats=labeled_stats)
    r2 = e2.submit(QueryRequest(tri))
    assert r2.count == r1.count
    assert e2.cache.stats.n_searches == 0
    assert e2.cache.stats.persist_hits == 1
