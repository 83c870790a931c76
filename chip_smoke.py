#!/usr/bin/env python3
"""Prove that the served pattern-matching path runs on TPU chips.

    python3 chip_smoke.py              # one chip: phases (a), (b), (c)
    python3 chip_smoke.py --chips 4    # four chips: the sharded path only

One process owns the chip(s); the RPC server is a thread of it.  Every
phase raises at its first fault and nothing here catches an error to
carry on, so any failure exits nonzero.

  (a) device     jax.devices()[0] must be a TPU, else exit 1 before any
                 result is printed.
  (b) exactness  tiny-er (triangle, P1-P6) and tiny-labeled (the first
                 labeled question of each category of
                 benchmarks/questions.py), counted by QueryEngine on the
                 compiled Mosaic kernel with a plan store attached: every
                 count equals the brute-force oracle (computed meanwhile
                 in worker processes that stay off JAX's backends), the
                 AOT export/install path runs without a failure, and a
                 restarted engine loads every tiny-labeled executable.
  (c) served     `launch/gateway.py --no-lm --listen` in a thread, an
                 RPCClient submitting requests to it: P1 on tiny-er
                 (equal to the oracle), then mico-syn (Table I MiCo
                 stand-in, generated from its seed, resident on the
                 chip) with SERVED_REQUESTS, whose triangle count equals
                 an independent scipy count.  Every served count equals
                 the same engine configuration's portable path on the
                 chip; the mico-syn triangle's portable count is cut at
                 PORTABLE_BUDGET_S and compared on the dispatches it
                 finished.
  --chips 4      ShardedMatcher over a 4-chip mesh on mico-syn against
                 the single-device count on device 0; the graph and the
                 root stripes must be laid out over all four chips.

The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# The largest capacity whose [2, rows] kernel offsets fit one
# pallas_call's SMEM share (kernels/intersect.py::prefetch_rows); P3 on
# tiny-er still escalates four times, to 2^18 rows.
SMALL_CAPACITY = 1 << 14
SMALL_PATTERNS = ("triangle", "P1", "P2", "P3", "P4", "P5", "P6")
SMALL_DATASET = "tiny-er"
LABELED_DATASET = "tiny-labeled"
SERVED_DATASET = "mico-syn"
# RPC request specs (serve/rpc.py wire format).  The stars count through
# the IEP tail (one fused signed-popcount pass per root).  None of P1-P6
# is served on mico-syn: the second level of each of their searched
# plans holds 1.8e8-1.6e9 partial embeddings (PERF.md, Findings), far
# past what one run can count on one chip twice (fused and portable);
# SMALL_SERVED serves P1 on SMALL_DATASET instead, where it ends.
SERVED_REQUESTS = (
    {"pattern": "triangle"},
    {"pattern": {"n": 4, "edges": [[0, 1], [0, 2], [0, 3]],
                 "name": "star4"}, "use_iep": True},
    {"pattern": {"n": 5, "edges": [[0, 1], [0, 2], [0, 3], [0, 4]],
                 "name": "star5"}, "use_iep": True},
)
SMALL_SERVED = ({"pattern": "P1"},)
# The sharded triangle is left out: ShardedMatcher retries a whole pass
# at doubled capacity whenever one chunk overflows (ROADMAP Queue 1 item
# 6); a host replay of its chunks' needs on mico-syn ends at 2^20 rows,
# the costliest path of all.  The stars fit a chunk at the serving
# capacity.
SHARDED_REQUESTS = SERVED_REQUESTS[1:]
SERVE_TIMEOUT_S = 1100
# Seconds the portable path may spend on the served mico-syn triangle.
# The whole portable count did not end in 570 s on one v5e (PERF.md,
# Findings); past the budget the run compares the dispatches it
# finished, and the whole fused count is held to the host count.
PORTABLE_BUDGET_S = 90


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ (a) --
def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"[a] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"[a] no accelerator: jax.devices()[0] is "
                         f"{d.platform!r}, not a TPU")
    if len(devs) < chips:
        raise SystemExit(f"[a] {chips} chips asked for, {len(devs)} found")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def assert_fused(fingerprint: str, hlo: str | None = None) -> None:
    """The count programs run the compiled Mosaic kernel: the executor
    resolved the fused path on a TPU backend, and the compiled HLO holds
    the kernel's custom call."""
    if "pallas=1" not in fingerprint:
        raise AssertionError(f"fused kernel not selected: {fingerprint}")
    if hlo is not None and "tpu_custom_call" not in hlo:
        raise AssertionError("compiled count program has no tpu_custom_call")


def compiled_text(matcher) -> str:
    """HLO text of a Matcher's base-capacity count program, compiled for
    the backend it runs on."""
    import jax
    import jax.numpy as jnp

    cap = matcher.cfg.capacity
    v0 = jnp.full((cap,), matcher.graph.n, dtype=jnp.int32)
    with jax.enable_x64(True):
        return matcher._fn(cap).lower(
            *matcher._call_args(), v0).compile().as_text()


# ------------------------------------------------------------------ (b) --
def _count_all(engine, requests) -> list:
    tickets = [engine.enqueue(r) for r in requests]
    while engine.pending() or engine.inflight():
        engine.run_pending()
    return [t.result for t in tickets]


def small_jobs() -> list:
    """(dataset, [(name, pattern)]) pairs of phase (b)."""
    from benchmarks.questions import inventory
    from repro.configs.graphpi import get_pattern

    jobs = [(SMALL_DATASET, [(n, get_pattern(n)) for n in SMALL_PATTERNS])]
    if LABELED_DATASET:
        # the first question of every category: each compiles its own
        # count program (seconds apiece on the chip's compiler)
        firsts = {}
        for q in inventory():
            firsts.setdefault(q.category, q)
        jobs.append((LABELED_DATASET,
                     [(q.qid, q.pattern) for q in firsts.values()]))
    return jobs


def _oracle_count(dataset: str, pattern) -> int:
    """Brute-force count of one small pattern; runs in a worker process
    that never touches JAX's backends."""
    from repro.configs.graphpi import get_dataset
    from repro.core.oracle import count_embeddings_oracle

    graph = get_dataset(dataset)
    return count_embeddings_oracle(graph.n, graph.edge_array(), pattern,
                                   labels=graph.labels)


def _cpu_only() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"


def exact_small(store_root: str, pool) -> int:
    """Oracle-exact counts on the small graphs, the oracle running in
    `pool` meanwhile.  Returns how many counts were checked."""
    from repro.configs.graphpi import get_dataset
    from repro.core.executor import ExecutorConfig
    from repro.query import PlanCache, PlanStore, QueryEngine, QueryRequest

    jobs = small_jobs()
    # one task per pattern, so the slowest brute-force counts (P3, P6)
    # run side by side while the chip compiles; the graph with the
    # quicker oracle is counted first
    oracle = [[pool.submit(_oracle_count, dataset, p) for _, p in named]
              for dataset, named in jobs]
    checked = 0
    for j, (dataset, named) in reversed(list(enumerate(jobs))):
        graph = get_dataset(dataset)
        cfg = ExecutorConfig(capacity=SMALL_CAPACITY)
        store = os.path.join(store_root, dataset)
        requests = [QueryRequest(p) for _, p in named]
        want = None
        # the labeled graph's counts are one dispatch each, so a
        # restarted engine loading every stored executable is cheap there
        for restart in ((False, True) if dataset == LABELED_DATASET
                        else (False,)):
            t = time.perf_counter()
            engine = QueryEngine(graph, cfg=cfg,
                                 cache=PlanCache(store=PlanStore(store)))
            results = _count_all(engine, requests)
            count_s = time.perf_counter() - t
            st = engine.cache.stats
            assert_fused(engine.cfg.fingerprint())
            if restart:
                how = "run from the stored executables"
            else:
                # a plan whose levels all have one predecessor and no IEP
                # tail needs no membership test, so no kernel; compile
                # the first plan that does and look for it
                kernel = {e.canon_key: e for e in engine.cache.entries()
                          if e.plan.iep is not None
                          or any(len(p) > 1 for p in e.plan.preds)}
                pick = next(((n, kernel[r.canon_key])
                             for (n, _), r in zip(named, results)
                             if r.canon_key in kernel), None)
                if pick is None:
                    raise AssertionError(f"[b] {dataset}: no plan runs "
                                         f"the kernel")
                first, entry = pick
                assert_fused(engine.cfg.fingerprint(),
                             compiled_text(entry.matcher))
                how = f"tpu_custom_call in {first}'s compiled program"
            want = want or [f.result() for f in oracle[j]]
            for (name, _), res, w in zip(named, results, want):
                if res.count != w or res.overflowed:
                    raise AssertionError(
                        f"[b] {dataset} {name}: count {res.count} != "
                        f"oracle {w} (overflowed={res.overflowed})")
            if st.export_fails or st.aot_load_fails:
                raise AssertionError(f"[b] {dataset}: AOT path failed: "
                                     f"{st.as_dict()}")
            if restart and st.aot_loads != len(set(
                    e.canon_key for e in engine.cache.entries())):
                raise AssertionError(f"[b] {dataset}: restarted engine "
                                     f"loaded {st.aot_loads} executables")
            counts = ", ".join(f"{n}={r.count}"
                               for (n, _), r in zip(named, results))
            log(f"[b] {dataset} {'restart' if restart else 'cold'}: "
                f"{len(named)} counts == oracle ({counts}); "
                f"{engine.cfg.fingerprint()}; {how}; "
                f"export_fails={st.export_fails} "
                f"aot_loads={st.aot_loads} "
                f"aot_load_fails={st.aot_load_fails} "
                f"compile_s={st.compile_seconds:.1f} "
                f"(all counts {count_s:.1f}s)")
            checked += len(named)
    return checked


# ------------------------------------------------------------------ (c) --
def host_triangles(graph) -> int:
    """Triangle count on the host, independent of the executor: orient
    every edge from lower to higher (degree, id), then sum (U @ U) ∘ U."""
    import numpy as np
    import scipy.sparse as sp

    deg = graph.degrees.astype(np.int64)
    src = np.repeat(np.arange(graph.n, dtype=np.int64), deg)
    dst = graph.indices[:len(src)].astype(np.int64)
    rank = deg * graph.n + np.arange(graph.n)
    up = rank[src] < rank[dst]
    U = sp.csr_matrix((np.ones(int(up.sum()), np.int64),
                       (src[up], dst[up])), shape=(graph.n, graph.n))
    return int((U @ U).multiply(U).sum())


def _serve_in_thread(argv: list[str]):
    from repro.launch import gateway

    box: dict = {}

    def run():
        try:
            box["rc"] = gateway.main(argv)
        except BaseException as e:   # noqa: BLE001 — re-raised by join
            box["exc"] = e

    th = threading.Thread(target=run, name="gateway", daemon=True)
    th.start()

    def join():
        th.join(timeout=SERVE_TIMEOUT_S)
        if th.is_alive():
            raise TimeoutError("gateway thread did not stop")
        if "exc" in box:
            raise RuntimeError("gateway failed") from box["exc"]
        if box.get("rc") != 0:
            raise RuntimeError(f"gateway exited with {box.get('rc')}")

    return th, join


def serve_over_rpc(graph, stats, specs, work_dir: str) -> tuple[list, str]:
    """Start `launch/gateway.py --no-lm --listen` on `graph` in a thread,
    submit `specs` from an RPCClient one after the other, and stop the
    server.  Returns the wire results and the engine's executor
    fingerprint."""
    from repro.query import PlanStore
    from repro.serve.rpc import RPCClient

    tag = f"[c] {graph.name}"
    # the engine reads its start-up graph statistics (which steer the
    # configuration search) from the plan store when they are there, as
    # a restarted server does; the counts are the requests' own
    work_dir = tempfile.mkdtemp(prefix=f"{graph.name}-", dir=work_dir)
    store = os.path.join(work_dir, "store")
    PlanStore(store).save_graph_stats(graph.fingerprint, stats)
    port_file = os.path.join(work_dir, "gateway.port")
    t0 = time.perf_counter()
    th, join = _serve_in_thread([
        "--dataset", graph.name, "--no-lm", "--single-device",
        "--cache-dir", store, "--listen", "0", "--port-file", port_file])
    while not os.path.exists(port_file):
        if not th.is_alive():
            join()
        if time.perf_counter() - t0 > SERVE_TIMEOUT_S:
            raise TimeoutError("gateway never started listening")
        time.sleep(0.2)
    host, port = open(port_file).read().split()
    log(f"{tag} setup: gateway listening after "
        f"{time.perf_counter() - t0:.2f}s (graph build + upload)")
    client = RPCClient(host, int(port), timeout=SERVE_TIMEOUT_S)
    fingerprint = client.stats()["stats"]["executor"]
    assert_fused(fingerprint)
    results = []
    for spec in specs:
        t = time.perf_counter()
        res = client.result(client.submit(spec))
        lat = time.perf_counter() - t
        name = res["pattern_name"]
        results.append(res)
        if res["overflowed"]:
            raise AssertionError(f"{tag} {name} overflowed")
        log(f"{tag} rpc {name} (iep_k={res['iep_k']}): "
            f"count={res['count']} latency={lat:.3f}s "
            f"(server {res['latency_s']:.3f}s, setup: search "
            f"{res['search_seconds']:.3f}s + compile "
            f"{res['compile_seconds']:.3f}s) max_needed="
            f"{res['max_needed']}")
    summary = client.stats()["stats"]
    cache = summary["cache"]
    if cache["export_fails"] or cache["n_compiles"] != len(specs):
        raise AssertionError(f"{tag} served cache: {cache}")
    client.shutdown()
    client.close()
    join()
    log(f"{tag} engine: {fingerprint}; "
        f"requests={summary['requests_resolved']} "
        f"executions={summary['executions']} latency={summary['latency']} "
        f"cache={cache}")
    return results, fingerprint


def compare_portable(graph, stats, fingerprint: str, specs, served_results,
                     budget_s: float | None = None) -> None:
    """Count every served spec again on the same engine configuration's
    portable path (`use_pallas=False`) and require the served plan and
    count.  With `budget_s`, a count still running after that many
    seconds is compared on the dispatches it finished: the same
    dispatches replayed on the fused path must leave the same raw total
    and the same spans."""
    from repro.configs.graphpi import get_pattern
    from repro.core.executor import ExecutorConfig, auto_buckets
    from repro.query import QueryEngine
    from repro.serve.rpc import request_from_spec

    tag = f"[c] {graph.name}"
    cfg = ExecutorConfig(capacity=1 << 15, use_pallas=False,
                         degree_buckets=auto_buckets(graph))
    if cfg.fingerprint() != fingerprint.replace("pallas=1", "pallas=0"):
        raise AssertionError(f"{tag} portable config {cfg.fingerprint()} "
                             f"differs from the served {fingerprint}")
    portable = QueryEngine(graph, cfg=cfg, stats=stats)
    for spec, want in zip(specs, served_results):
        t = time.perf_counter()
        entry = portable.plan(request_from_spec(spec, get_pattern)).entry
        name = want["pattern_name"]
        if (list(entry.config.order) != want["order"]
                or entry.config.iep_k != want["iep_k"]):
            raise AssertionError(f"{tag} {name}: portable plan "
                                 f"{entry.config} != served {want}")
        state, res = entry.count_partial(None, max_dispatches=4)
        while res is None and (budget_s is None
                               or time.perf_counter() - t < budget_s):
            state, res = entry.count_partial(state, max_dispatches=4)
        if res is not None:
            if res.count != want["count"]:
                raise AssertionError(f"{tag} {name}: portable count "
                                     f"{res.count} != served {want}")
            log(f"{tag} portable {name}: count={res.count} == served "
                f"({time.perf_counter() - t:.3f}s, {state.dispatches} "
                f"dispatches)")
            continue
        # the whole portable count does not fit the run: replay the same
        # dispatches on the fused path and compare what they produced
        t_fused = replay_on_default_path(
            graph, entry.plan, cfg, portable._arrays, state)
        done = graph.n - sum(e - s for s, e, _ in state.spans)
        share = state.total / entry.plan.iep_divisor / max(want["count"], 1)
        log(f"{tag} portable {name}: first {state.dispatches} dispatches "
            f"({done} of {graph.n} roots, {100 * done / graph.n:.2f}%) "
            f"give raw total {state.total} ({100 * share:.2f}% of the "
            f"served count) == fused, same spans left "
            f"({time.perf_counter() - t:.1f}s portable incl. compile, "
            f"{t_fused:.1f}s fused)")


def served(work_dir: str) -> None:
    from repro.configs.graphpi import get_dataset, get_pattern
    from repro.core.perf_model import GraphStats
    from repro.serve.rpc import request_from_spec

    # one of the paper's P1-P6 served on the graph where it ends, equal
    # to the oracle and to the portable path
    small = get_dataset(SMALL_DATASET)
    stats = GraphStats(n_vertices=small.n, n_edges=small.m,
                       tri_cnt=host_triangles(small))
    results, fingerprint = serve_over_rpc(small, stats, SMALL_SERVED,
                                          work_dir)
    for spec, res in zip(SMALL_SERVED, results):
        want = _oracle_count(SMALL_DATASET,
                             request_from_spec(spec, get_pattern).pattern)
        if res["count"] != want:
            raise AssertionError(f"[c] {small.name} {res['pattern_name']}: "
                                 f"count {res['count']} != oracle {want}")
        log(f"[c] {small.name} {res['pattern_name']} == oracle ({want})")
    compare_portable(small, stats, fingerprint, SMALL_SERVED, results)

    graph = get_dataset(SERVED_DATASET)
    t = time.perf_counter()
    host_tri = host_triangles(graph)
    log(f"[c] {graph.name}: |V|={graph.n} |E|={graph.m} "
        f"max_degree={graph.max_degree}; host triangles={host_tri} "
        f"({time.perf_counter() - t:.2f}s, scipy)")
    stats = GraphStats(n_vertices=graph.n, n_edges=graph.m,
                       tri_cnt=host_tri)
    results, fingerprint = serve_over_rpc(graph, stats, SERVED_REQUESTS,
                                          work_dir)
    if results[0]["count"] != host_tri:
        raise AssertionError(f"[c] triangle {results[0]} "
                             f"!= host count {host_tri}")
    log(f"[c] {graph.name} triangle == host count ({host_tri})")
    # the stars in full; the triangle within PORTABLE_BUDGET_S
    compare_portable(graph, stats, fingerprint, SERVED_REQUESTS[1:],
                     results[1:])
    compare_portable(graph, stats, fingerprint, SERVED_REQUESTS[:1],
                     results[:1], budget_s=PORTABLE_BUDGET_S)


def replay_on_default_path(graph, plan, cfg, arrays, state) -> float:
    """Run a fresh count of `plan` for `state.dispatches` dispatches on
    the backend's default path (the fused kernel on a TPU) and require
    the raw partial total and the span stack left over that `state`
    holds.  Returns the seconds it took."""
    from dataclasses import replace

    from repro.core.executor import Matcher

    replay = Matcher(graph, plan, replace(cfg, use_pallas=None),
                     arrays=arrays)
    assert_fused(replay.cfg.fingerprint())
    t = time.perf_counter()
    got, _ = replay.count_partial(max_dispatches=state.dispatches)
    if (got.total, got.spans, got.dispatches) != (
            state.total, state.spans, state.dispatches):
        raise AssertionError(
            f"after {state.dispatches} dispatches: total {state.total} "
            f"and {len(state.spans)} spans left, replayed {got.total} "
            f"and {len(got.spans)}")
    return time.perf_counter() - t


# ------------------------------------------------------------- 4 chips --
def sharded() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.configs.graphpi import get_dataset, get_pattern
    from repro.core.config_search import search_configuration
    from repro.core.executor import (ExecutorConfig, Matcher, ShardedMatcher,
                                     auto_buckets, device_graph)
    from repro.core.perf_model import GraphStats
    from repro.core.plan import build_plan
    from repro.serve.rpc import request_from_spec

    graph = get_dataset(SERVED_DATASET)
    tri = host_triangles(graph)
    stats = GraphStats(n_vertices=graph.n, n_edges=graph.m, tri_cnt=tri)
    cfg = ExecutorConfig(capacity=1 << 15, degree_buckets=auto_buckets(graph))
    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("data",))
    arrays = jax.device_put(device_graph(graph),
                            NamedSharding(mesh, PartitionSpec()))
    for leaf in jax.tree_util.tree_leaves(tuple(arrays)):
        held = {s.device for s in leaf.addressable_shards}
        if held != set(devs):
            raise AssertionError(f"[4] graph array held by {held}")
    log(f"[4] {graph.name} (|V|={graph.n}, |E|={graph.m}) replicated on "
        f"{len(devs)} chips; host triangles={tri}")
    for spec in SHARDED_REQUESTS:
        req = request_from_spec(spec, get_pattern)
        name = req.pattern.name
        conf = search_configuration(req.pattern, stats,
                                    use_iep=req.use_iep).best
        plan = build_plan(req.pattern, conf.order, conf.res_set,
                          iep_k=conf.iep_k)
        sm = ShardedMatcher(graph, plan, mesh, cfg=cfg, arrays=arrays)
        # the root stripes: chip i holds roots i, i+4, i+8, ... (padding
        # = vertex n)
        stripes = []
        for i, d in enumerate(devs):
            (shard,) = [s for s in sm._v0.addressable_shards
                        if s.device == d]
            roots = np.asarray(shard.data)
            roots = roots[roots < graph.n]
            if not len(roots) or (roots % len(devs) != i).any():
                raise AssertionError(f"[4] stripe {i} on {d} is wrong")
            stripes.append(len(roots))
        if sum(stripes) != graph.n:
            raise AssertionError(f"[4] stripes cover {sum(stripes)} roots")
        t = time.perf_counter()
        sm.warmup()
        with jax.enable_x64(True):
            hlo = sm._fn(sm.cfg.capacity).lower(
                *sm._call_args(), jnp.full_like(sm._v0, graph.n)
            ).compile().as_text()
        assert_fused(cfg.fingerprint(), hlo)
        if "all-reduce" not in hlo:
            raise AssertionError("[4] sharded program has no all-reduce")
        setup = time.perf_counter() - t
        t = time.perf_counter()
        got = sm.count()
        dt = time.perf_counter() - t
        with jax.default_device(devs[0]):
            one = Matcher(graph, plan, cfg)
            t = time.perf_counter()
            want = one.count()
            dt1 = time.perf_counter() - t
        if got.count != want.count or got.overflowed or want.overflowed:
            raise AssertionError(f"[4] {name}: sharded {got} != "
                                 f"single-device {want}")
        log(f"[4] {name}: sharded count={got.count} == single-device count "
            f"on {devs[0]}; stripes of {stripes} roots on "
            f"{[str(d) for d in devs]}; setup {setup:.2f}s (compile), "
            f"sharded {dt:.3f}s (capacity {sm._capacity}), single-device "
            f"{dt1:.3f}s")


# ----------------------------------------------------------------- main --
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    for p in (src, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[setup] compile cache at {enable_compile_cache()}")
    device = device_info(args.chips)
    if args.chips == 4:
        sharded()
    else:
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor

        with tempfile.TemporaryDirectory() as tmp, ProcessPoolExecutor(
                max_workers=min(8, os.cpu_count() or 1),
                initializer=_cpu_only,
                mp_context=multiprocessing.get_context("spawn")) as pool:
            t = time.perf_counter()
            n = exact_small(os.path.join(tmp, "store"), pool)
            log(f"[b] {n} counts equal the oracle "
                f"({time.perf_counter() - t:.1f}s)")
            t = time.perf_counter()
            served(tmp)
            log(f"[c] served phase done ({time.perf_counter() - t:.1f}s)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
