"""End-to-end distributed pattern-matching driver (the paper's workload).

    PYTHONPATH=src python -m repro.launch.mine --pattern P1 --dataset tiny-er
    PYTHONPATH=src python -m repro.launch.mine --pattern P2 --dataset small-rmat \
        --use-iep --verify

Pipeline (paper Fig. 3): restriction generation (Alg. 1) → 2-phase
schedule generation → performance-model configuration selection → JAX
compilation → distributed counting (shard_map over the host mesh's data
axis, fine-grained task striping).  `--mode graphzero` runs the baseline
(single restriction set, degree-heuristic schedule) for comparison.

Since the query-serving subsystem landed, this CLI is a one-request
client of the same `PlanCache`/`QueryEngine` code path that
`launch/query_serve.py` serves traffic through — there is exactly one
request path.  With `--cache-dir` repeat invocations load the persisted
plan (and its AOT executable) instead of re-searching/re-tracing.
"""
from __future__ import annotations

import argparse
import sys

import jax


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--pattern", default="P1")
    ap.add_argument("--dataset", default="tiny-er")
    ap.add_argument("--mode", default="graphpi",
                    choices=["graphpi", "graphzero", "naive"])
    ap.add_argument("--use-iep", action="store_true")
    ap.add_argument("--verify", action="store_true",
                    help="check against the pure-python oracle (small graphs)")
    ap.add_argument("--capacity", type=int, default=1 << 15)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--single-device", action="store_true")
    ap.add_argument("--cache-dir", default="",
                    help="persistent plan store: repeat invocations skip "
                         "the configuration search (and the JIT, single-"
                         "device) via the on-disk cache (DESIGN.md §5)")
    from ..obs.cli import add_trace_args, finish_tracing, start_tracing

    add_trace_args(ap)
    args = ap.parse_args(argv)

    from ..configs.graphpi import get_dataset, get_pattern
    from ..core.executor import ExecutorConfig, auto_buckets
    from ..launch.compile_cache import enable_compile_cache
    from ..launch.mesh import make_host_mesh
    from ..query import PlanStore, QueryEngine, QueryRequest

    enable_compile_cache()
    start_tracing(args)

    pattern = get_pattern(args.pattern)
    graph = get_dataset(args.dataset)
    print(f"[mine] pattern={pattern.name} (n={pattern.n}, m={pattern.m}, "
          f"|Aut|={pattern.aut_count()})  graph={graph.name} "
          f"(|V|={graph.n}, |E|={graph.m}, max_deg={graph.max_degree})")

    mesh = None
    if not args.single_device and len(jax.devices()) > 1:
        mesh = make_host_mesh(model=args.model_axis)
    store = PlanStore(args.cache_dir) if args.cache_dir else None
    cfg = ExecutorConfig(capacity=args.capacity,
                         degree_buckets=auto_buckets(graph))
    engine = QueryEngine(graph, cfg=cfg, mesh=mesh, store=store)
    print(f"[mine] stats: tri_cnt={engine.stats.tri_cnt} "
          f"({engine.stats_seconds:.2f}s)")

    res = engine.submit(QueryRequest(
        pattern, use_iep=args.use_iep, verify=args.verify, mode=args.mode))
    cs = engine.cache.stats
    how = ("cache hit" if res.cache_hit
           else "persisted plan" if cs.persist_hits else "cache miss")
    print(f"[mine] config: schedule={res.order} restrictions={res.res_set} "
          f"iep_k={res.iep_k} (search {res.search_seconds:.3f}s, "
          f"compile {res.compile_seconds:.3f}s, {how}"
          f"{', AOT executable' if cs.aot_loads else ''})")
    exec_s = res.latency_s - res.search_seconds - res.compile_seconds
    print(f"[mine] count={res.count}  wall={exec_s:.3f}s  "
          f"(query latency {res.latency_s:.3f}s incl. search+compile; "
          f"max frontier rows used: {res.max_needed}"
          f"{', OVERFLOWED' if res.overflowed else ''})")

    finish_tracing(args, registry=engine.metrics, tag="mine")

    if args.verify:
        print(f"[mine] oracle={res.expected}  "
              f"{'OK' if res.verified else 'MISMATCH'}")
        if not res.verified:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
