"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run              # quick tier
    PYTHONPATH=src python -m benchmarks.run --full       # paper-scale tier
    PYTHONPATH=src python -m benchmarks.run --only fig10,tab3

Each module prints `bench,key=value...,value,unit` CSV rows and writes a
JSON artifact under artifacts/bench/.  The quick tier finishes on a CPU
container in minutes; --full uses the larger synthetic datasets.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from . import (
    fig8_overall,
    fig9_schedules,
    fig10_iep,
    fig11_model_accuracy,
    fig12_scaling,
    gateway_mix,
    kernel_intersect,
    live_churn,
    query_throughput,
    questions,
    tab2_restrictions,
    tab3_overhead,
)

BENCHES = {
    "fig8": fig8_overall.main,       # overall perf vs GraphZero/naive
    "tab2": tab2_restrictions.main,  # restriction-set selection speedup
    "fig9": fig9_schedules.main,     # schedule landscape + 2-phase filter
    "fig10": fig10_iep.main,         # IEP on/off
    "fig10_fused": fig10_iep.main_fused,  # IEP tail: separate vs fused
    "fig11": fig11_model_accuracy.main,  # model pick vs oracle
    "fig12": fig12_scaling.main,     # scaling / load balance
    "tab3": tab3_overhead.main,      # preprocessing overhead
    "kernel": kernel_intersect.main, # Pallas intersection kernel
    "query": query_throughput.main,  # serve path: cold vs warm queries/s
    "gateway": gateway_mix.main,     # mixed graph+LM: coalescing/interference
    "questions": questions.main,     # labeled QA: oracle accuracy + q/s
    "live_churn": live_churn.main,   # serve-while-mutating vs reload
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of " + ",".join(BENCHES))
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    names = [n.strip() for n in args.only.split(",") if n.strip()] or \
        list(BENCHES)
    failures = []
    for name in names:
        print(f"\n=== {name} {'(full)' if args.full else '(quick)'} ===")
        t0 = time.time()
        try:
            BENCHES[name](args.full)
            print(f"=== {name} done in {time.time() - t0:.1f}s ===")
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED: {failures}", file=sys.stderr)
        return 1
    print("\nall benchmarks OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
