#!/usr/bin/env python3
"""The control of `correct`: a run of a cell with the program's counts
replaced by the plain reference's, held in an int32 accumulator.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

The configurations state exact int64 counts.  int32 accumulation is the
cheaper representation a later change could be tempted by, so the
control answers what it would: every class of the mix is counted by
`bench/reference/` and wrapped to 32 bits (`reference.control`), and the
served stack returns that in place of the device's count.  Everything
else (graph, server, warm-up, loop, check) is the cell's own run.  The
last stdout line is the run's result; `correct` has to come out false.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import sys
import tempfile

import run


def replace_counts(answers: dict) -> None:
    """Make every cached plan return `answers[pattern name]` without
    touching the device."""
    from repro.core.executor import CountResult
    from repro.query.cache import CacheEntry

    def count_partial(self, state=None, *, chunk=None, max_dispatches=None):
        return None, CountResult(count=answers[self.pattern.name],
                                 overflowed=False, max_needed=0)

    CacheEntry.count_partial = count_partial


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench, cell, config, mix = run.load_cell(args.workload)
    device = run.prepare(cell)
    if device is None:
        return 2
    n, edges = run.make_edges(config["generator"], args.seed)
    replace_counts({k: run.reference.control(v) for k, v in
                    run.references(mix, n, edges, None).items()})
    with tempfile.TemporaryDirectory() as tmp:
        out = run.run_cell(bench, cell, config, mix, seed=args.seed,
                           seconds=args.seconds, trace=False, device=device,
                           artifacts=tmp)
    run.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
