"""The reader of `bench/metrics/predicted_need_share.clique4.py` on
made-up runs: a traced run whose dispatches carry the host plan's
prediction, a run of a program without it, and an untraced run.

    PYTHONPATH=src python -m pytest tests/bench_harness -q
"""
from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402


def dispatch(**attrs):
    return {"name": "executor.dispatch", "dur_ns": 0,
            "attrs": {"capacity": 64, **attrs}}


NOTHING = [SimpleNamespace(spans=None, trace=None),
           SimpleNamespace(spans=[], trace=None),
           SimpleNamespace(spans=[], trace={"idle_gaps": []})]


def test_predicted_need_share_is_predicted_over_needed():
    """1.0 over exact dispatches, below 1 where one needed more than
    predicted; None where no dispatch carries a prediction."""
    read = run.reader("predicted_need_share.clique4")
    exact = [dispatch(needed=n, predicted=n) for n in (40, 64)]
    under = dispatch(needed=96, predicted=24)
    assert read(SimpleNamespace(spans=exact, trace=None)) == 1.0
    assert read(SimpleNamespace(spans=exact + [under], trace=None)) == \
        pytest.approx((40 + 64 + 24) / (40 + 64 + 96))
    # dispatches of a program without the prediction are left out
    old = exact + [dispatch(needed=500)]
    assert read(SimpleNamespace(spans=old, trace=None)) == 1.0
    for ctx in NOTHING + [SimpleNamespace(spans=[dispatch(needed=1)],
                                          trace=None)]:
        assert read(ctx) is None
