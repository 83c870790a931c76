"""The readers of `bench/span_readers.py`, through the metric files that
bind them, on made-up runs: a traced run of a program that records the
spans they read, a run of a program that lacks them, and an untraced
run.  Each returns None where it has nothing to read.

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

SUFFIXES = ("count", "star")


def span(name, dur_ms=0.0, **attrs):
    return {"name": name, "dur_ns": int(dur_ms * 1e6), "attrs": attrs}


def dispatch(**attrs):
    return span("executor.dispatch", needed=1, capacity=8, **attrs)


NOTHING = [SimpleNamespace(spans=None, trace=None),
           SimpleNamespace(spans=[], trace=None),
           SimpleNamespace(spans=[], trace={"idle_gaps": []})]


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_host_turn_ms_is_executor_idle_per_dispatch(suffix):
    read = run.reader("host_turn_ms." + suffix)
    trace = {"idle_gaps": [["outside the engine", 0.5],
                           ["executor.readback", 0.006],
                           ["engine.round", 0.1],
                           ["executor.launch", 0.002],
                           ["executor.count", 0.001]]}
    spans = [dispatch() for _ in range(4)] + [span("executor.roots")]
    ctx = SimpleNamespace(spans=spans, trace=trace)
    assert read(ctx) == pytest.approx(9e-3 * 1e3 / 4)
    # a program without the child spans names its idle by the dispatch
    trace["idle_gaps"] = [["executor.dispatch", 0.004]]
    assert read(ctx) == pytest.approx(1.0)
    for ctx in NOTHING:
        assert read(ctx) is None
    # a trace but no dispatch: nothing to divide by
    assert read(SimpleNamespace(spans=[span("engine.round")],
                                trace=trace)) is None


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_window_fill_is_valid_over_streamed_entries(suffix):
    read = run.reader("window_fill." + suffix)
    spans = [dispatch(rows_live=3, entries_valid=10, entries_streamed=100),
             dispatch(rows_live=5, entries_valid=30, entries_streamed=100),
             span("executor.count", dispatches=2)]
    assert read(SimpleNamespace(spans=spans, trace=None)) == \
        pytest.approx(0.2)
    # dispatches of a program without the fill count are left out
    spans.append(dispatch())
    assert read(SimpleNamespace(spans=spans, trace=None)) == \
        pytest.approx(0.2)
    assert read(SimpleNamespace(spans=[dispatch()], trace=None)) is None
    for ctx in NOTHING:
        assert read(ctx) is None


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_front_door_ms_is_request_less_its_executions(suffix):
    read = run.reader("front_door_ms." + suffix)
    spans = [span("rpc.request", 10.0, ticket=1, tenant="t", ok=True),
             span("rpc.request", 6.0, ticket=2, tenant="t", ok=True),
             span("rpc.request", 1.0, tenant="t", ok=False),   # rejected
             span("engine.execute", 4.0, tickets=[1, 2]),     # 2 rides 1
             span("engine.execute", 2.0, tickets=[1]),        # resumed
             span("executor.count", 5.0)]
    ctx = SimpleNamespace(spans=spans, trace=None)
    assert read(ctx) == pytest.approx(((10 - 6) + (6 - 4)) / 2)
    # a program whose executions list no tickets cannot join them
    old = [s for s in spans if s["name"] != "engine.execute"]
    old.append(span("engine.execute", 4.0))
    assert read(SimpleNamespace(spans=old, trace=None)) is None
    # no answered request
    executes = [s for s in spans if s["name"] == "engine.execute"]
    assert read(SimpleNamespace(spans=executes, trace=None)) is None
    for ctx in NOTHING:
        assert read(ctx) is None
