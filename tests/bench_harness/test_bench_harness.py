"""The benchmark harness (`bench/`) on the CPU, at tiny sizes.

    PYTHONPATH=src python -m pytest tests/bench_harness -q

The harness has no CPU mode: these tests steer its device check from
here (as tests/test_chip_smoke.py does for chip_smoke.py), serve a
tiny-er graph in place of the configuration's, and check the loops, the
comparison that decides `correct`, the references, the control, and the
trace reduction against a profiler trace recorded on a v5e.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
from graphgen import make_edges, simple_edges  # noqa: E402

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = [w["name"] for w in bench_json()["workloads"]]
TRI_CELL = "graph500-s13.count-tri-star4"


def tiny_er():
    from repro.graph.datasets import named_dataset

    g = named_dataset("tiny-er")
    return g.n, simple_edges(g.n, g.edge_array())


# ------------------------------------------------------- resolution ----
@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files_by_name(cell):
    bench, c, config, mix = run.load_cell(cell)
    assert config["name"] == c["config"]
    assert config["chips"] == c["chips"]
    for cls in mix["classes"]:
        ref = reference.reference(cls["reference"])
        assert getattr(ref, "is_" + cls["reference"])(cls)
    assert hasattr(run.driver(mix["kind"]), "drive")
    for trace in (False, True):
        names = [m["name"] for m in run.cell_metrics(bench, cell, trace)]
        assert names, (cell, trace)
        for name in names:
            assert callable(run.reader(name))
    assert "setup_s" in [m["name"] for m in run.cell_metrics(bench, cell,
                                                             False)]


def test_every_metric_and_config_file_is_used():
    bench = bench_json()
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


# ---------------------------------------------------------- traffic ----
def _closed(mix, seed, k=12):
    it = traffic.closed_requests(mix, seed)
    return [next(it)[1] for _ in range(k)]


def test_closed_mix_is_fixed_by_the_seed():
    _, _, _, mix = run.load_cell(TRI_CELL)
    a, b, c = (_closed(mix, s) for s in (7, 7, 2 ** 31 + 11))
    assert [r.spec for r in a] == [r.spec for r in b]
    assert [r.spec for r in a] != [r.spec for r in c]
    # the same classes in the same cycle whatever the seed
    assert [r.cls for r in a] == [r.cls for r in c] == mix["cycle"] * 6


def test_graph_keeps_its_shape_and_moves_its_ids_with_the_seed():
    _, _, config, _ = run.load_cell(TRI_CELL)
    (n, a), (_, b), (_, c) = (make_edges(config["generator"], s)
                              for s in (1, 1, 2 ** 33 + 5))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    deg = lambda e: np.sort(np.bincount(e.reshape(-1), minlength=n))  # noqa
    assert np.array_equal(deg(a), deg(c))
    assert (n, len(a), deg(a)[-1]) == (8192, 101979, 2241)


def test_kronecker_quadrants_follow_the_initiator():
    """At SCALE 1 each draw is one quadrant: (0,0) with probability A,
    (0,1) with B, (1,0) with C and (1,1) with D = 1 - A - B - C."""
    from graphgen import kronecker_edges

    e = kronecker_edges(1, 200_000, 0.57, 0.19, 0.19, seed=4)
    share = np.bincount(e[:, 0] * 2 + e[:, 1], minlength=4) / len(e)
    assert share == pytest.approx([0.57, 0.19, 0.19, 0.05], abs=0.005)


# -------------------------------------------------------- references ----
STARS = {k: {"n": k, "edges": [[0, i] for i in range(1, k)]}
         for k in (3, 4, 5)}
TRIANGLE = {"n": 3, "edges": [[0, 1], [1, 2], [0, 2]]}


def test_references_equal_the_oracle_on_tiny_er():
    from repro.core.oracle import count_embeddings_oracle
    from repro.core.pattern import Pattern

    n, edges = tiny_er()
    for ref, pat in [("triangle", TRIANGLE)] + [("star", s)
                                                for s in STARS.values()]:
        want = count_embeddings_oracle(
            n, edges, Pattern(pat["n"], tuple(map(tuple, pat["edges"]))))
        assert reference.reference(ref).count(n, edges, pat) == want


def test_references_refuse_another_pattern():
    n, edges = tiny_er()
    with pytest.raises(ValueError):
        reference.reference("star").count(n, edges, TRIANGLE)
    with pytest.raises(ValueError):
        reference.reference("triangle").count(n, edges, STARS[3])


# ------------------------------------------------------------ the run ---
@pytest.fixture
def tiny(monkeypatch):
    """Steer a run onto the CPU: the device check passes, and tiny-er is
    served in place of the configuration's graph."""
    monkeypatch.setattr(run, "prepare", lambda cell: dict(DEVICE))
    monkeypatch.setattr(run, "make_edges", lambda gen, seed: tiny_er())


def _last_line(capsys) -> dict:
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert out.err.strip().splitlines()[-1].startswith("check ")
    return line


def test_closed_loop_prints_a_well_formed_last_line(tiny, capsys):
    assert run.main(["--workload", TRI_CELL,
                     "--seed", str(2 ** 31 + 7), "--seconds", "2",
                     "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"count_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "tpu"
    assert line["checks"] == {"wrong_counts": {"value": 0, "limit": 0},
                              "missing_answers": {"value": 0, "limit": 0}}


def test_readers_on_a_made_up_window():
    """Each reader on a window of three answered counts and one that
    failed, with its spans and reduced trace."""
    from types import SimpleNamespace

    from loops import Record, Window

    recs = [Record("a", "default", {}, due=t, sent=t, done=t + 1.0,
                   result={"count": 1}) for t in (0.0, 1.0, 2.0)]
    recs.append(Record("a", "default", {}, due=3.0, sent=3.0, error="x"))
    spans = [{"name": "executor.count", "attrs": {"dispatches": 4}},
             {"name": "executor.count", "attrs": {"dispatches": 2}}]
    spans += [{"name": "executor.dispatch",
               "attrs": {"needed": need, "capacity": 8}}
              for need in (3, 9, 8, 20)]
    ctx = SimpleNamespace(
        mix={"kind": "closed"}, win=Window(0.0, 3.0, recs, end=4.0),
        setup_s=12.5, spans=spans,
        trace={"busy_s": 3.0, "window_s": 4.0,
               "kernel_s": {"level_expand": 0.006}})
    assert run.reader("count_s")(ctx) == pytest.approx(1.0)
    assert run.reader("star_count_s")(ctx) == pytest.approx(1.0)
    assert run.reader("setup_s")(ctx) == 12.5
    for suffix in ("count", "star"):
        assert run.reader("dispatches." + suffix)(ctx) == pytest.approx(2.0)
        assert run.reader("level_expand_ms." + suffix)(ctx) == \
            pytest.approx(2.0)
        assert run.reader("idle_share." + suffix)(ctx) == pytest.approx(0.25)
    assert run.reader("overflow_share.count")(ctx) == pytest.approx(0.5)
    # an untraced run has nothing to read
    ctx.spans = ctx.trace = None
    assert run.reader("dispatches.count")(ctx) is None
    assert run.reader("idle_share.star")(ctx) is None


def _fault_answer(monkeypatch):
    """An answer altered where it is produced."""
    from repro.core.executor import Matcher

    orig = Matcher.count_partial

    def count_partial(self, *a, **kw):
        state, out = orig(self, *a, **kw)
        if out is not None:
            out.count += 1
        return state, out

    monkeypatch.setattr(Matcher, "count_partial", count_partial)


def _fault_half_batch(monkeypatch):
    """Half of every dispatch's roots left out (every other one)."""
    import jax.numpy as jnp

    from repro.core.executor import Matcher

    orig = Matcher._fn

    def fn(self, capacity):
        f = orig(self, capacity)

        def half(*args):
            v0 = args[-1]
            cut = jnp.where(jnp.arange(v0.shape[0]) % 2 == 0, v0,
                            self.graph.n)
            return f(*args[:-1], cut)

        return half

    monkeypatch.setattr(Matcher, "_fn", fn)


def _fault_state_unchanged(monkeypatch):
    """A step that returns its state unchanged: no dispatch adds to the
    running total."""
    from repro.core.executor import Matcher

    orig = Matcher.count_partial

    def count_partial(self, state=None, **kw):
        total = 0 if state is None else state.total
        state, out = orig(self, state, **kw)
        state.total = total
        if out is not None:
            out.count = total // self.plan.iep_divisor
        return state, out

    monkeypatch.setattr(Matcher, "count_partial", count_partial)


@pytest.mark.parametrize("fault", [_fault_answer, _fault_half_batch,
                                   _fault_state_unchanged])
def test_a_broken_timed_path_is_not_correct(tiny, capsys, monkeypatch,
                                            fault):
    fault(monkeypatch)
    assert run.main(["--workload", TRI_CELL,
                     "--seed", "5", "--seconds", "1", "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False
    assert line["checks"]["wrong_counts"]["value"] > 0


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


# ----------------------------------------------------------- control ----
@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_every_cell_at_its_size(cell):
    """The int32 control, at the cell's own graph, answers at least one
    class of the mix wrongly, so `wrong_counts` comes out above 0."""
    _, _, config, mix = run.load_cell(cell)
    n, edges = make_edges(config["generator"], 3)
    want = run.references(mix, n, edges, None)
    wrong = {k for k, v in want.items() if reference.control(v) != v}
    assert wrong


def test_control_run_serves_the_wrapped_answers(tiny, capsys, monkeypatch):
    """`bench/control.py` drives a whole run with the program's counts
    replaced; tiny-er's counts fit int32, so wrap at 16 bits here to see
    the wrapped answers reach the check."""
    import control

    monkeypatch.setattr(reference, "control",
                        lambda x: (x + 2 ** 15) % 2 ** 16 - 2 ** 15)
    from repro.query.cache import CacheEntry

    monkeypatch.setattr(CacheEntry, "count_partial",
                        CacheEntry.count_partial)
    monkeypatch.setattr(run, "references", run.references)
    assert control.main(["--workload", TRI_CELL,
                         "--seed", "9", "--seconds", "1"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False
    assert line["checks"]["wrong_counts"]["value"] > 0


def test_control_wraps_like_int32():
    x = 6846241713
    assert reference.control(x) == int(np.array(x).astype(np.int32))
    assert reference.control(1181673) == 1181673


# ------------------------------------------------------------- trace ----
def test_trace_reduction_on_a_v5e_trace():
    """Two star4 counts on one v5e, 50 ms apart, inside
    a `bench.window` annotation; the expected numbers were read from the
    trace independently (testdata/star4x2.json says how)."""
    with open(os.path.join(BENCH, "testdata", "star4x2.json")) as f:
        want = json.load(f)
    dev, host = trace_reduce.load(os.path.join(BENCH, "testdata",
                                               want["trace"]))
    got = trace_reduce.reduce(dev, host)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["kernel_s"]["level_expand"] == pytest.approx(
        want["level_expand_s"], rel=1e-9)
    assert got["busy_s"] < got["window_s"]
    idle = sum(s for _, s in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-9)
    assert len(got["device_ops"]) <= trace_reduce.TOP


def test_self_times_subtract_nested_ops():
    ev = [("cond", 0, 10), ("fusion", 2, 5), ("fusion2", 6, 8)]
    assert trace_reduce.self_times(ev) == {"cond": 5, "fusion": 3,
                                           "fusion2": 2}


def test_idle_gaps_are_named_by_the_innermost_open_span():
    dev = {"/device:TPU:0": [("%a.1 = s32[1]", 10, 20),
                             ("%level_expand.3 = s32[4]", 30, 40)]}
    host = [(trace_reduce.WINDOW, 0, 50)]
    spans = [("engine.round", 100, 145), ("executor.dispatch", 120, 130)]
    got = trace_reduce.reduce(dev, host, spans, anchor_ns=100)
    assert got["busy_s"] == pytest.approx(20e-9)
    assert got["kernel_s"] == {"level_expand": pytest.approx(10e-9)}
    assert dict(got["idle_gaps"]) == pytest.approx(
        {"engine.round": 10e-9, "executor.dispatch": 10e-9,
         "outside the engine": 10e-9})
