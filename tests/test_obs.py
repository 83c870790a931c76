"""Observability layer (repro.obs): tracer span nesting (including
across threads), near-zero disabled cost, Chrome/JSONL export schema,
deterministic histograms, the unified latency dict, registry snapshots,
the summarize CLI gates, and span nesting through a real gateway replay."""
import json
import threading
import time

import numpy as np
import pytest

from repro.core.executor import ExecutorConfig, compute_stats
from repro.graph.datasets import erdos_renyi
from repro.obs import (
    Histogram, MetricsRegistry, Tracer, get_tracer, latency_summary,
    now_ns, set_tracer, timer,
)
from repro.obs.metrics import _key, percentile
from repro.obs.summarize import main as summarize_main, summarize

CFG = ExecutorConfig(capacity=1 << 12)


@pytest.fixture()
def tracer():
    """Enabled tracer installed as the process tracer for one test."""
    old = get_tracer()
    tr = set_tracer(Tracer(enabled=True))
    yield tr
    set_tracer(old)


# ----------------------------------------------------------------- tracer
def test_span_nesting_parent_child(tracer):
    with tracer.span("a.root", k=1) as root:
        with tracer.span("a.child") as c1:
            pass
        with tracer.span("a.child") as c2:
            with tracer.span("a.grand") as g:
                pass
    spans = {s["id"]: s for s in tracer.spans()}
    assert spans[root.span_id]["parent"] is None
    assert spans[c1.span_id]["parent"] == root.span_id
    assert spans[c2.span_id]["parent"] == root.span_id
    assert spans[g.span_id]["parent"] == c2.span_id
    assert spans[root.span_id]["attrs"] == {"k": 1}
    # children close before parents, so durations nest too
    assert spans[g.span_id]["dur_ns"] <= spans[c2.span_id]["dur_ns"]


def test_span_set_attaches_mid_span_attrs(tracer):
    with tracer.span("x.y", a=1) as sp:
        sp.set(b=2, a=3)
    (rec,) = tracer.spans()
    assert rec["attrs"] == {"a": 3, "b": 2}


def test_spans_never_parent_across_threads(tracer):
    """Each thread gets its own parent chain: a span opened on a worker
    thread while the main thread holds an open span must be a root."""
    results = {}

    def worker(name):
        with tracer.span(f"w.{name}") as outer:
            with tracer.span(f"w.{name}.inner") as inner:
                pass
        results[name] = (outer.span_id, outer.parent_id,
                         inner.span_id, inner.parent_id)

    with tracer.span("main.root"):
        threads = [threading.Thread(target=worker, args=(f"t{i}",))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    for _, (oid, oparent, _iid, iparent) in results.items():
        assert oparent is None          # not parented under main.root
        assert iparent == oid           # nested within its own thread
    # main's span records main's thread id; workers record their own
    # (idents can be reused across short-lived threads, so >= 2 not 5)
    tids = {s["tid"] for s in tracer.spans()}
    main_tid = threading.get_ident()
    assert main_tid in tids and len(tids) >= 2


def test_disabled_tracer_is_shared_noop_and_cheap():
    tr = Tracer(enabled=False)
    assert tr.span("a.b", k=1) is tr.span("c.d")    # no allocation
    assert len(tr) == 0
    n = 100_000
    best = float("inf")
    for _ in range(3):
        with timer() as t:
            for _ in range(n):
                with tr.span("hot.loop", i=0):
                    pass
        best = min(best, t.seconds)
    # ~0.4us/span measured; generous 2us bound for loaded CI machines
    assert best / n < 2e-6, f"{best / n * 1e9:.0f}ns per disabled span"


def test_record_and_begin_are_thread_and_coroutine_safe():
    """Spans recorded from many threads at once are all kept, each with
    its own id; spans begun and ended by interleaved coroutines overlap
    without nesting."""
    import asyncio
    import sys

    tr = Tracer(enabled=True)
    n_threads, per = 8, 500

    def worker(k):
        for i in range(per):
            t0 = now_ns()
            tr.record("t.rec", t0, now_ns(), worker=k, i=i)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    recs = tr.spans()
    assert len(recs) == n_threads * per
    assert len({r["id"] for r in recs}) == len(recs)
    assert {(r["attrs"]["worker"], r["attrs"]["i"]) for r in recs} == {
        (k, i) for k in range(n_threads) for i in range(per)}
    assert all(r["parent"] is None and r["dur_ns"] >= 0 for r in recs)

    async def request(k):
        pending = tr.begin("c.req", k=k)
        await asyncio.sleep(0)
        pending.end(done=True)

    async def serve():
        await asyncio.gather(*(request(k) for k in range(50)))

    tr.clear()
    asyncio.run(serve())
    reqs = tr.spans()
    assert sorted(r["attrs"]["k"] for r in reqs) == list(range(50))
    assert all(r["attrs"]["done"] and r["parent"] is None for r in reqs)
    # every request began before any ended: they overlap on one thread
    assert max(r["t0_ns"] for r in reqs) < min(
        r["t0_ns"] + r["dur_ns"] for r in reqs)


def test_annotator_runs_only_while_the_tracer_is_enabled(monkeypatch):
    import repro.obs.trace as trace_mod

    events = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    monkeypatch.setattr(trace_mod, "_annotator", Annotation)
    off = Tracer(enabled=False)
    with off.span("a.b"):
        pass
    off.begin("a.c").end()
    off.record("a.d", 0, 1)
    assert events == [] and len(off) == 0
    on = Tracer(enabled=True)
    with on.span("x.outer"):
        with on.span("x.inner"):
            pass
    on.begin("x.req").end(ok=True)
    assert events == [("enter", "x.outer"), ("enter", "x.inner"),
                      ("exit", "x.inner"), ("exit", "x.outer"),
                      ("enter", "x.req"), ("exit", "x.req")]
    # with no capture recording the hook returns None: spans still record
    monkeypatch.setattr(trace_mod, "_annotator", lambda name: None)
    with on.span("y.z"):
        pass
    assert [s["name"] for s in on.spans()] == [
        "x.inner", "x.outer", "x.req", "y.z"]


def test_spans_are_host_events_of_a_profiler_capture(tracer, tmp_path):
    """The executor's hook puts every span of an enabled tracer into a
    running profiler capture, on the capture's own clock."""
    import glob

    import jax

    import repro.core.executor  # noqa: F401 — installs the hook

    with tracer.span("probe.before"):
        pass
    with jax.profiler.trace(str(tmp_path)):
        with tracer.span("probe.outer"):
            tracer.begin("probe.detached").end()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"probe.outer", "probe.detached"} <= names
    assert "probe.before" not in names


def test_chrome_export_round_trips(tracer, tmp_path):
    with tracer.span("engine.round", tickets=3):
        with tracer.span("executor.count", depth=4):
            pass
    path = tmp_path / "trace.json"
    assert tracer.export_chrome(str(path)) == 2
    doc = json.load(open(path))                     # must parse
    assert doc["displayTimeUnit"] == "ms"
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    outer, inner = by_name["engine.round"], by_name["executor.count"]
    for e in (outer, inner):
        assert e["ph"] == "X"
        assert e["cat"] == e["name"].split(".")[0]  # perfetto category
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
    assert inner["args"]["parent"] == outer["args"]["id"]
    assert outer["args"]["tickets"] == 3
    assert inner["args"]["depth"] == 4
    # ... and the summarizer accepts its own exporter's output
    summ = summarize(doc)
    assert summ["events"] == 2
    assert summ["rows"][0]["count"] == 1


def test_jsonl_export(tracer, tmp_path):
    with tracer.span("a.b"):
        pass
    path = tmp_path / "spans.jsonl"
    assert tracer.export_jsonl(str(path)) == 1
    (rec,) = [json.loads(line) for line in open(path)]
    assert rec["name"] == "a.b" and rec["parent"] is None


def test_max_spans_bound_counts_drops(tmp_path):
    tr = Tracer(enabled=True, max_spans=2)
    for i in range(4):
        with tr.span("s.n", i=i):
            pass
    assert len(tr) == 2 and tr.dropped == 2
    path = tmp_path / "t.json"
    tr.export_chrome(str(path))
    assert json.load(open(path))["otherData"]["dropped_spans"] == 2
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_timer_measures():
    with timer() as t:
        time.sleep(0.01)
    assert t.seconds >= 0.005


# ---------------------------------------------------------------- metrics
def test_percentile_matches_numpy():
    rng = np.random.default_rng(3)
    vals = sorted(rng.exponential(10.0, size=257).tolist())
    for q in (0, 12.5, 50, 95, 99, 100):
        assert percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)), rel=1e-12)
    assert percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_histogram_exact_counts_and_deterministic_decimation():
    h1, h2 = Histogram(max_samples=16), Histogram(max_samples=16)
    vals = [float((7 * i) % 101) for i in range(1000)]
    for v in vals:
        h1.observe(v)
        h2.observe(v)
    # count/total exact even after the reservoir thinned
    assert h1.count == 1000 and h1.total == pytest.approx(sum(vals))
    assert len(h1._samples) < 1000
    # no RNG: identical sequences give identical reservoirs + summaries
    assert h1._samples == h2._samples
    assert h1.summary() == h2.summary()
    s = h1.summary()
    assert s["n"] == 1000 and 0 <= s["p50"] <= 100


def test_latency_summary_unified_keys():
    h = Histogram()
    keys = {"n", "p50_ms", "p95_ms", "p99_ms", "mean_ms"}
    empty = latency_summary(h)
    assert set(empty) == keys and empty["n"] == 0
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    s = latency_summary(h)
    assert set(s) == keys
    assert s["p50_ms"] == 2.0 and s["mean_ms"] == 2.0


def test_registry_keys_snapshot_and_reset_window():
    reg = MetricsRegistry()
    # labels sort into one canonical key, order-independent
    assert _key("s.m", {"b": 2, "a": 1}) == "s.m{a=1,b=2}"
    c = reg.counter("engine.executions")
    assert reg.counter("engine.executions") is c    # get-or-create
    c.inc(3)
    reg.gauge("engine.pending").set(7)
    reg.histogram("scheduler.turn_item_ms", workload="graph",
                  phase="solo").observe(4.0)
    reg.register_collector(lambda: {"cache.hits": 9})
    snap = reg.snapshot()
    assert snap["engine.executions"] == 3
    assert snap["engine.pending"] == 7
    assert snap["scheduler.turn_item_ms{phase=solo,workload=graph}"]["n"] == 1
    assert snap["cache.hits"] == 9
    reg.reset_window()
    snap = reg.snapshot()
    assert snap["engine.executions"] == 0           # counters zeroed
    assert snap["scheduler.turn_item_ms{phase=solo,workload=graph}"]["n"] == 0
    assert snap["engine.pending"] == 7              # gauges keep state
    assert snap["cache.hits"] == 9                  # collectors unaffected


# -------------------------------------------------------------- summarize
def _doc(events):
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def _ev(name, sid, parent, dur, ts=0.0):
    return {"name": name, "cat": name.split(".")[0], "ph": "X", "ts": ts,
            "dur": dur, "pid": 1, "tid": 1,
            "args": {"id": sid, "parent": parent}}


def test_summarize_self_time_and_coverage():
    doc = _doc([_ev("a.root", 1, None, 100.0),
                _ev("a.leaf", 2, 1, 60.0, ts=10.0)])
    s = summarize(doc)
    assert s["wall_us"] == 100.0 and s["leaf_us"] == 60.0
    assert s["leaf_coverage"] == pytest.approx(0.6)
    rows = {r["name"]: r for r in s["rows"]}
    assert rows["a.root"]["self_us"] == 40.0 and not rows["a.root"]["leaf"]
    assert rows["a.leaf"]["self_us"] == 60.0 and rows["a.leaf"]["leaf"]


def test_summarize_rejects_malformed():
    with pytest.raises(ValueError):
        summarize({"notATrace": []})
    with pytest.raises(ValueError):
        summarize(_doc([]))                         # no complete events
    with pytest.raises(ValueError):
        summarize(_doc([{"ph": "X", "name": "x", "args": {}}]))  # no dur


def test_summarize_cli_exit_codes(tmp_path, capsys):
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_doc([_ev("a.root", 1, None, 100.0),
                                     _ev("a.leaf", 2, 1, 60.0)])))
    assert summarize_main([str(good)]) == 0
    assert "leaf_coverage=60.0%" in capsys.readouterr().out
    # coverage gate: 60% < 95% -> exit 2 (the bench/CI acceptance knob)
    assert summarize_main([str(good), "--min-coverage", "0.95"]) == 2
    assert summarize_main([str(good), "--min-coverage", "0.5"]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert summarize_main([str(bad)]) == 1
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps(_doc([])))
    assert summarize_main([str(empty)]) == 1
    assert summarize_main([str(tmp_path / "missing.json")]) == 1


# ---------------------------------------------- instrumented serving path
@pytest.fixture(scope="module")
def obs_graph():
    return erdos_renyi(64, 256, seed=7, name="er64")


@pytest.fixture(scope="module")
def obs_stats(obs_graph):
    return compute_stats(obs_graph, CFG)


def _replay(graph, stats, metrics):
    """One gateway_smoke-shaped run: 2 classes x (original + iso dup)."""
    from repro.configs.graphpi import get_pattern
    from repro.query import QueryEngine, QueryRequest, relabeled_variant
    from repro.serve.gateway import Gateway, GraphQueryWorkload, Share

    engine = QueryEngine(graph, cfg=CFG, stats=stats, metrics=metrics)
    reqs = []
    for i, name in enumerate(("triangle", "P1")):
        p = get_pattern(name)
        reqs.append(QueryRequest(p))
        reqs.append(QueryRequest(relabeled_variant(p, seed=i)))
    gw = Gateway(metrics=metrics)
    wl = gw.add(GraphQueryWorkload(engine, reqs), Share(quantum=2))
    gw.run()
    return engine, gw, wl.results()


def test_gateway_replay_span_nesting(tracer, obs_graph, obs_stats):
    """The acceptance-criteria trace shape: scheduler rounds nest engine
    plan/execute spans which nest executor dispatch spans."""
    engine, _gw, results = _replay(obs_graph, obs_stats, MetricsRegistry())
    assert len(results) == 4
    spans = tracer.spans()
    by_id = {s["id"]: s for s in spans}

    def parent_name(s):
        return by_id[s["parent"]]["name"] if s["parent"] else None

    names = {s["name"] for s in spans}
    assert {"gateway.run", "scheduler.round", "scheduler.turn",
            "engine.round", "engine.plan", "engine.execute",
            "executor.count", "executor.dispatch",
            "cache.search", "cache.compile"} <= names
    for s in spans:
        if s["name"] == "scheduler.round":
            assert parent_name(s) == "gateway.run"
        elif s["name"] == "scheduler.turn":
            assert parent_name(s) == "scheduler.round"
        elif s["name"] == "engine.round":
            assert parent_name(s) == "scheduler.turn"
        elif s["name"] in ("engine.plan", "engine.execute"):
            assert parent_name(s) == "engine.round"
        elif s["name"] == "executor.dispatch":
            assert parent_name(s) == "executor.count"
        elif s["name"] in ("executor.roots", "executor.launch",
                           "executor.readback"):
            assert parent_name(s) == "executor.dispatch"
    # each dispatch's host turn: its roots, one launch, and one readback
    # of `needed` (a second of the count where the chunk did not overflow)
    dispatches = [s for s in spans if s["name"] == "executor.dispatch"]
    assert dispatches
    for d in dispatches:
        kids = [s["name"] for s in spans if s["parent"] == d["id"]]
        assert kids[:3] == ["executor.roots", "executor.launch",
                            "executor.readback"]
        assert kids[3:] == (["executor.readback"]
                            if d["attrs"]["needed"] <= d["attrs"]["capacity"]
                            else [])
        assert d["attrs"]["entries_valid"] <= d["attrs"]["entries_streamed"]
    # coalescing evidence rides on the round + execute spans: each
    # iso duplicate becomes a rider on its class lead, never a second
    # execution
    rounds = [s for s in spans if s["name"] == "engine.round"]
    assert sum(s["attrs"]["tickets"] for s in rounds) == 4
    assert sum(s["attrs"]["coalesced"] for s in rounds) == 2
    execs = [s for s in spans if s["name"] == "engine.execute"]
    assert len(execs) == 2
    assert sum(s["attrs"]["riders"] for s in execs) == 2
    # a request's spans join on its ticket: every ticket is listed once
    assert sorted(t for s in execs for t in s["attrs"]["tickets"]) == [
        0, 1, 2, 3]
    # the trace localizes time: leaf spans cover >=95% of the wall
    doc = {"traceEvents": tracer.chrome_events()}
    assert summarize(doc)["leaf_coverage"] >= 0.95


def test_dispatch_spans_carry_the_planned_need(tracer, obs_graph,
                                               obs_stats):
    """Span planning rides on the trace: a count that starts afresh
    carries `planned_spans` on its `executor.count`, and every
    `executor.dispatch` the host's `predicted` need, a lower bound on
    the device's `needed` wherever it fits capacity."""
    _replay(obs_graph, obs_stats, MetricsRegistry())
    spans = tracer.spans()
    counts = [s["attrs"] for s in spans if s["name"] == "executor.count"]
    fresh = [c for c in counts if "planned_spans" in c]
    assert len(fresh) == 2                 # one per executed class
    assert all(not c["resumed"] and c["planned_spans"] >= 1 for c in fresh)
    assert all(c["resumed"] for c in counts if "planned_spans" not in c)
    dispatches = [s["attrs"] for s in spans
                  if s["name"] == "executor.dispatch"]
    assert dispatches
    for d in dispatches:
        if d["predicted"] <= d["capacity"]:
            assert d["predicted"] <= d["needed"]
        else:
            assert d["needed"] > d["capacity"]
    # nothing overflowed, so every dispatch ran a planned span
    assert all(d["needed"] <= d["capacity"] for d in dispatches)
    assert sum(c["planned_spans"] for c in fresh) == len(dispatches)


def test_count_spans_carry_the_predicted_steps(tracer, obs_graph,
                                               obs_stats):
    """A fresh `executor.count` says how many of its plan's window steps
    the host plan covers (`predicted_steps`) beside the plan's total
    (`window_steps`).  On er64 every level's candidates fit one
    dispatch's slots, so the host enumerates the whole plan, the
    triangle's two steps and the house's four, and every dispatch reads
    back exactly the need the host predicted."""
    _replay(obs_graph, obs_stats, MetricsRegistry())
    spans = tracer.spans()
    fresh = [s["attrs"] for s in spans if s["name"] == "executor.count"
             and "planned_spans" in s["attrs"]]
    assert sorted((c["predicted_steps"], c["window_steps"])
                  for c in fresh) == [(2, 2), (4, 4)]
    dispatches = [s["attrs"] for s in spans
                  if s["name"] == "executor.dispatch"]
    assert all(d["needed"] == d["predicted"] for d in dispatches)


def test_registry_snapshot_stable_across_replays(obs_graph, obs_stats):
    """Two identical replays on fresh engines expose the same snapshot
    key set with the same integer counters (latency values differ)."""
    snaps = []
    for _ in range(2):
        metrics = MetricsRegistry()
        engine, gw, _ = _replay(obs_graph, obs_stats, metrics)
        assert engine.latency_percentiles().keys() == {
            "n", "p50_ms", "p95_ms", "p99_ms", "mean_ms"}
        rep = gw.report()["workloads"]["graph"]
        assert set(rep["turn_item_ms"]["solo"]) == {
            "n", "p50_ms", "p95_ms", "p99_ms", "mean_ms"}
        snaps.append(metrics.snapshot())
    a, b = snaps
    assert a.keys() == b.keys()
    for k in ("engine.requests_resolved", "engine.executions",
              "engine.coalesced", "cache.hits", "cache.misses"):
        assert a[k] == b[k], k
    assert a["engine.query_latency_ms"]["n"] == 4
    assert b["engine.query_latency_ms"]["n"] == 4
