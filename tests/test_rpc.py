"""Ticket RPC front door (serve/rpc.py): framing, wire shapes, and a
real socket round-trip whose counts must be bit-identical to the
in-process engine path (ISSUE 9 acceptance)."""
import asyncio
import threading

import pytest

from repro.configs.graphpi import get_pattern
from repro.core.executor import ExecutorConfig, compute_stats
from repro.graph.datasets import erdos_renyi
from repro.query import QueryEngine, QueryRequest
from repro.serve.gateway import Gateway, GraphQueryWorkload, Share
from repro.serve.rpc import (
    MAX_FRAME, GatewayRPCServer, RPCClient, RPCError, encode_frame,
    read_frame, request_from_spec, result_to_wire,
)

CFG = ExecutorConfig(capacity=1 << 12)


@pytest.fixture(scope="module")
def graph():
    return erdos_renyi(64, 256, seed=7, name="er64")


@pytest.fixture(scope="module")
def stats(graph):
    return compute_stats(graph, CFG)


# -------------------------------------------------------------- framing
def _read_bytes(data: bytes):
    async def go():
        reader = asyncio.StreamReader()
        reader.feed_data(data)
        reader.feed_eof()
        return await read_frame(reader)
    return asyncio.run(go())


def test_frame_roundtrip():
    msg = {"op": "submit", "pattern": {"n": 3, "edges": [[0, 1], [1, 2]]},
           "tenant": "t0"}
    assert _read_bytes(encode_frame(msg)) == msg


def test_frame_eof_and_oversize():
    assert _read_bytes(b"") is None            # clean EOF -> None
    assert _read_bytes(b"\x00\x00") is None    # torn header -> None
    import struct
    huge = struct.pack(">I", MAX_FRAME + 1)
    with pytest.raises(ValueError):
        _read_bytes(huge)
    with pytest.raises(ValueError):
        encode_frame({"pad": "x" * (MAX_FRAME + 16)})


def test_request_from_spec_matches_trace_format():
    req = request_from_spec({"pattern": "triangle", "tenant": "acme"})
    assert req.pattern == get_pattern("triangle")
    assert req.tenant == "acme"
    assert req.use_iep is False and req.mode == "graphpi"
    inline = request_from_spec(
        {"pattern": {"n": 3, "edges": [[2, 1], [0, 2], [1, 0]]}})
    assert inline.pattern.n == 3 and inline.pattern.name == "inline"
    assert inline.tenant == "default"


# ----------------------------------------------------------- socket path
TRACE = [
    {"pattern": "triangle"},
    {"pattern": "P1"},
    {"pattern": {"n": 3, "edges": [[2, 1], [0, 2], [1, 0]]}},
    {"pattern": "triangle"},          # duplicate: must coalesce server-side
]


def _start_server(engine):
    """GatewayRPCServer on an ephemeral port, event loop in a daemon
    thread; returns (server, thread, port)."""
    gw = Gateway()
    wl = gw.add(GraphQueryWorkload(engine), Share(quantum=4))
    server = GatewayRPCServer(gw, wl)
    ready = threading.Event()
    box = {}

    def on_ready(host, port):
        box["port"] = port
        ready.set()

    th = threading.Thread(target=server.serve_forever,
                          kwargs={"on_ready": on_ready}, daemon=True)
    th.start()
    assert ready.wait(timeout=60), "RPC server never came up"
    return server, th, box["port"]


def test_socket_counts_bit_identical(graph, stats):
    """The acceptance counter-assert: every count fetched over the
    socket equals the count the in-process engine computes for the same
    trace."""
    ref_engine = QueryEngine(graph, cfg=CFG, stats=stats)
    ref = []
    for spec in TRACE:
        t = ref_engine.enqueue(request_from_spec(spec))
        ref_engine.run_pending()
        ref.append(t.result.count)

    engine = QueryEngine(graph, cfg=CFG, stats=stats, chunk=8,
                         preempt_dispatches=4)
    server, th, port = _start_server(engine)
    client = RPCClient("127.0.0.1", port, timeout=120.0)
    try:
        tickets = [client.submit(spec) for spec in TRACE]
        results = [client.result(tk) for tk in tickets]
        assert [r["count"] for r in results] == ref
        for r in results:
            assert "count=" in r["line"]      # what the smoke diff greps
        # the duplicate triangle never re-plans: depending on how the
        # drive loop interleaves with the submits it either coalesces
        # into the in-flight group or hits the plan cache — both count
        # as hits, and both must cover the repeated class
        stats_resp = client.stats()
        assert stats_resp["stats"]["requests_resolved"] == len(TRACE)
        s = stats_resp["stats"]
        assert s["cache"]["hits"] + s["coalesced"] >= 1
        assert s["cache"]["misses"] == 2      # triangle class + P1 class
        assert stats_resp["rounds"] >= 1
        # resolved tickets: poll reports done, cancel refuses
        p = client.poll(tickets[0])
        assert p == {"ok": True, "done": True, "cancelled": False}
        assert client.cancel(tickets[0]) is False
        assert client.poll(999).get("ok") is False       # unknown ticket
        with pytest.raises(RPCError):
            client.result(999)
    finally:
        client.shutdown()
        client.close()
        th.join(timeout=30)
    assert not th.is_alive()
    assert engine.preemptions >= 1            # budget was actually active


def test_socket_request_spans_join_on_their_ticket(graph, stats):
    """Each submit's ticket is on its `rpc.request` span and on the
    `engine.execute` spans that served it, which the request span
    covers."""
    from repro.obs import Tracer, get_tracer, set_tracer

    old = get_tracer()
    tracer = set_tracer(Tracer(enabled=True))
    engine = QueryEngine(graph, cfg=CFG, stats=stats)
    server, th, port = _start_server(engine)
    client = RPCClient("127.0.0.1", port, tenant="acme", timeout=120.0)
    try:
        tickets = [client.submit(spec) for spec in TRACE]
        for tk in tickets:
            client.result(tk)
    finally:
        client.shutdown()
        client.close()
        th.join(timeout=30)
        set_tracer(old)
    assert not th.is_alive()
    spans = tracer.spans()
    reqs = {s["attrs"]["ticket"]: s for s in spans
            if s["name"] == "rpc.request"}
    assert sorted(reqs) == sorted(tickets)
    for tk, req in reqs.items():
        assert req["attrs"]["tenant"] == "acme" and req["attrs"]["ok"]
        assert req["parent"] is None
        execs = [s for s in spans if s["name"] == "engine.execute"
                 and tk in s["attrs"]["tickets"]]
        assert execs
        for s in execs:
            assert req["t0_ns"] <= s["t0_ns"]
            assert s["t0_ns"] + s["dur_ns"] <= req["t0_ns"] + req["dur_ns"]


def test_socket_admission_rejection(graph, stats):
    """tenant_depth=0 rejects every submit: the wire carries the full
    Rejection payload and the client surfaces it as RPCError."""
    engine = QueryEngine(graph, cfg=CFG, stats=stats, tenant_depth=0)
    server, th, port = _start_server(engine)
    client = RPCClient("127.0.0.1", port, tenant="acme", timeout=60.0)
    try:
        with pytest.raises(RPCError) as ei:
            client.submit({"pattern": "triangle"})
        resp = ei.value.resp
        assert resp["error"] == "rejected"
        assert resp["rejection"] == {"tenant": "acme",
                                     "reason": "queue depth bound",
                                     "depth": 0, "limit": 0}
        assert engine.rejections == {"acme": 1}
        assert client.call({"op": "bogus"})["ok"] is False
    finally:
        client.shutdown()
        client.close()
        th.join(timeout=30)
    assert not th.is_alive()


def test_socket_mutate_then_replay_bit_identical(graph):
    """Live serving over the wire: queries, then insert/delete mutations,
    then the same queries again — every post-mutation count equals a
    fresh engine built from scratch on the mutated edge set, and the
    mutation is acked with the epoch it queued against."""
    from repro.graph.csr import GraphCSR

    engine = QueryEngine(graph, cfg=CFG, live=True)
    ins = [[0, 63], [1, 62], [2, 61]]
    ins = [e for e in ins if not graph.has_edge(*e)]
    dels = [[int(u), int(v)] for u, v in graph.edge_array()[:2]]
    server, th, port = _start_server(engine)
    client = RPCClient("127.0.0.1", port, timeout=120.0)
    try:
        before = [client.result(client.submit({"pattern": n}))["count"]
                  for n in ("triangle", "P1")]
        ack = client.mutate("insert_edges", ins)
        assert ack["ok"] and ack["verb"] == "insert_edges"
        assert ack["queued_edges"] == len(ins)
        client.mutate("delete_edges", dels)
        after = [client.result(client.submit({"pattern": n}))["count"]
                 for n in ("triangle", "P1")]
        client.mutate("compact")
        compacted = [client.result(client.submit({"pattern": n}))["count"]
                     for n in ("triangle", "P1")]
    finally:
        client.shutdown()
        client.close()
        th.join(timeout=30)
    assert not th.is_alive()
    edges = set(map(tuple, graph.edge_array().tolist()))
    edges |= {tuple(e) for e in ins}
    edges -= {tuple(e) for e in dels}
    rebuilt = GraphCSR.from_edges(graph.n, sorted(edges), name="rebuilt")
    ref_engine = QueryEngine(rebuilt, cfg=CFG)
    ref = []
    for n in ("triangle", "P1"):
        t = ref_engine.enqueue(request_from_spec({"pattern": n}))
        ref_engine.run_pending()
        ref.append(t.result.count)
    assert after == ref and compacted == ref
    assert after != before                  # the mutation actually bit
    assert engine.summary()["live"]["mutations_applied"] >= len(ins)
