"""The system under test, as `launch/gateway.py --no-lm --single-device
--listen` builds it: QueryEngine -> Gateway + GraphQueryWorkload ->
GatewayRPCServer, serving in a thread of this process (the process that
holds the chip).  The graph is the benchmark's own, and the engine gets
its start-up statistics from the benchmark's host reference, as a
restarted server gets them from its plan store, so it never counts its
start-up triangles on the device.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass

JOIN_TIMEOUT_S = 120
GRAPH_QUANTUM = 4                 # launch/gateway.py --graph-quantum


@dataclass
class Served:
    engine: object
    server: object
    thread: threading.Thread
    box: dict                     # the server loop's address, or its error
    host: str
    port: int

    def stop(self) -> None:
        """Stop serving and wait for the thread; re-raise what it raised."""
        self.box["loop"].call_soon_threadsafe(self.server.stop)
        self.thread.join(timeout=JOIN_TIMEOUT_S)
        if self.thread.is_alive():
            raise TimeoutError("server thread did not stop")
        if "exc" in self.box:
            raise RuntimeError("server failed") from self.box["exc"]


def build_graph(n: int, edges, name: str):
    from repro.graph.csr import GraphCSR

    return GraphCSR.from_edges(n, edges, relabel_by_degree=True, name=name)


def start(graph, tri_cnt: int, engine_cfg: dict) -> Served:
    """Build the served stack on `graph`, with the launcher's default
    gateway settings, and start listening on an ephemeral localhost
    port."""
    import asyncio

    from repro.core.executor import ExecutorConfig, auto_buckets
    from repro.core.perf_model import GraphStats
    from repro.launch.mesh import shared_host_mesh
    from repro.obs import MetricsRegistry
    from repro.query import PlanCache, QueryEngine
    from repro.configs.graphpi import get_pattern
    from repro.serve.gateway import Gateway, GraphQueryWorkload, Share
    from repro.serve.rpc import GatewayRPCServer

    if engine_cfg.get("degree_buckets", "auto") != "auto":
        raise ValueError("only auto degree buckets are supported")
    if int(engine_cfg.get("devices", 1)) != 1:
        raise ValueError("only the single-device engine is supported")
    cfg = ExecutorConfig(capacity=int(engine_cfg["capacity"]),
                         degree_buckets=auto_buckets(graph))
    stats = GraphStats(n_vertices=graph.n, n_edges=graph.m, tri_cnt=tri_cnt)
    metrics = MetricsRegistry()
    engine = QueryEngine(graph, cfg=cfg, cache=PlanCache(), stats=stats,
                         metrics=metrics)
    gw = Gateway(mesh=shared_host_mesh(model=1), metrics=metrics)
    workload = gw.add(GraphQueryWorkload(engine), Share(quantum=GRAPH_QUANTUM))
    server = GatewayRPCServer(gw, workload, host="127.0.0.1", port=0,
                              get_pattern=get_pattern)
    ready = threading.Event()
    box: dict = {}

    def on_ready(host, port):
        box["loop"] = asyncio.get_running_loop()
        box["addr"] = (host, port)
        ready.set()

    def run():
        try:
            server.serve_forever(on_ready=on_ready)
        except BaseException as e:  # noqa: BLE001 — re-raised by stop()
            box["exc"] = e
            ready.set()

    th = threading.Thread(target=run, name="gateway", daemon=True)
    th.start()
    if not ready.wait(JOIN_TIMEOUT_S) or "exc" in box:
        raise RuntimeError("server did not start") from box.get("exc")
    host, port = box["addr"]
    return Served(engine, server, th, box, host, port)
