#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of BENCHMARK.json names a configuration (`bench/configs/`) and a
traffic mix (`bench/traffic/`).  The run makes the configuration's graph
from the seed, computes its host statistics, starts the served stack in
a thread of this process, warms every pattern class of the mix, drives
the mix's loop (`bench/loops/`) for `--seconds`, then checks every
answer against the plain references (`bench/reference/`).

The last line of stdout is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics, or with `--trace 1`
its per-layer ones, each read by `bench/metrics/<name>.py`), `device`,
with `--trace 1` a `breakdown`, and last `checks`: each number compared
with its limit.  The same checks are the last lines of stderr.  Without
a TPU, or without the program beside it, the run exits nonzero before
printing a result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from types import SimpleNamespace

T_START = time.perf_counter()

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import numpy as np  # noqa: E402

from graphgen import make_edges, seed_sequence  # noqa: E402
from loops import driver  # noqa: E402
import reference  # noqa: E402
from traffic import relabel  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_age_s() -> float:
    """Seconds since this process started (Linux /proc), else since this
    module was imported."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return max(age, time.perf_counter() - T_START)
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_START


# ------------------------------------------------------------- the cell --
def load_cell(name: str, root: str = ROOT) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, mix) of the cell `name`."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    (conf,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return bench, cell, config, mix


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metric entries a run of `cell` reports."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------- the device --
def device_info(chips: int) -> dict:
    """The device the run measures; exits nonzero unless JAX sees at
    least `chips` TPU chips."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"no accelerator: jax.devices()[0] is "
                         f"{d.platform!r}, not a TPU")
    if len(devs) < chips:
        raise SystemExit(f"{chips} chips asked for, {len(devs)} found")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class CompileCounter:
    """Executables JAX makes for this process (compiled, or loaded from
    the persistent cache), and persistent-cache hits and misses."""

    def __init__(self):
        import jax.monitoring as mon

        self.programs = self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"programs": self.programs, "cache_hits": self.hits,
                "cache_misses": self.misses}


# ------------------------------------------------------------ one run ----
def references(mix: dict, n: int, edges, tri: int | None) -> dict:
    """Reference count of every class of the mix (classes of one
    isomorphism type ask the same question of the same graph)."""
    out = {}
    for c in mix["classes"]:
        pattern = {"n": c["n"], "edges": c["edges"]}
        if c["reference"] == "triangle" and tri is not None:
            out[c["name"]] = tri
        else:
            out[c["name"]] = reference.reference(c["reference"]).count(
                n, edges, pattern)
    return out


def check(win, mix: dict, n: int, edges, want: dict) -> dict:
    """Compare every answer of the window with the reference.  Each
    request's own relabelled pattern is checked to be of its class."""
    cls = {c["name"]: c for c in mix["classes"]}
    wrong = missing = 0
    for rec in win.records:
        if rec.result is None:
            missing += 1
            continue
        ref = reference.reference(cls[rec.cls]["reference"])
        kind = getattr(ref, "is_" + cls[rec.cls]["reference"])
        if not kind(rec.spec["pattern"]) or int(
                rec.result["count"]) != want[rec.cls] or rec.result.get(
                "overflowed"):
            wrong += 1
    return {"wrong_counts": {"value": wrong, "limit": 0},
            "missing_answers": {"value": missing, "limit": 0}}


def warm(host: str, port: int, mix: dict, seed: int) -> None:
    """Count every class of the mix once through the served path: plans,
    compiles (or loads from the cache) and runs every program the window
    will use, escalated capacities included."""
    from repro.serve.rpc import RPCClient

    rng = np.random.default_rng(seed_sequence(seed, "warm"))
    client = RPCClient(host, port, timeout=1200.0)
    try:
        for c in mix["classes"]:
            t = time.perf_counter()
            res = client.result(client.submit(relabel(c, "warmup", rng)))
            log(f"[setup] warm {c['name']}: count={res['count']} "
                f"{time.perf_counter() - t:.3f}s (search "
                f"{res['search_seconds']:.3f}s, compile "
                f"{res['compile_seconds']:.3f}s)")
    finally:
        client.close()


def quarters(win) -> str:
    """Mean latency of each class in each quarter of the window, so that a
    slow stretch of a run shows where it fell."""
    span = max(win.end - win.t0, 1e-9)
    out = []
    for name in dict.fromkeys(r.cls for r in win.records):
        q = [[] for _ in range(4)]
        for r in win.records:
            if r.cls == name and r.done is not None:
                q[min(int(4 * (r.sent - win.t0) / span), 3)].append(
                    r.done - r.sent)
        out.append(name + " " + " / ".join(
            f"{sum(x) / len(x) * 1e3:.1f}" if x else "-" for x in q))
    return "; ".join(out) + " ms"


def run_cell(bench: dict, cell: dict, config: dict, mix: dict, *,
             seed: int, seconds: float, trace: bool, device: dict,
             artifacts: str) -> dict:
    import jax

    from repro.obs import Tracer, set_tracer

    import server as served_stack
    import trace_reduce

    compiles = CompileCounter()
    tracer = set_tracer(Tracer(enabled=trace))
    t = time.perf_counter()
    n, edges = make_edges(config["generator"], seed)
    graph = served_stack.build_graph(n, edges, config["name"])
    t_graph = time.perf_counter() - t
    # The engine's start-up statistics need the triangle count.  The
    # reference counts it on the host; a restarted server would read it
    # from its plan store, so its seconds are left out of `setup_s`.
    t = time.perf_counter()
    tri = reference.reference("triangle").count(
        n, edges, {"n": 3, "edges": [[0, 1], [1, 2], [0, 2]]})
    t_ref = time.perf_counter() - t
    log(f"[setup] {config['name']}: |V|={graph.n} |E|={graph.m} "
        f"max_degree={graph.max_degree}; graph {t_graph:.2f}s, host "
        f"triangles {tri} {t_ref:.2f}s (left out of setup_s)")
    srv = served_stack.start(graph, tri, config["engine"])
    try:
        warm(srv.host, srv.port, mix, seed)
        setup_compiles = compiles.snapshot()
        tracer.clear()
        loop = driver(mix["kind"])
        setup_s = process_age_s() - t_ref
        log(f"[setup] {setup_s:.3f}s to the window; executables "
            f"{setup_compiles}")
        if trace:
            trace_dir = os.path.join(artifacts, "trace")
            with jax.profiler.trace(trace_dir,
                                    profiler_options=trace_reduce.options()):
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                    anchor_ns = time.perf_counter_ns()
                    win = loop.drive(srv.host, srv.port, mix, seed, seconds)
        else:
            win = loop.drive(srv.host, srv.port, mix, seed, seconds)
        window_compiles = {k: v - setup_compiles[k]
                           for k, v in compiles.snapshot().items()}
        peak = memory_peak_bytes()
        spans = tracer.spans() if trace else None
    finally:
        srv.stop()
    del srv
    set_tracer(Tracer(enabled=False))
    gc.collect()
    reduced = None
    if trace:
        on_host = [(s["name"], tracer.epoch_ns + s["t0_ns"],
                    tracer.epoch_ns + s["t0_ns"] + s["dur_ns"])
                   for s in spans]
        reduced = trace_reduce.reduce_dir(os.path.join(artifacts, "trace"),
                                          on_host, anchor_ns)
    t = time.perf_counter()
    want = references(mix, n, edges, tri)
    checks = check(win, mix, n, edges, want)
    log(f"[check] references {want}; {len(win.records)} answers compared "
        f"with them in {time.perf_counter() - t:.3f}s")
    log(f"[window] mean latency by quarter: {quarters(win)}")
    log(f"[window] {len(win.records)} requests, executables made in the "
        f"window {window_compiles}")
    # what the metric readers read: the window's client records, the
    # program's spans and counters, and the reduced profiler trace
    ctx = SimpleNamespace(mix=mix, win=win, setup_s=setup_s, spans=spans,
                          trace=reduced)
    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=peak)
    out = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(win.records),
        "failed": sum(r.result is None for r in win.records),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def prepare(cell: dict) -> dict | None:
    """Put the program on the path, check the device and turn on the
    persistent compilation cache; None when there is no program beside
    the benchmark."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"no program beside the benchmark: {src}/repro is missing")
        return None
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache

    import jax

    device = device_info(int(cell["chips"]))
    log(f"[setup] compile cache at {enable_compile_cache()}")
    # every program of the cell goes to the persistent cache, however
    # quickly it compiled, so a second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, mix = load_cell(args.workload)
    device = prepare(cell)
    if device is None:
        return 2
    artifacts = os.path.join(ROOT, "artifacts", "bench", args.workload)
    os.makedirs(artifacts, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=artifacts) as tmp:
        out = run_cell(bench, cell, config, mix, seed=args.seed,
                       seconds=args.seconds, trace=bool(args.trace),
                       device=device, artifacts=tmp)
    report(out)
    return 0


def report(out: dict) -> None:
    """Each number compared, beside its limit, as the last lines of
    stderr; the result as the last line of stdout."""
    for name, c in out["checks"].items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
