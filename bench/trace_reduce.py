"""From a JAX profiler trace (`.xplane.pb`) to device busy time, kernel
time and idle gaps.

On a TPU the trace holds one plane per chip (`/device:TPU:<i>`) whose
`XLA Ops` line has one event per HLO instruction run, named by its HLO
text (`%level_expand.3 = s32[...] custom-call(...)`); events of a
`conditional` or `while` nest the instructions of their bodies.  Host
planes and device planes share one clock (nanoseconds from the start of
the trace), so the benchmark's `bench.window` annotation on the host
bounds the window on the device too.

- busy: the union of the `XLA Ops` intervals inside the window, averaged
  over the chips;
- kernel time: the summed durations of the ops whose instruction name is
  the kernel's (`level_expand` for `pallas_call(name="level_expand")`);
- device ops: self time (less nested ops) per instruction, summed, each
  named by its instruction and result shape;
- idle gaps: the complement of busy inside the window, each named by the
  innermost program span (`repro.obs`) that was open on the host at its
  midpoint, or "outside the engine" where none was.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
KERNELS = ("level_expand",)
TOP = 10
_INSTR = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s|=|$)")


def options():
    """Profiler options for the traced run: no Python function tracing
    (it would trace the server's every call) and no HLO protos."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def instruction(name: str) -> str:
    """`%fusion.13 = s32[6029312]{0:T(1024)} fusion(...)` ->
    `fusion.13 = s32[6029312]`: the instruction and its result's shape."""
    return name.lstrip("%").split("{", 1)[0].split("(", 1)[0].rstrip(" =")


def kernel_of(name: str) -> str | None:
    m = _INSTR.match(name.split(" = ", 1)[0])
    base = m.group(1) if m else ""
    return base if base in KERNELS else None


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events):
    """Self time of each (name, start, end) event of one line, nested
    events subtracted from the ones that hold them."""
    out: dict[str, float] = {}
    stack: list[list] = []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        out[name] = out.get(name, 0.0) + (e - s)
        if stack:
            parent = stack[-1]
            out[parent[0]] = out.get(parent[0], 0.0) - (
                min(e, parent[2]) - s)
        stack.append([name, s, e])
    return out


def load(path: str):
    """(device op events per chip, host events) of one `.xplane.pb`
    (gzipped where the name ends in `.gz`):
    {plane: [(name, start_ns, end_ns)]}, [(name, start_ns, end_ns)]."""
    import gzip

    import jax

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    dev, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
    return dev, host


def reduce(dev: dict, host: list, spans=(), anchor_ns: float = 0.0) -> dict:
    """The window's busy and window seconds, kernel seconds, top device
    ops and top idle gaps.  `spans` are program spans (name, start_ns,
    end_ns) on the host's `perf_counter_ns` clock, which read
    `anchor_ns` as the window's annotation opened."""
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW!r} annotation, got "
                         f"{len(wins)}")
    lo, hi = wins[0]
    if not dev:
        raise ValueError("the trace holds no TPU op events")
    busy_ns, kernels, ops, gaps = 0.0, {}, {}, {}
    shift = lo - anchor_ns
    spans = sorted(((n, s + shift, e + shift) for n, s, e in spans),
                   key=lambda x: x[1])
    for events in dev.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if e > lo and s < hi]
        merged = union((s, e) for _, s, e in inside)
        busy_ns += sum(e - s for s, e in merged)
        for n, s, e in inside:
            k = kernel_of(instruction(n))
            if k:
                kernels[k] = kernels.get(k, 0.0) + (e - s)
        for n, t in self_times(
                [(instruction(n), s, e) for n, s, e in inside]).items():
            ops[n] = ops.get(n, 0.0) + t
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                name = holder(spans, (s + e) / 2)
                gaps[name] = gaps.get(name, 0.0) + (e - s)
    chips = len(dev)
    top = lambda d: [[k, v / chips / 1e9] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": busy_ns / chips / 1e9, "window_s": (hi - lo) / 1e9,
            "kernel_s": {k: v / chips / 1e9 for k, v in kernels.items()},
            "device_ops": top(ops), "idle_gaps": top(gaps)}


def holder(spans, t: float) -> str:
    """Name of the innermost span open at `t` (the latest-started one
    that has not ended)."""
    name = "outside the engine"
    for n, s, e in spans:
        if s > t:
            break
        if e > t:
            name = n
    return name


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {len(paths)}")
    return paths[0]


def reduce_dir(trace_dir: str, spans=(), anchor_ns: float = 0.0) -> dict:
    dev, host = load(find(trace_dir))
    return reduce(dev, host, spans, anchor_ns)
