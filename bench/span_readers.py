"""What the metric files that read the program's in-program spans bind.

Like `readers.py`, each takes the run's context (`run.run_cell`) and
returns None where it has nothing to read: in an untraced run, and on a
program whose spans lack what it reads (`entries_*` on
`executor.dispatch`, `rpc.request` spans, `tickets` on
`engine.execute`).
"""


def host_turn_ms(ctx):
    """Device-idle ms per dispatch in the outer loop's host turn: the
    window's idle gaps named by an `executor.*` span (the dispatch's
    children `executor.roots` / `executor.launch` / `executor.readback`,
    the dispatch itself, or `executor.count` between dispatches), over
    the window's `executor.dispatch` spans.  The reduced trace keeps the
    ten largest holders, which hold the `executor.*` ones."""
    if ctx.trace is None or ctx.spans is None:
        return None
    n = sum(s["name"] == "executor.dispatch" for s in ctx.spans)
    if not n:
        return None
    idle_s = sum(seconds for name, seconds in ctx.trace["idle_gaps"]
                 if name.startswith("executor."))
    return idle_s * 1e3 / n


def window_fill(ctx):
    """Share of the candidate-window slots the window's dispatches
    streamed that held a real neighbour: the `entries_valid` the device
    counted over the `entries_streamed` (cap x width of every launched
    bucket window) of the window's `executor.dispatch` spans."""
    if ctx.spans is None:
        return None
    disp = [s["attrs"] for s in ctx.spans if s["name"] == "executor.dispatch"
            and "entries_streamed" in s["attrs"]]
    streamed = sum(a["entries_streamed"] for a in disp)
    if not streamed:
        return None
    return sum(a["entries_valid"] for a in disp) / streamed


def front_door_ms(ctx):
    """Mean ms per answered request outside the engine's execution: each
    `rpc.request` span (submit frame handled to result reply written)
    less the durations of the `engine.execute` spans whose `tickets`
    list its ticket.  It holds the framing, admission, planning and the
    wait for a round."""
    if ctx.spans is None:
        return None
    engine_ns: dict = {}
    for s in ctx.spans:
        if s["name"] == "engine.execute":
            for t in s["attrs"].get("tickets", ()):
                engine_ns[t] = engine_ns.get(t, 0) + s["dur_ns"]
    if not engine_ns:
        return None
    ms = [(s["dur_ns"] - engine_ns.get(s["attrs"]["ticket"], 0)) / 1e6
          for s in ctx.spans
          if s["name"] == "rpc.request" and s["attrs"].get("ok")]
    return sum(ms) / len(ms) if ms else None
