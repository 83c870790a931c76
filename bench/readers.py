"""What the per-cell metric files of `bench/metrics/` read.

A metric file names one metric of BENCHMARK.json and binds `read` to
one of these functions, so that a quantity measured in cells that report
different end-to-end metrics is computed once.  Each takes the run's
context (`run.run_cell`): the mix, the window's client records, the
set-up seconds, and in a traced run the program's spans and the reduced
profiler trace.  A reader with nothing to read returns None.
"""


def answered(ctx) -> int:
    return sum(r.result is not None for r in ctx.win.records)


def seconds_per_count(ctx):
    """From the first submit to the last answer, over the counts
    answered.  The closed loop sends whole cycles of the mix, the last
    one started before the deadline."""
    if ctx.mix["kind"] != "closed":
        return None
    done = [r.done for r in ctx.win.records if r.result is not None]
    return (max(done) - ctx.win.t0) / len(done) if done else None


def dispatches_per_count(ctx):
    """Kernel dispatches per answered count: the `dispatches` of every
    `executor.count` span of the window (core/executor.py
    `Matcher.count_partial`), over the counts answered."""
    if ctx.spans is None or not answered(ctx):
        return None
    total = sum(s["attrs"].get("dispatches", 0) for s in ctx.spans
                if s["name"] == "executor.count")
    return total / answered(ctx)


def overflow_share(ctx):
    """Share of the window's `executor.dispatch` spans whose chunk needed
    more frontier rows than its capacity, so that it was bisected (or
    escalated) and dispatched again."""
    if ctx.spans is None:
        return None
    disp = [s["attrs"] for s in ctx.spans if s["name"] == "executor.dispatch"]
    if not disp:
        return None
    return sum(a["needed"] > a["capacity"] for a in disp) / len(disp)


def level_expand_ms(ctx):
    """Device milliseconds of the fused level-expansion kernel
    (kernels/intersect.py, `pallas_call(name="level_expand")`) per
    answered count, from the profiler trace of the window."""
    if ctx.trace is None or not ctx.trace["kernel_s"].get("level_expand") \
            or not answered(ctx):
        return None
    return ctx.trace["kernel_s"]["level_expand"] * 1e3 / answered(ctx)


def idle_share(ctx):
    """Share of the traced window in which no operation ran on the
    device: 1 - (union of device op intervals) / (window)."""
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return 1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"]
