"""Share of the device's need that the host's span plan foresaw, in the
4-clique cell: the `predicted` over the `needed` of the window's
`executor.dispatch` spans, each summed (core/executor.py
`Matcher.predicted`).  1.0 where the plan is exact; below 1 where the
device found frontier rows the plan did not count.  None in an untraced
run, or on a program whose dispatch spans carry no `predicted`."""


def read(ctx):
    if ctx.spans is None:
        return None
    disp = [s["attrs"] for s in ctx.spans if s["name"] == "executor.dispatch"
            and "predicted" in s["attrs"]]
    needed = sum(a["needed"] for a in disp)
    if not needed:
        return None
    return sum(a["predicted"] for a in disp) / needed
