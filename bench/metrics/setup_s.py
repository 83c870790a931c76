"""Set-up: from process start to the window's first due request (graph
generation, server start, plan search, compiling or loading every
program from the cache, the warm-up counts), less the seconds of the
host triangle count that a restarted server reads from its plan
store."""


def read(ctx):
    return ctx.setup_s
