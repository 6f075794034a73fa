"""host process: seconds the cyclic collector ran (`host.gc_s`, between
the `start` and `stop` of each collection, on whichever thread set it off)
per query the server admitted over the window, in ms. None where the
program has no such group."""


def read(ctx):
    gc_s = ctx.delta("host", "gc_s")
    answers = ctx.delta("scheduler", "admitted")
    if gc_s is None or not answers:
        return None
    return 1000.0 * gc_s / answers
