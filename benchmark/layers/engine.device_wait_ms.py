"""parallel/engine.py dispatch guard: mean, over the traced queries that
have any, of their `engine.device_wait` spans, in ms: from handing a
program to the runtime to its answer on the host (launch latency, device
time, transfer). No Python of the program runs in it."""


def read(ctx):
    return ctx.span_mean_ms("engine.device_wait")
