"""sched/ scheduler and batcher: mean per traced query of the time it was
held, `sched.wait` + `batch.hold`, in ms."""


def read(ctx):
    return ctx.span_mean_ms("sched.wait", "batch.hold")
