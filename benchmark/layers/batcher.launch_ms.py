"""sched/batcher.py: mean, over the traced queries that led a group, of
their `batch.launch` spans, in ms: the fused launch the whole group waited
for. A follower's `batch.hold` beyond the window is its leader's launch."""


def read(ctx):
    return ctx.span_mean_ms("batch.launch")
