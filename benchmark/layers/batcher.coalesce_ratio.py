"""sched/batcher.py: dispatches enqueued per fused launch over the window
(1 means nothing coalesced)."""


def read(ctx):
    launches = ctx.delta("batcher", "launches")
    enqueued = ctx.delta("batcher", "enqueued")
    if not launches or enqueued is None:
        return None
    return enqueued / launches
