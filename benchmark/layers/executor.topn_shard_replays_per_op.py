"""executor.py ladder: shards that a batched TopN runner handed one at a
time to the per-shard rung (`executor.topn_shard_replays`) per query the
server admitted over the window. 0.0 while the batched runners answer on
arrays; over 0 when a runner met a DeviceDispatchError under a src the host
evaluator cannot serve. None where the program has no such counter."""


def read(ctx):
    replays = ctx.delta("executor", "topn_shard_replays")
    answers = ctx.delta("scheduler", "admitted")
    if replays is None or not answers:
        return None
    return replays / answers
