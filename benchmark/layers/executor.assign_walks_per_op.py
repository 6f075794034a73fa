"""executor.py ladder: placements worked out shard by shard for a shard
list (`executor.assign_walks`) per query the server admitted over the
window; the other assignments were reads of what an earlier one kept.
None where the program has no such counter."""


def read(ctx):
    walks = ctx.delta("executor", "assign_walks")
    answers = ctx.delta("scheduler", "admitted")
    if walks is None or not answers:
        return None
    return walks / answers
