"""parallel/engine.py fingerprint cache: walks over a view's fragments to
ask "is it stale?" (`fp_walks`) per query the server admitted over the
window. None where the program has no such counter."""


def read(ctx):
    walks = ctx.delta("engine_cache", "fp_walks")
    answers = ctx.delta("scheduler", "admitted")
    if walks is None or not answers:
        return None
    return walks / answers
