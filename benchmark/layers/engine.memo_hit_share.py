"""parallel/engine.py result memo: hits over hits + misses in the window,
in %."""


def read(ctx):
    hits = ctx.delta("engine_cache", "memo_hits")
    misses = ctx.delta("engine_cache", "memo_misses")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
