"""parallel/engine.py program cache: programs built inside the window.
Warm-up is there to make this 0; each one is a compile, or a load from the
persistent cache, that some request of the window waited for."""


def read(ctx):
    return ctx.delta("engine_cache", "fn_cache_builds")
