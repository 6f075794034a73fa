"""parallel/engine.py stack cache: bytes of planes copied into a fresh
stack on the device (`restack_bytes`) per fused launch of the batcher over
the window, in MB (10^6 B)."""


def read(ctx):
    restacked = ctx.delta("engine_cache", "restack_bytes")
    launches = ctx.delta("batcher", "launches")
    if restacked is None or not launches:
        return None
    return restacked / launches / 1e6
