"""parallel/engine.py on a mesh: device programs launched over more than
one device (`engine_cache.mesh_launches`) per query the server admitted
over the window. 0.0 on a one-device engine; None where the program has
no such counter."""


def read(ctx):
    launches = ctx.delta("engine_cache", "mesh_launches")
    answers = ctx.delta("scheduler", "admitted")
    if launches is None or not answers:
        return None
    return launches / answers
