"""parallel/engine.py stack cache: mean, over the traced queries that have
any, of the summed self time of their `engine.stack` spans, in ms: get-or-
build of the stacked planes, less the `gather` spans under it."""


def read(ctx):
    totals = []
    for t in ctx.traces:
        mine = [s["self_ms"] for s in t.get("spans", ())
                if s["name"] == "engine.stack" and "self_ms" in s]
        if mine:
            totals.append(sum(mine))
    return sum(totals) / len(totals) if totals else None
