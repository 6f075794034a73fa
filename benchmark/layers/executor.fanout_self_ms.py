"""executor.py ladder: mean per traced query of the summed self time of its
`executor.fanout` spans, in ms: the fan-out's own bookkeeping (assigning
shards to their owners, the routing-epoch checks) without the dispatches
and the reduce under it. The one host span that grows with the shards."""


def read(ctx):
    totals = []
    for t in ctx.traces:
        mine = [s["self_ms"] for s in t.get("spans", ())
                if s["name"] == "executor.fanout" and "self_ms" in s]
        if mine:
            totals.append(sum(mine))
    return sum(totals) / len(totals) if totals else None
