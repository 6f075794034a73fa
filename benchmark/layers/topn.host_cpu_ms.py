"""executor.py ladder: as `topn.host_self_ms`, but CPU: mean, over the
traced queries that have any, of the summed `self_cpu_ms` of their
`topn.rank` and `topn.replay` spans, in ms: how much of the host half of
a batched TopN is work of its thread, the rest of `topn.host_self_ms`
being waits for the interpreter lock. None where no such span has a
`self_cpu_ms`."""

NAMES = ("topn.rank", "topn.replay")


def read(ctx):
    totals = []
    for t in ctx.traces:
        mine = [s["self_cpu_ms"] for s in t.get("spans", ())
                if s["name"] in NAMES and "self_cpu_ms" in s]
        if mine:
            totals.append(sum(mine))
    return sum(totals) / len(totals) if totals else None
