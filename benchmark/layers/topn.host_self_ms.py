"""executor.py ladder: mean, over the traced queries that have any, of the
summed self time of their `topn.rank` and `topn.replay` spans, in ms: the
host half of a batched TopN (the shards' rankings laid side by side, the
candidate masks and their union; then the row lookup, the replay of the
heap selection, totals and pairs) without the device programs between
them, which are `topn.chunk`. Work plus waits for the interpreter lock
(PERF.md, PR 34). None where no query has such a span."""

NAMES = ("topn.rank", "topn.replay")


def read(ctx):
    totals = []
    for t in ctx.traces:
        mine = [s["self_ms"] for s in t.get("spans", ())
                if s["name"] in NAMES and "self_ms" in s]
        if mine:
            totals.append(sum(mine))
    return sum(totals) / len(totals) if totals else None
