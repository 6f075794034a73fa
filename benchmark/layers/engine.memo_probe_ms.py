"""parallel/engine.py result memo: mean per traced query of the summed self
time of its `engine.memo_probe` spans, in ms: the per-fragment fingerprint
walk and the lookup."""


def read(ctx):
    totals = []
    for t in ctx.traces:
        mine = [s["self_ms"] for s in t.get("spans", ())
                if s["name"] == "engine.memo_probe" and "self_ms" in s]
        if mine:
            totals.append(sum(mine))
    return sum(totals) / len(totals) if totals else None
