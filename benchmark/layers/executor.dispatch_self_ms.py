"""executor.py ladder: mean per traced query of the summed self time of its
`device.dispatch` spans, in ms: the span's length less what the batcher
and the engine did under it (`batch.hold`, `batch.launch`,
`engine.memo_probe`, ...). The ladder's own cost."""


def read(ctx):
    totals = []
    for t in ctx.traces:
        mine = [s["self_ms"] for s in t.get("spans", ())
                if s["name"] == "device.dispatch" and "self_ms" in s]
        if mine:
            totals.append(sum(mine))
    return sum(totals) / len(totals) if totals else None
