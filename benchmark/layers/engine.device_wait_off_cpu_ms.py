"""parallel/engine.py dispatch guard: mean, over the traced queries that
have any, of the summed `dur_ms - cpu_ms` of their `engine.device_wait`
spans, in ms: the part of the waits for the device in which the waiting
thread did not run, which is the device's work plus the wait to have the
interpreter lock back. Beside `device.busy_ms_per_op` that splits a wait
into device and lock, on a mesh too. None where the spans carry no
`cpu_ms`."""


def read(ctx):
    totals = []
    for t in ctx.traces:
        mine = [s["dur_ms"] - s["cpu_ms"] for s in t.get("spans", ())
                if s["name"] == "engine.device_wait" and "cpu_ms" in s]
        if mine:
            totals.append(sum(mine))
    return sum(totals) / len(totals) if totals else None
