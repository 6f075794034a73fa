"""server/handler.py: mean per traced query of the `cpu_ms` of its root
span `request`, in ms: the CPU time of the serving thread for one answer,
children included. Under one interpreter lock it cannot pass
1 / `ops_per_s` by much; what lies between it and `host.cpu_ms_per_op` is
CPU of other threads. None where the spans carry no `cpu_ms`."""


def read(ctx):
    mine = [s["cpu_ms"] for t in ctx.traces for s in t.get("spans", ())
            if s["name"] == "request" and "cpu_ms" in s]
    return sum(mine) / len(mine) if mine else None
