"""server/handler.py: mean per traced query of the self time of its root
span `request`, in ms: what no stage below accounts for (HTTP framing,
JSON, result encoding). A program without span trees has no `self_ms`."""


def read(ctx):
    mine = [s["self_ms"] for t in ctx.traces for s in t.get("spans", ())
            if s["name"] == "request" and "self_ms" in s]
    return sum(mine) / len(mine) if mine else None
