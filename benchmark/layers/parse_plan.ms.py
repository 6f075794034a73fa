"""pql/ and plan/: mean per traced query of its `parse` and `plan.compile`
spans, in ms (host clock, host work)."""


def read(ctx):
    return ctx.span_mean_ms("parse", "plan.compile")
