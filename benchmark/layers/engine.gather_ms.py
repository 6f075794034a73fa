"""parallel/engine.py plane caches: mean per traced query of its `gather`
spans, in ms: making the leaf planes resident and stacking them."""


def read(ctx):
    return ctx.span_mean_ms("gather")
