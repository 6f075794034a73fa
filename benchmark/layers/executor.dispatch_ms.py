"""executor.py ladder: mean per traced query of its `device.dispatch`
spans, in ms. Host clock around the device call: it includes the wait for
the device and for whoever holds it, so it is no device time."""


def read(ctx):
    return ctx.span_mean_ms("device.dispatch")
