"""Device: milliseconds in which an operation ran on the chip for each
request answered, inside the window's profiler capture: the busy share of
the trace (union of device-op intervals over the trace's own first-to-last
device event) over the answers a second that the load generator counted
while the tracer ran. A share of the capture alone would say little: the
profiler's Python tracer slows the host several times over, and the device
idles the more for it; what the device does for one request stays."""


def read(ctx):
    p = ctx.profile
    if not p or not p["window_s"] or not ctx.capture_ops_per_s:
        return None
    return 1000.0 * p["busy_s"] / p["window_s"] / ctx.capture_ops_per_s
