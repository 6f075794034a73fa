"""Device: peak bytes in use on the fullest chip, in GB (10^9), as the
allocator reports it after the window."""


def read(ctx):
    peak = ctx.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
