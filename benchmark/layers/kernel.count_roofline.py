"""ops/ kernels: the batched Count's share of the HBM roofline, in %, from
the kernel probe's profiler capture.

A wave is `width` concurrent Counts over `leaves` planes each, every plane
another one. Whatever program serves it reads each plane from HBM at least
once: width * leaves * shards * 131,072 bytes (a shard's row is 2^20 bits).
The share is the least time for all waves at the chip's published HBM
bandwidth over the time the device was busy inside the capture. It names
no kernel, so it still reads after the Pallas kernel is replaced; it
counts as busy whatever else the device did for the waves (stacking the
planes, delta refreshes), which is the point."""

ROW_BYTES = (1 << 20) // 8


def least_bytes(probe, shards):
    """Of the waves that lie wholly inside the capture (`waves_inside`): a
    wave that the capture's end cut off adds to the busy time only, so the
    share reads low and never over what the device did."""
    return (probe["waves_inside"] * probe["width"] * probe["leaves"]
            * shards * ROW_BYTES)


def read(ctx):
    probe = ctx.probe
    if not probe or not probe.get("profile"):
        return None
    peak = ctx.peaks.get(ctx.device["kind"])
    busy = probe["profile"]["busy_s"]
    if peak is None or not busy or not probe["waves_inside"]:
        return None
    least_s = least_bytes(probe, ctx.cfg["shards"]) / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / busy
