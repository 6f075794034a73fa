"""ops/ kernels: the filtered TopN's share of the HBM roofline, in %, from
the kernel probe's profiler capture, reckoned as `kernel.count_roofline`
reckons the batched Count's.

A wave is `width` concurrent TopNs that each rank `leaves` - 1 candidate
rows under one filter row; one Set on a ranked row before each wave makes
every memo entry stale, so whatever programs serve the wave read each of
those planes from HBM at least once: width * leaves * shards * 131,072
bytes (1.0 GiB at 8,209 planes of one shard, 1.31 ms at 819 GB/s). The
share is the least time for the waves that lie wholly inside the capture
at the chip's published HBM bandwidth over the time the device was busy in
the capture. It names no kernel; it counts as busy whatever else the
device did for the waves (the refetch of the winners, a stale chunk's
scatter), so it reads low and never over what the device did."""

ROW_BYTES = (1 << 20) // 8


def read(ctx):
    probe = ctx.probe
    if not probe or not probe.get("profile"):
        return None
    peak = ctx.peaks.get(ctx.device["kind"])
    busy = probe["profile"]["busy_s"]
    if peak is None or not busy or not probe["waves_inside"]:
        return None
    least_bytes = (probe["waves_inside"] * probe["width"] * probe["leaves"]
                   * ctx.cfg["shards"] * ROW_BYTES)
    return 100.0 * least_bytes / peak["hbm_bytes_per_s"] / busy
