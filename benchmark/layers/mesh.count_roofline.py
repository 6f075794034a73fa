"""ops/ kernels on a mesh: the batched Count's share of the HBM roofline,
in %, reckoned per chip, from the kernel probe's profiler capture.

The probe is `kernel.count_roofline`'s: waves of `width` concurrent Counts
over `leaves` planes each, every plane another one, so whatever serves a
wave reads width * leaves * shards * 131,072 bytes at least once. The
shard axis is split over the chips, and each chip reads its own shard
rows of every plane: the least a chip can do is those bytes over the
device planes of the trace (`device_planes`) at ONE chip's published HBM
bandwidth. That over `busy_s`, which is already the mean of the chips'
busy times, is the share; a chip cannot read its rows in less, so it
cannot pass 100%. (`kernel.count_roofline` divides every shard's bytes
by one chip's bandwidth and would read up to four times that here.)"""

ROW_BYTES = (1 << 20) // 8


def read(ctx):
    probe = ctx.probe
    if not probe or not probe.get("profile"):
        return None
    peak = ctx.peaks.get(ctx.device["kind"])
    profile = probe["profile"]
    busy, chips = profile["busy_s"], profile.get("device_planes")
    if peak is None or not busy or not chips or not probe["waves_inside"]:
        return None
    least_bytes = (probe["waves_inside"] * probe["width"] * probe["leaves"]
                   * ctx.cfg["shards"] * ROW_BYTES)
    return 100.0 * least_bytes / chips / peak["hbm_bytes_per_s"] / busy
