"""Device, on a mesh: bytes in use on the fullest chip over those on the
emptiest, after the window, as each chip's allocator reports them in
/debug/vars (`device.devices[].bytes_in_use`). 1.0 is even; the shard
axis is split evenly, so what lifts it is what lives on one chip alone.
None where the backend reports no bytes (the CPU's)."""


def read(ctx):
    devices = (ctx.after or {}).get("device", {}).get("devices") or ()
    in_use = [d.get("bytes_in_use") for d in devices]
    if not in_use or not all(in_use):
        return None
    return max(in_use) / min(in_use)
