"""From a JAX profiler trace to device busy and idle time, device time by
operation name, and the longest idle gaps with what the host ran in them.

Run as a process of its own, `python xplane.py <profile dir>`, once the
server child has exited: it only parses a file, and is started with
JAX_PLATFORMS=cpu so that it never asks for the chip. Prints one JSON
object. The arithmetic (`reduce`) works on plain event tuples, so the
tests check it on a small recorded trace without the profiler.
"""

import glob
import json
import os
import re
import sys

# Lines of a device plane that repeat what "XLA Ops" already holds.
_SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                  "Framework Name Scope", "Source code")
HOST_EVENTS_MAX = 2_000_000


def load(path):
    """(device events, host events) of one .xplane.pb: tuples of
    (plane, line, name, start_ns, dur_ns)."""
    from jax.profiler import ProfileData

    device, host = [], []
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith("/device:") \
            and "CPU" not in plane.name
        if not is_device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if is_device and line.name in _SUMMARY_LINES:
                continue
            out = device if is_device else host
            for ev in line.events:
                if out is host and len(host) >= HOST_EVENTS_MAX:
                    break
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return device, host


def union_ns(intervals):
    """Length of the union of (start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps_ns(intervals, lo, hi):
    """The idle stretches of [lo, hi] that no interval covers."""
    out, end = [], lo
    for s, e in sorted(intervals):
        if s > end:
            out.append((end, min(s, hi)))
        end = max(end, e)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return out


# A thread parked in one of these says nothing of what the host was doing.
_PARKED = re.compile(r"(wait|sleep|acquire|select|poll|recv|recv_into|accept"
                     r"|readinto|readline|get)$")


def _host_name(host, g0, g1):
    """What the host was doing in an idle gap: the shortest host event that
    covers at least half of it (the deepest frame that still spans it),
    threads that were only parked left aside; failing that, the event that
    overlaps it most."""
    half = 0.5 * (g1 - g0)
    best = most = None
    for _, line, name, s, d in host:
        overlap = min(s + d, g1) - max(s, g0)
        if overlap <= 0:
            continue
        if most is None or overlap > most[0]:
            most = (overlap, line, name)
        if overlap >= half and not _PARKED.search(name) \
                and (best is None or d < best[0]):
            best = (d, line, name)
    pick = best or most
    return f"{pick[1]}:{pick[2]}"[:120] if pick else "unattributed"


def short_name(name):
    """An XLA op's event name is its whole HLO line; the op's own name,
    without its number, is what stays the same from program to program:
    `%batched_gather_expr_count.1 = s32[...] custom-call(...)` ->
    `batched_gather_expr_count`."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def reduce(device, host=(), top=10):
    """busy_s: union of the device operations' intervals, averaged over the
    device planes. window_s: from the first device event to the end of the
    last, both the trace's own. The host events are no measure of it: the
    Python tracer reports frames with the start they had long before the
    capture, and goes on reporting through the seconds that stopping it
    takes, when the device is no longer traced. by_name: device seconds by operation
    name, summed over planes. idle_gaps: the longest stretches of the
    window in which no operation ran on the first device, each with what
    the host was doing in it."""
    if not device:
        return None
    planes = sorted({e[0] for e in device})
    lo = min(e[3] for e in device)
    hi = max(e[3] + e[4] for e in device)
    busy = 0.0
    by_name = {}
    for p in planes:
        mine = [e for e in device if e[0] == p]
        busy += union_ns([(e[3], e[3] + e[4]) for e in mine])
        for _, _, name, _, d in mine:
            name = short_name(name)
            by_name[name] = by_name.get(name, 0.0) + d
    first = [(e[3], e[3] + e[4]) for e in device if e[0] == planes[0]]
    gaps = sorted(gaps_ns(first, lo, hi), key=lambda g: g[0] - g[1])[:top]
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy / len(planes) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_planes": len(planes),
        "device_events": len(device),
        "by_name": {k: v / 1e9 for k, v in ops},
        "device_ops": [[k, v / 1e9] for k, v in ops[:top]],
        "idle_gaps": [[_host_name(host, a, b), (b - a) / 1e9]
                      for a, b in gaps],
    }


def sample(device, host, stretch_ns=500e6, most=1500):
    """A short stretch from the middle of a capture, small enough to keep
    as a recorded trace for the tests: the device events that start in it
    and the host events that overlap it."""
    if not device:
        return {"device": [], "host": []}
    starts = sorted(e[3] for e in device)
    lo = starts[len(starts) // 2]
    hi = lo + stretch_ns
    dev = [e for e in device if lo <= e[3] < hi][:most]
    hst = [e for e in host if e[3] < hi and e[3] + e[4] > lo
           and e[4] < 10 * stretch_ns][:most]
    return {"device": dev, "host": hst}


def reduce_dir(profile_dir, sample_out=None):
    paths = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    device, host = load(paths[-1])
    if sample_out:
        with open(sample_out, "w") as f:
            json.dump(sample(device, host), f)
    out = reduce(device, host)
    if out is not None:
        out["xplane_bytes"] = os.path.getsize(paths[-1])
        out["host_events"] = len(host)
    return out


if __name__ == "__main__":
    print(json.dumps(reduce_dir(*sys.argv[1:3])))
