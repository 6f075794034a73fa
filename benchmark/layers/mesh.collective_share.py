"""Device, on a mesh: the share of device-operation time that went to
cross-chip operations, in %, over the window's profiler capture: the
summed lengths of the collectives' events on every chip over the summed
lengths of all device operations (`by_name` of benchmark/xplane.py, where
an operation's name is its HLO name without its number).

What counts as cross-chip: XLA's collectives by their HLO names, each
also in its asynchronous pair of `-start` and `-done` (a `-done` holds the
wait for the other chips): `all-reduce` (the sums over the shard axis that
XLA puts into the Counts' and TopN's programs), `psum` (the same
operation under the name the gather kernel's `shard_map` gives it),
`all-gather`, `reduce-scatter`, `collective-permute`, `all-to-all`. The
first four-chip capture (PR 30) held `all-reduce` and `psum` and none of
the others. 0.0 where a capture holds none of them (one chip); None where
there is no capture."""

import re

CROSS_CHIP = re.compile(r"^(all-reduce|psum|all-gather|reduce-scatter"
                        r"|collective-permute|all-to-all)(-start|-done)?$")


def read(ctx):
    by_name = (ctx.profile or {}).get("by_name")
    total = sum(by_name.values()) if by_name else 0.0
    if not total:
        return None
    across = sum(s for name, s in by_name.items() if CROSS_CHIP.match(name))
    return 100.0 * across / total
