"""Load a drawn index into a live server.

Fields go through `POST /internal/fragment/data` (the route by which a
node receives a fragment on resize or restore) with a roaring file as the
body. A fragment position
is row * 2^20 + column-in-shard and a container's key is position >> 16,
so row r owns keys 16r..16r+15.

A set field's fragment is its view `standard`. An int field (options
`{"type": "int", "min": .., "max": ..}`) is one fragment a shard of the
view `bsig_<field>`, laid out as upstream's bsiGroup lays it: with `depth`
the smallest d for which max - min < 2^d, row i holds bit i of
value - min for i < depth, and row `depth` holds every column that has a
value (not null).
"""

import json
import struct

import numpy as np

from client import in_threads
from generate import SHARD_WIDTH

COOKIE = 12348          # magic number, storage version 0
ARRAY, BITMAP = 1, 2
ARRAY_MAX = 4096


def roaring_body(pos):
    """The route's body for one fragment: u64 length, then a roaring file
    of the sorted, distinct fragment positions `pos`. A container of at
    most 4096 bits is an array of its low 16 bits, a larger one a bitmap."""
    pos = np.asarray(pos, dtype=np.uint64)
    low = (pos & np.uint64(0xFFFF)).astype("<u2")
    keys, start, count = np.unique(pos >> np.uint64(16), return_index=True,
                                   return_counts=True)
    headers, payload = [], []
    for key, lo, n in zip(keys.tolist(), start.tolist(), count.tolist()):
        chunk = low[lo:lo + n]
        if n <= ARRAY_MAX:
            headers.append(struct.pack("<QHH", key, ARRAY, n - 1))
            payload.append(chunk.tobytes())
        else:
            bits = np.zeros(1 << 16, dtype=np.uint8)
            bits[chunk] = 1
            headers.append(struct.pack("<QHH", key, BITMAP, n - 1))
            payload.append(np.packbits(bits, bitorder="little").tobytes())
    out = [struct.pack("<II", COOKIE, len(keys))] + headers
    off = 8 + 16 * len(keys)
    for p in payload:
        out.append(struct.pack("<I", off))
        off += len(p)
    file = b"".join(out + payload)
    return struct.pack("<Q", len(file)) + file


def fragment_positions(data, name, shard):
    """Sorted fragment positions of one set field in one shard."""
    lo, hi = shard * SHARD_WIDTH, (shard + 1) * SHARD_WIDTH
    parts = []
    for r, cols in enumerate(data.cols[name]):
        a, b = np.searchsorted(cols, [lo, hi])
        parts.append((np.uint64(r) << np.uint64(20))
                     | (cols[a:b] - np.uint32(lo)).astype(np.uint64))
    return np.concatenate(parts)


def bsi_depth(options):
    """Bit planes of an int field's values, as upstream's bsiGroup reckons
    them: the smallest d with max - min < 2^d."""
    return (options["max"] - options["min"]).bit_length()


def bsi_positions(data, field, shard):
    """Sorted fragment positions of one int field in one shard."""
    cols, values = data.values[field["name"]]
    lo, hi = shard * SHARD_WIDTH, (shard + 1) * SHARD_WIDTH
    a, b = np.searchsorted(cols, [lo, hi])
    col = (cols[a:b] - np.uint32(lo)).astype(np.uint64)
    opts, mine = field["options"], values[a:b]
    if mine.size and (mine.min() < opts["min"] or mine.max() > opts["max"]):
        raise ValueError(f"{field['name']}: a value outside its options")
    offset = mine - opts["min"]
    depth = bsi_depth(opts)
    parts = [(np.uint64(i) << np.uint64(20)) | col[(offset >> i) & 1 == 1]
             for i in range(depth)]
    parts.append((np.uint64(depth) << np.uint64(20)) | col)
    return np.concatenate(parts)


def create_schema(srv, cfg):
    index = cfg["index"]
    srv.request("POST", f"/index/{index}", "{}")
    for f in cfg["fields"]:
        body = json.dumps({"options": f["options"]}) if "options" in f \
            else "{}"
        srv.request("POST", f"/index/{index}/field/{f['name']}", body)


def load(srv, cfg, data, threads=4):
    """Post every fragment; returns the bytes sent."""
    index = cfg["index"]
    jobs = [(f, s) for s in range(data.shards) for f in cfg["fields"]]

    def send(job):
        f, s = job
        name = f["name"]
        if f.get("options", {}).get("type") == "int":
            pos, view = bsi_positions(data, f, s), "bsig_" + name
        else:
            pos, view = fragment_positions(data, name, s), "standard"
        body = roaring_body(pos)
        srv.request(
            "POST", f"/internal/fragment/data?index={index}&field={name}"
            f"&view={view}&shard={s}", body)
        return len(body)

    return sum(in_threads(threads, send, jobs))
