"""Load a drawn index into a live server.

Fields go through `POST /internal/fragment/data` (the route by which a
node receives a fragment on resize or restore) with a roaring file as the
body. A fragment position
is row * 2^20 + column-in-shard and a container's key is position >> 16,
so row r owns keys 16r..16r+15.
"""

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


def create_schema(srv, cfg):
    index = cfg["index"]
    srv.request("POST", f"/index/{index}", "{}")
    for f in cfg["fields"]:
        srv.request("POST", f"/index/{index}/field/{f['name']}", "{}")


def load(srv, cfg, data, threads=4):
    """Post every fragment; returns the bytes sent."""
    index = cfg["index"]
    jobs = [(f["name"], s) for s in range(data.shards)
            for f in cfg["fields"]]

    def send(job):
        name, s = job
        body = roaring_body(fragment_positions(data, name, s))
        srv.request(
            "POST", f"/internal/fragment/data?index={index}&field={name}"
            f"&view=standard&shard={s}", body)
        return len(body)

    return sum(in_threads(threads, send, jobs))
