"""Shard placement math (port of /root/reference/cluster.go:776-857).

Kept byte-identical to the reference: shard -> partition via FNV-1a 64 over
(index name + big-endian shard), partition -> node via jump consistent
hashing, replicas on consecutive ring nodes. The same math places shards on
TPU mesh devices (parallel/mesh.py) so single-host and multi-host layouts
agree.
"""

from __future__ import annotations

import struct

from ..constants import DEFAULT_PARTITION_N

_MASK64 = (1 << 64) - 1


def fnv64a(data: bytes) -> int:
    h = 14695981039346656037
    for b in data:
        h ^= b
        h = (h * 1099511628211) & _MASK64
    return h


def shard_hash(index: str, shard: int) -> int:
    """FNV-1a 64 over the index name and the big-endian shard: the pure
    half of `partition`, which Cluster keeps per (index, shard)."""
    return fnv64a(index.encode() + struct.pack(">Q", shard))


def partition(index: str, shard: int, partition_n: int = DEFAULT_PARTITION_N) -> int:
    return shard_hash(index, shard) % partition_n


def jump_hash(key: int, n: int) -> int:
    """Jump consistent hash (cluster.go:846-857 jmphasher)."""
    b, j = -1, 0
    while j < n:
        b = j
        key = (key * 2862933555777941757 + 1) & _MASK64
        j = int(float(b + 1) * (float(1 << 31) / float((key >> 33) + 1)))
    return b


# Entries a memo of one of the pure functions here may hold before it is
# emptied and starts again: placement asks about partition_n keys per
# node count and one hash per shard of an index, so a deployment stays
# far below it and a stream of wild shard numbers cannot grow it.
MEMO_ENTRIES = 1 << 16


class JmpHasher:
    """jump_hash, kept per (key, n): a pure function, so what is kept
    never goes stale and needs no invalidation."""

    def __init__(self):
        self._kept = {}

    def hash(self, key: int, n: int) -> int:
        b = self._kept.get((key, n))
        if b is None:
            if len(self._kept) >= MEMO_ENTRIES:
                self._kept.clear()
            b = self._kept[(key, n)] = jump_hash(key, n)
        return b


class ModHasher:
    """Deterministic placement for tests (reference test/cluster.go:18)."""

    def hash(self, key: int, n: int) -> int:
        return key % n if n else 0
