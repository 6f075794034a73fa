"""Per-fragment TopN row-count caches.

Behavioral port of the reference's cache.go: rankCache (sorted, trimmed),
lruCache, nopCache, plus the Pair/Pairs merge math used by the cross-shard
TopN reduce (cache.go:315-427). Where the reference re-sorts a rank cache
at most every 10 seconds, this one re-ranks after every write: a ranking
older than a write would be a different answer.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..constants import DEFAULT_CACHE_SIZE


@dataclass(frozen=True)
class Pair:
    id: int
    count: int
    key: str = ""

    def to_dict(self):
        d = {"id": self.id, "count": self.count}
        if self.key:
            d["key"] = self.key
        return d


def add_pairs(a: List[Pair], b: List[Pair]) -> List[Pair]:
    """Merge pair lists summing counts per id (reference cache.go:370 Pairs.Add)."""
    counts: Dict[int, int] = {}
    for p in a:
        counts[p.id] = counts.get(p.id, 0) + p.count
    for p in b:
        counts[p.id] = counts.get(p.id, 0) + p.count
    return [Pair(id=i, count=c) for i, c in counts.items()]


def sort_pairs(pairs: List[Pair]) -> List[Pair]:
    """Descending by count; ties broken by ascending id for determinism."""
    return sorted(pairs, key=lambda p: (-p.count, p.id))


RankArrays = Tuple[np.ndarray, np.ndarray]  # (ids, counts), int64, rank order


def rank_entries(entries: Dict[int, int]) -> RankArrays:
    """{row: count} as a ranking in sort_pairs' order (count down, then id
    up), for the executor's batched TopN runners, which work on the shard
    axis and read no Pair. Built in numpy, with no Pair and no tuple a
    row. `entries` is read in one C-level copy, so a writer that inserts
    mid-rank (holding the fragment mutex, not ours) cannot make it
    raise."""
    snap = entries.copy()
    ids = np.fromiter(snap.keys(), np.int64, len(snap))
    counts = np.fromiter(snap.values(), np.int64, len(snap))
    order = np.lexsort((ids, -counts))
    return ids[order], counts[order]


def pairs_of(arrays: RankArrays) -> List[Pair]:
    ids, counts = arrays
    return [Pair(id=i, count=c) for i, c in zip(ids.tolist(), counts.tolist())]


_NO_RANKING: RankArrays = rank_entries({})

# Rank-cache rebuilds and the rows they ranked, process-wide (/debug/vars
# `executor`), and each thread's own rebuilds (topn.rank's `rebuilt` tag).
rank_rebuilds = 0
rank_rows_sorted = 0
_this_thread = threading.local()


def thread_rank_rebuilds() -> int:
    return getattr(_this_thread, "rebuilds", 0)


class RankCache:
    """Keeps the top `max_entries` (row, count) pairs, ranked lazily.

    Lock-free: a write bumps `_gen` and drops the ranking, and a rebuild
    publishes only if no write came after its snapshot, so a reader sees
    the ranking of the last write, or ranks the rows itself."""

    def __init__(self, max_entries: int = DEFAULT_CACHE_SIZE):
        self.max_entries = max_entries
        self.entries: Dict[int, int] = {}
        self._gen = 0
        self._arrays: Optional[RankArrays] = None
        # (the arrays they were made from, top()'s Pairs)
        self._pairs: Optional[Tuple[RankArrays, List[Pair]]] = None

    def add(self, row_id: int, n: int) -> None:
        if n == 0:
            self.entries.pop(row_id, None)
        else:
            self.entries[row_id] = n
        self._gen += 1  # after the write: a rebuild of this generation saw it
        self._arrays = self._pairs = None

    bulk_add = add

    def get(self, row_id: int) -> int:
        return self.entries.get(row_id, 0)

    def ids(self) -> List[int]:
        return sorted(list(self.entries))

    def __len__(self) -> int:
        return len(self.entries)

    def invalidate(self) -> RankArrays:
        """Rank the rows now, trimmed to `max_entries`, and return the
        ranking. It is kept for later readers only if no write came
        between the snapshot and here; the check and the stores below make
        no call, so no other thread runs between them."""
        global rank_rebuilds, rank_rows_sorted
        gen = self._gen
        ids, counts = rank_entries(self.entries)
        rank_rebuilds += 1
        rank_rows_sorted += len(ids)
        _this_thread.rebuilds = thread_rank_rebuilds() + 1
        kept = None
        if len(ids) > self.max_entries:
            ids, counts = ids[: self.max_entries], counts[: self.max_entries]
            kept = dict(zip(ids.tolist(), counts.tolist()))
        arrays = (ids, counts)
        if self._gen == gen:
            if kept is not None:
                self.entries = kept
            self._arrays = arrays
        return arrays

    def top(self) -> List[Pair]:
        arrays = self.top_arrays()
        pairs = self._pairs
        if pairs is None or pairs[0] is not arrays:
            pairs = self._pairs = (arrays, pairs_of(arrays))
        return list(pairs[1])

    def top_arrays(self) -> RankArrays:
        """top() as (ids, counts) arrays, shared and not to be written."""
        arrays = self._arrays
        return arrays if arrays is not None else self.invalidate()

    def clear(self) -> None:
        self.entries.clear()
        self._gen += 1
        self._arrays = self._pairs = None


class LRUCache:
    """LRU row-count cache (reference cache.go:58-130, lru/lru.go)."""

    def __init__(self, max_entries: int = DEFAULT_CACHE_SIZE):
        self.max_entries = max_entries
        self.entries: OrderedDict[int, int] = OrderedDict()

    def add(self, row_id: int, n: int) -> None:
        if row_id in self.entries:
            self.entries.move_to_end(row_id)
        self.entries[row_id] = n
        if len(self.entries) > self.max_entries:
            self.entries.popitem(last=False)

    bulk_add = add

    def get(self, row_id: int) -> int:
        n = self.entries.get(row_id, 0)
        if row_id in self.entries:
            self.entries.move_to_end(row_id)
        return n

    def ids(self) -> List[int]:
        return sorted(list(self.entries))

    def __len__(self) -> int:
        return len(self.entries)

    def invalidate(self) -> None:
        pass

    def top(self) -> List[Pair]:
        return pairs_of(self.top_arrays())

    def top_arrays(self) -> RankArrays:
        return rank_entries(self.entries)

    def clear(self) -> None:
        self.entries.clear()


class NopCache:
    def add(self, row_id: int, n: int) -> None:
        pass

    bulk_add = add

    def get(self, row_id: int) -> int:
        return 0

    def ids(self) -> List[int]:
        return []

    def __len__(self) -> int:
        return 0

    def invalidate(self) -> None:
        pass

    def top(self) -> List[Pair]:
        return []

    def top_arrays(self) -> RankArrays:
        return _NO_RANKING

    def clear(self) -> None:
        pass


def new_cache(cache_type: str, size: int):
    from ..constants import CACHE_TYPE_LRU, CACHE_TYPE_NONE, CACHE_TYPE_RANKED
    from ..errors import InvalidCacheTypeError

    if cache_type == CACHE_TYPE_RANKED:
        return RankCache(size)
    if cache_type == CACHE_TYPE_LRU:
        return LRUCache(size)
    if cache_type == CACHE_TYPE_NONE:
        return NopCache()
    raise InvalidCacheTypeError(cache_type)
