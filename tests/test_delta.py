"""Delta-refresh device caches: dirty-word journal, scattered HBM updates,
mutation-path bump audit, byte-cache accounting, memo epoch fast path.

The tentpole invariant under test: after ANY sequence of writes, a
delta-refreshed resident plane/stack is byte-identical to a full regather
by a fresh engine — including the fallbacks (journal overflow, bulk
mutations, threshold exceeded), which must degrade to the full path, never
to a partial delta.
"""

import itertools

import numpy as np
import pytest

from pilosa_tpu.constants import SHARD_WIDTH, WORDS_PER_ROW
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.fragment import (
    ALL_ROWS, ChangeJournal, Fragment, WriteEpoch)
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.parallel import EngineConfig
from pilosa_tpu.parallel.engine import (
    DELTA_MIN_UPDATES, Leaf, ShardedQueryEngine)
from pilosa_tpu.pql.parser import parse


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


def unfolded(stack):
    """A resident stack as the (U, S, W) array it stands for: one of fewer
    than 8 shards a device is kept as (U, S*k, W//k)
    (parallel/mesh.py stack_fold)."""
    stack = np.asarray(stack)
    return stack.reshape(stack.shape[0], -1, WORDS_PER_ROW)


def plant(holder, n_shards=4, n_rows=4, per_row=300, seed=7):
    idx = holder.create_index_if_not_exists("i")
    fld = idx.create_field_if_not_exists("f")
    rng = np.random.default_rng(seed)
    for row in range(n_rows):
        cols = []
        for s in range(n_shards):
            local = rng.choice(SHARD_WIDTH, size=per_row, replace=False)
            cols.extend(int(s * SHARD_WIDTH + c) for c in local)
        fld.import_bits([row] * len(cols), cols)
    return idx.field("f")


# ------------------------------------------------------------ journal unit


class TestDirtyJournal:
    def test_point_writes_journal_their_words(self):
        f = Fragment(None, "i", "f", "standard", 0)
        f.open()
        g0 = f.generation
        f.set_bit(1, 64 * 3 + 5)
        f.set_bit(1, 64 * 9)
        f.clear_bit(1, 64 * 3 + 5)
        w = f.dirty_words_since(1, g0)
        assert sorted(w.tolist()) == [3, 9]
        # Another row's cached gen sees the churn but no dirty words.
        assert f.dirty_words_since(2, g0).tolist() == []
        # Fully-caught-up generation: empty delta.
        assert f.dirty_words_since(1, f.generation).tolist() == []

    def test_future_generation_refuses(self):
        f = Fragment(None, "i", "f", "standard", 0)
        f.open()
        # A generation from a previous fragment incarnation (reopen resets
        # the counter) must force a full regather, not an empty delta.
        assert f.dirty_words_since(1, f.generation + 5) is None

    def test_overflow_poisons_then_recovers(self):
        f = Fragment(None, "i", "f", "standard", 0, delta_journal_ops=8)
        f.open()
        g0 = f.generation
        for k in range(12):  # > journal bound
            f.set_bit(1, 64 * k)
        assert f.dirty_words_since(1, g0) is None
        # History since the reset IS complete again.
        g1 = f.generation
        f.set_bit(1, 64 * 50)
        assert f.dirty_words_since(1, g1).tolist() == [50]

    def test_hot_word_churn_does_not_overflow(self):
        """The journal is bounded by UNIQUE dirty words: sustained rewrites
        of the same few words (the mixed ingest+serve regime) must not
        trip the overflow reset and force periodic full regathers."""
        f = Fragment(None, "i", "f", "standard", 0, delta_journal_ops=8)
        f.open()
        g0 = f.generation
        for k in range(100):  # 100 writes, 2 unique words
            f.set_bit(1, 64 * (k % 2) + k % 32)
            f.clear_bit(1, 64 * (k % 2) + k % 32)
        w = f.dirty_words_since(1, g0)
        assert w is not None, "hot-word churn overflowed the journal"
        assert sorted(w.tolist()) == [0, 1]

    def test_bulk_import_poisons_touched_rows_only(self):
        f = Fragment(None, "i", "f", "standard", 0, delta_journal_ops=4)
        f.open()
        g0 = f.generation
        f.set_bit(2, 7)
        # 6 positions > journal bound: row 1 gets poisoned, row 2's
        # history must survive.
        f.bulk_import(np.full(6, 1, np.uint64), np.arange(6, dtype=np.uint64))
        assert f.dirty_words_since(1, g0) is None
        assert f.dirty_words_since(2, g0).tolist() == [0]

    def test_read_from_resets_journal(self):
        import io

        src = Fragment(None, "i", "f", "standard", 0)
        src.open()
        src.set_bit(1, 100)
        buf = io.BytesIO()
        src.write_to(buf)
        dst = Fragment(None, "i", "f", "standard", 0)
        dst.open()
        g0 = dst.generation
        dst.set_bit(1, 200)
        buf.seek(0)
        dst.read_from(buf)
        assert dst.dirty_words_since(1, g0) is None

    def test_row_words64_matches_plane(self):
        f = Fragment(None, "i", "f", "standard", 0)
        f.open()
        rng = np.random.default_rng(3)
        for c in rng.integers(0, SHARD_WIDTH, 200):
            f.set_bit(2, int(c))
        plane64 = f.plane_np(2).view(np.uint64)
        idxs = np.unique(rng.integers(0, SHARD_WIDTH // 64, 32))
        np.testing.assert_array_equal(f.row_words64(2, idxs), plane64[idxs])


# ------------------------------------------------- mutation-path bump audit


def _merge_small(frag):
    # Replica diff below MERGE_BULK_THRESHOLD: per-bit set/clear path.
    rows = np.array([1, 1], dtype=np.uint64)
    cols = np.array([10, 11], dtype=np.uint64)
    frag.merge_block(0, [(rows, cols), (rows, cols)])


def _merge_bulk(frag):
    # Diff above MERGE_BULK_THRESHOLD: storage-level scatter path.
    n = Fragment.MERGE_BULK_THRESHOLD + 8
    rows = np.full(n, 1, dtype=np.uint64)
    cols = np.arange(n, dtype=np.uint64)
    frag.merge_block(0, [(rows, cols), (rows, cols)])


def _read_from(frag):
    import io

    src = Fragment(None, "i", "f", "standard", 0)
    src.open()
    src.set_bit(3, 123)
    buf = io.BytesIO()
    src.write_to(buf)
    buf.seek(0)
    frag.read_from(buf)


MUTATIONS = {
    "set_bit": lambda f: f.set_bit(1, 500),
    "clear_bit": lambda f: f.clear_bit(0, 0),  # row 0 bit 0 pre-planted
    "set_value": lambda f: f.set_value(3, 8, 77),
    "bulk_import": lambda f: f.bulk_import(
        np.array([2, 2], np.uint64), np.array([5, 6], np.uint64)),
    "import_value": lambda f: f.import_value(
        np.array([9], np.uint64), np.array([41], np.uint64), 8),
    "merge_block_small": _merge_small,
    "merge_block_bulk": _merge_bulk,
    "read_from": _read_from,
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_every_mutation_path_bumps_generation_and_epoch(name):
    """A mutation path that skips the generation or epoch bump serves a
    stale delta silently — this audit pins all of them (fragment.py's two
    generation += 1 sites plus every caller of _invalidate_row)."""
    epoch = WriteEpoch()
    f = Fragment(None, "i", "f", "standard", 0, epoch=epoch)
    f.open()
    f.set_bit(0, 0)  # seed so clear_bit actually clears
    g0, e0 = f.generation, epoch.value
    MUTATIONS[name](f)
    assert f.generation > g0, f"{name} did not bump generation"
    assert epoch.value > e0, f"{name} did not bump write epoch"


class _SpyEpoch(WriteEpoch):
    """An epoch that notes, at every bump, how far the fragment's
    generation and its view's journal have come."""

    def __init__(self, frag):
        super().__init__()
        self.frag, self.seen = frag, []

    def bump(self):
        self.seen.append((self.frag.generation, self.frag.journal.stamp[1]))
        super().bump()


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_every_mutation_path_tells_the_journal_before_the_epoch(name):
    """What the engine's caches trust instead of asking every fragment:
    each path notes in its view's journal, for every generation bump, its
    shard and the row it changed (or ALL_ROWS) with the generation BEFORE
    the bump, and the entry is there when the epoch moves. A path that
    skips it leaves a resident plane of that row served as fresh."""
    journal = ChangeJournal()
    f = Fragment(None, "i", "f", "standard", 0, journal=journal)
    f.open()
    f.set_bit(0, 0)  # seed so clear_bit actually clears
    rows = range(10)  # the paths' set rows and BSI planes between them
    planes = {r: f.plane_np(r).copy() for r in rows}
    g0, s0 = f.generation, journal.stamp
    f.epoch = spy = _SpyEpoch(f)
    MUTATIONS[name](f)
    ents = journal.since(s0, journal.stamp)
    assert ents, f"{name} told the journal nothing"
    assert f.generation == g0 + len(ents)  # an entry a bump
    for k, (seq, shard, row, fp) in enumerate(ents):
        assert (seq, shard) == (s0[1] + k + 1, f.shard)
        assert fp == (None if row == ALL_ROWS else (f.incarnation, g0 + k))
    # Journal before epoch: at every bump of the epoch the journal had an
    # entry for every bump of the generation so far.
    assert spy.seen
    assert all(gen - g0 == seq - s0[1] for gen, seq in spy.seen)
    named = {row for _, _, row, _ in ents}
    changed = {r for r in rows if not np.array_equal(planes[r], f.plane_np(r))}
    assert changed, f"{name} changed no plane: the audit audits nothing"
    assert ALL_ROWS in named or changed <= named
    # And the words are where the entry says: since the generation it
    # carries, the fragment's own journal has every changed word.
    for _, _, row, fp in ents:
        if row != ALL_ROWS:
            words = f.dirty_words_since(row, fp[1])
            if words is not None:  # None: a bulk path, regathered whole
                w64 = f.plane_np(row).view(np.uint64)
                was = planes[row].view(np.uint64)
                assert set(np.flatnonzero(w64 != was)) <= set(words.tolist())


# ---------------------------------------------------- engine delta refresh


def _full_leaf(holder, leaf, shards):
    """Ground-truth plane assembly straight from storage."""
    bufs = []
    for s in shards:
        frag = holder.fragment("i", leaf.field, leaf.view, s)
        bufs.append(
            frag.plane_np(leaf.row) if frag is not None
            else np.zeros(WORDS_PER_ROW, np.uint32))
    return np.stack(bufs)


def test_single_set_refreshes_leaf_via_delta(holder):
    """ISSUE acceptance: one set() on a resident leaf refreshes the cached
    plane via the delta path — counter-proven (leaf_delta_hits > 0, bytes
    moved KiB-scale vs the multi-MiB full plane)."""
    fld = plant(holder)
    engine = ShardedQueryEngine(holder)
    shards = list(range(4))
    call = parse("Count(Intersect(Row(f=0), Row(f=1)))").calls[0].children[0]
    before = engine.count("i", call, shards)
    full_bytes = engine.counters["full_refresh_bytes"]
    assert full_bytes >= 2 * 4 * WORDS_PER_ROW * 4  # two multi-MiB planes

    col = 3 * SHARD_WIDTH + 4321
    assert fld.set_bit(0, col)
    after = engine.count("i", call, shards)
    assert engine.counters["leaf_delta_hits"] > 0
    assert engine.counters["full_refresh_bytes"] == full_bytes  # no full walk
    assert engine.counters["delta_bytes"] <= 1024  # vs MiB-scale planes
    want = before + (1 if holder.fragment("i", "f", "standard", 3).bit(1, col)
                     else 0)
    assert after == want
    # The refreshed cached plane is byte-identical to a storage regather.
    leaf = Leaf("f", "standard", 0)
    arr = np.asarray(engine._gather_leaf("i", leaf, tuple(shards)))
    np.testing.assert_array_equal(arr[:4], _full_leaf(holder, leaf, shards))


def test_single_set_refreshes_stack_via_delta(holder):
    fld = plant(holder)
    engine = ShardedQueryEngine(holder)
    shards = list(range(4))
    calls = [parse(f"Intersect(Row(f={a}), Row(f={b}))").calls[0]
             for a, b in [(0, 1), (1, 2), (2, 3)]]
    engine.count_batch("i", calls, shards)
    full_bytes = engine.counters["full_refresh_bytes"]
    assert fld.set_bit(2, 2 * SHARD_WIDTH + 99)
    got = engine.count_batch("i", calls, shards)
    assert engine.counters["stack_delta_hits"] > 0
    assert engine.counters["full_refresh_bytes"] == full_bytes
    singles = [
        int(np.bitwise_count(np.bitwise_and(
            _full_leaf(holder, Leaf("f", "standard", a), shards),
            _full_leaf(holder, Leaf("f", "standard", b), shards))).sum())
        for a, b in [(0, 1), (1, 2), (2, 3)]
    ]
    assert got.tolist() == singles


def test_delta_disabled_by_config(holder):
    plant(holder)
    engine = ShardedQueryEngine(
        holder, config=EngineConfig(delta_max_fraction=0.0))
    shards = list(range(4))
    call = parse("Row(f=0)").calls[0]
    engine.count("i", call, shards)
    holder.index("i").field("f").set_bit(0, 1)
    engine.count("i", call, shards)
    assert engine.counters["leaf_delta_hits"] == 0
    assert engine.counters["leaf_misses"] >= 2


def test_delta_threshold_falls_back_to_full(holder):
    """A write burst past delta_max_fraction must regather, and still be
    correct."""
    fld = plant(holder)
    engine = ShardedQueryEngine(
        holder, config=EngineConfig(delta_max_fraction=1e-9))
    shards = list(range(4))
    call = parse("Row(f=0)").calls[0]
    c0 = engine.count("i", call, shards)
    new_cols = [7, 71, 717]
    added = sum(fld.set_bit(0, c) for c in new_cols)
    assert engine.count("i", call, shards) == c0 + added
    assert engine.counters["leaf_delta_hits"] == 0


def test_write_stream_moves_fewer_bytes_with_delta_on_than_off(holder):
    """Batched Counts over a resident stack while a write stream dirties
    its planes (2 shards x 8 rows, 4 batches, 4 single-bit sets before
    each): the same traffic moves fewer bytes host->device with the delta
    path on than with it forced off, and each side pays through its own
    counter only. One monotone write stream across both runs: re-setting
    a set bit bumps no generation, so a stream of its own per run would
    hand the second run phantoms."""
    n_shards, n_rows, batches, writes = 2, 8, 4, 4
    fld = plant(holder, n_shards=n_shards, n_rows=n_rows, per_row=1024,
                seed=17)
    shards = list(range(n_shards))
    calls = [parse(f"Intersect(Row(f={a}), Row(f={(a + off) % n_rows}))").calls[0]
             for off in range(1, n_rows) for a in range(n_rows)]
    stream = itertools.count(1)

    def write_burst():
        for i in itertools.islice(stream, writes):
            assert fld.set_bit(i % n_rows, (i * 7919) % SHARD_WIDTH)

    def run(config):
        engine = ShardedQueryEngine(holder, config=config)
        try:
            np.asarray(engine.count_batch_async("i", calls, shards))
            base = dict(engine.counters)
            for _ in range(batches):
                write_burst()
                got = np.asarray(engine.count_batch_async("i", calls, shards))
            moved = {k: engine.counters[k] - base[k] for k in (
                "delta_bytes", "full_refresh_bytes", "stack_delta_hits")}
            # The batch program pads its query count to a power of two.
            return got[:len(calls)].tolist(), base["full_refresh_bytes"], moved
        finally:
            engine.close()

    _, _, on = run(EngineConfig())
    got, cold_bytes, off = run(EngineConfig(delta_max_fraction=0.0))
    assert on["full_refresh_bytes"] == 0 and on["stack_delta_hits"] > 0
    # A refresh is one scatter of (u, shard, col, value) int32 quadruples,
    # padded to at least DELTA_MIN_UPDATES of them.
    assert 0 < on["delta_bytes"] <= batches * DELTA_MIN_UPDATES * 4 * 4
    assert off["delta_bytes"] == 0 and off["stack_delta_hits"] == 0
    # A write to `f` stales every resident leaf of `f`, and the view's
    # journal says which rows: with no delta every batch walks and uploads
    # again the four planes its burst wrote to, as the cold batch did all
    # eight, and republishes the other four as they stand.
    assert cold_bytes >= n_rows * n_shards * WORDS_PER_ROW * 4
    assert off["full_refresh_bytes"] == batches * writes * cold_bytes // n_rows
    assert on["delta_bytes"] < off["full_refresh_bytes"]
    fresh = ShardedQueryEngine(holder)
    try:
        assert got == fresh.count_batch("i", calls, shards).tolist()
    finally:
        fresh.close()


def test_property_random_writes_delta_equals_full(holder):
    """Property: across randomized write sequences — point sets/clears,
    BSI writes, bulk imports, journal overflow — the delta-maintained leaf
    and stack tensors stay byte-identical to a fresh engine's full
    regather."""
    fld = plant(holder, n_shards=3, n_rows=4)
    # Tiny journals so the sequence crosses the overflow fallback too.
    for s in range(3):
        holder.fragment("i", "f", "standard", s).delta_journal_ops = 64
    engine = ShardedQueryEngine(holder)
    shards = tuple(range(3))
    leaves = [Leaf("f", "standard", r) for r in range(4)]
    rng = np.random.default_rng(42)

    def mutate_once():
        kind = rng.integers(0, 4)
        row = int(rng.integers(0, 4))
        col = int(rng.integers(0, 3 * SHARD_WIDTH))
        if kind == 0:
            fld.set_bit(row, col)
        elif kind == 1:
            fld.clear_bit(row, col)
        elif kind == 2:  # small burst into one word neighborhood
            base = col - col % 64
            for k in range(int(rng.integers(1, 8))):
                fld.set_bit(row, min(base + k, 3 * SHARD_WIDTH - 1))
        else:  # bulk import: poisons the journal for the touched rows
            n = 200
            cols = rng.integers(0, 3 * SHARD_WIDTH, n).astype(np.uint64)
            fld.import_bits(np.full(n, row, np.uint64), cols)

    for round_ in range(8):
        mutate_once()
        # Delta-maintained tensors...
        stack = unfolded(
            engine._stacked_leaf_tensor("i", leaves, shards, pad=True))
        plane = np.asarray(engine._gather_leaf("i", leaves[0], shards))
        # ...must equal a cold rebuild straight from storage.
        for u, leaf in enumerate(leaves):
            np.testing.assert_array_equal(
                stack[u, :3], _full_leaf(holder, leaf, list(shards)),
                err_msg=f"round {round_} leaf {u} stack diverged")
        np.testing.assert_array_equal(
            plane[:3], _full_leaf(holder, leaves[0], list(shards)),
            err_msg=f"round {round_} leaf plane diverged")
    # The sequence must actually have exercised the delta path.
    assert engine.counters["stack_delta_hits"] > 0


def test_recreated_index_never_serves_stale_delta(holder):
    """A deleted+recreated index resets generation counters while the
    engine's name-keyed caches survive; the incarnation half of the
    fingerprint must force a full regather even when the fresh counter
    climbs back past the cached generation."""
    fld = plant(holder, n_shards=2, n_rows=2)
    engine = ShardedQueryEngine(holder)
    shards = list(range(2))
    call = parse("Row(f=0)").calls[0]
    old = engine.count("i", call, shards)
    gen0 = holder.fragment("i", "f", "standard", 0).generation
    assert old > 0

    holder.delete_index("i")
    idx = holder.create_index("i")
    fld = idx.create_field("f")
    # Different, smaller content; push the fresh generation past the
    # cached one with journaled single-bit writes.
    for k in range(gen0 + 3):
        fld.set_bit(0, k)
    got = engine.count("i", call, shards)
    assert got == gen0 + 3, (got, gen0)
    assert engine.counters["leaf_delta_hits"] == 0  # full regather, no delta


def test_recreated_index_never_serves_stale_memo(holder):
    """Memo epoch fast path: a recreated index's fresh epoch climbing back
    to a stored entry's value must not alias the old count."""
    plant(holder, n_shards=1, n_rows=1)
    engine = ShardedQueryEngine(holder)
    call = parse("Row(f=0)").calls[0]
    old = engine.count("i", call, [0])
    epoch0 = holder.index("i").write_epoch.value
    holder.delete_index("i")
    fld = holder.create_index("i").create_field("f")
    fresh = holder.index("i").write_epoch
    bits = 0
    # Drive the fresh epoch to the stored value (a new view and a new
    # fragment move it too, so count the bits, not the bumps).
    while fresh.value < epoch0:
        fld.set_bit(0, bits)
        bits += 1
    assert fresh.value == epoch0
    got = engine.count("i", call, [0])
    assert got == bits != old


def test_recreated_field_never_serves_stale_memo(holder):
    """delete_field must bump the index write epoch: the recreated field
    shares the index's WriteEpoch instance, so without the bump the memo's
    O(1) fast path would keep serving the deleted field's counts."""
    plant(holder, n_shards=1, n_rows=1)
    engine = ShardedQueryEngine(holder)
    call = parse("Row(f=0)").calls[0]
    old = engine.count("i", call, [0])
    assert old > 0
    idx = holder.index("i")
    idx.delete_field("f")
    idx.create_field("f")  # empty
    assert engine.count("i", call, [0]) == 0


def test_stack_delta_keeps_pad_rows_in_sync(holder):
    """pow2 pad rows duplicate leaf 0; a delta touching leaf 0 must update
    them too, preserving the full-rebuild invariant (pad == leaf 0's
    current plane)."""
    fld = plant(holder, n_shards=2, n_rows=3)
    engine = ShardedQueryEngine(holder)
    shards = (0, 1)
    leaves = [Leaf("f", "standard", r) for r in range(3)]  # pads to 4
    engine._stacked_leaf_tensor("i", leaves, shards, pad=True)
    fld.set_bit(0, 12345)
    stack = unfolded(
        engine._stacked_leaf_tensor("i", leaves, shards, pad=True))
    assert engine.counters["stack_delta_hits"] > 0
    assert stack.shape[0] == 4
    np.testing.assert_array_equal(stack[3], stack[0])
    np.testing.assert_array_equal(
        stack[0, :2], _full_leaf(holder, leaves[0], list(shards)))


# ----------------------------------------------- byte-cache accounting


class TestByteCacheAccounting:
    """The delta path republishes entries in place, so the byte counters
    must be provably exact across insert/replace/evict first."""

    def _engine(self, holder):
        return ShardedQueryEngine(holder)

    def _sum(self, cache):
        return sum(e[1].nbytes for e in cache.values())

    def test_insert_replace_evict_accounting(self, holder):
        plant(holder, n_shards=1, n_rows=1, per_row=4)
        engine = self._engine(holder)
        cache, used, budget = {}, 0, 100
        a = np.zeros(10, np.uint8)  # 10 bytes
        b = np.zeros(40, np.uint8)
        c = np.zeros(60, np.uint8)
        with engine._lock:
            used = engine._byte_cache_put(cache, "a", ((), a), budget, used,
                                          "leaf_evictions")
            used = engine._byte_cache_put(cache, "b", ((), b), budget, used,
                                          "leaf_evictions")
        assert used == self._sum(cache) == 50
        # Replace key "a" with a bigger payload: no double count.
        with engine._lock:
            used = engine._byte_cache_put(cache, "a", ((), b), budget, used,
                                          "leaf_evictions")
        assert used == self._sum(cache) == 80
        assert engine.counters["leaf_evictions"] == 0
        # Pushing past budget evicts LRU ("b" was least recently put).
        with engine._lock:
            used = engine._byte_cache_put(cache, "c", ((), c), budget, used,
                                          "leaf_evictions")
        assert used == self._sum(cache)
        assert used <= budget
        assert "c" in cache
        assert engine.counters["leaf_evictions"] > 0

    def test_oversized_entry_keeps_itself(self, holder):
        plant(holder, n_shards=1, n_rows=1, per_row=4)
        engine = self._engine(holder)
        cache, used = {}, 0
        big = np.zeros(500, np.uint8)
        with engine._lock:
            used = engine._byte_cache_put(cache, "k", ((), big), 100, used,
                                          "leaf_evictions")
        # An over-budget entry still resides (evicting it would thrash);
        # accounting stays exact.
        assert list(cache) == ["k"]
        assert used == self._sum(cache) == 500

    def test_live_refresh_accounting_through_delta(self, holder):
        """End to end: deltas and full refreshes across writes keep
        leaf/stack byte counters equal to the resident sum."""
        fld = plant(holder)
        engine = ShardedQueryEngine(holder)
        shards = tuple(range(4))
        leaves = [Leaf("f", "standard", r) for r in range(2)]
        for k in range(6):
            engine._stacked_leaf_tensor("i", leaves, shards, pad=True)
            engine._gather_leaf("i", leaves[0], shards)
            fld.set_bit(k % 2, k * 64)
        with engine._lock:
            assert engine._leaf_bytes == sum(
                e[1].nbytes for e in engine._leaf_cache.values())
            assert engine._stack_bytes == sum(
                e[1].nbytes for e in engine._stack_cache.values())


# ------------------------------------------------- memo epoch fast path


def test_memo_probe_short_circuits_on_quiet_epoch(holder, monkeypatch):
    plant(holder)
    idx = holder.index("i")
    idx.create_field_if_not_exists("g")
    idx.field("g").set_bit(1, 2)
    engine = ShardedQueryEngine(holder)
    shards = list(range(4))
    call = parse("Intersect(Row(f=0), Row(f=1))").calls[0]
    want = engine.count("i", call, shards)

    walks = {"n": 0}
    real_fp = engine._fingerprint

    def counting_fp(*a, **kw):
        walks["n"] += 1
        return real_fp(*a, **kw)

    monkeypatch.setattr(engine, "_fingerprint", counting_fp)
    # Quiet index: the repeat probe must answer WITHOUT the O(U x S)
    # fingerprint walk.
    assert engine.count("i", call, shards) == want
    assert walks["n"] == 0
    # A write to an unrelated field bumps the epoch: one walk re-validates
    # (fp unchanged -> still a hit), and the refreshed epoch makes the
    # next probe O(1) again.
    idx.field("g").set_bit(1, 77)
    assert engine.count("i", call, shards) == want
    assert walks["n"] > 0
    walks["n"] = 0
    assert engine.count("i", call, shards) == want
    assert walks["n"] == 0
    # A write to a member fragment invalidates for real.
    idx.field("f").set_bit(0, 13)
    got = engine.count("i", call, shards)
    frag0 = holder.fragment("i", "f", "standard", 0)
    assert got == want + (1 if frag0.bit(1, 13) else 0)
