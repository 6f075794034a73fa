"""Tiered plane storage (pilosa_tpu/tier/): the HBM ↔ host-RAM ↔ disk
residency manager behind the engine's device caches.

The tentpole invariants under test: a demote-to-host/disk → re-promote
cycle is bit-exact against a cold gather (fingerprint equality included);
delta-fold-on-promotion matches a full regather after interleaved writes;
a concurrent query during demotion sees either tier correctly (no torn
plane); and a corrupt spill file degrades to a regather, never to a query
error. Plus the satellite surfaces: the oversized-entry policy and the
memo eviction counters in the engine byte caches, and the env > [engine]
> [tier] > default budget resolution.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.constants import SHARD_WIDTH, WORDS_PER_ROW
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.errors import CorruptFragmentError
from pilosa_tpu.parallel import EngineConfig
from pilosa_tpu.parallel.engine import Leaf, ShardedQueryEngine
from pilosa_tpu.pql.parser import parse
from pilosa_tpu.storage.bitmap import decode_plane_words
from pilosa_tpu.tier import TierConfig
from pilosa_tpu.tier.manager import TierManager

N_WORDS64 = WORDS_PER_ROW // 2  # decode_plane_words speaks 64-bit words


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


def plant(holder, n_shards=2, n_rows=8, per_row=300, seed=7, index="i"):
    idx = holder.create_index_if_not_exists(index)
    fld = idx.create_field_if_not_exists("f")
    rng = np.random.default_rng(seed)
    expected = {}
    for row in range(n_rows):
        cols = []
        for s in range(n_shards):
            local = rng.choice(SHARD_WIDTH, size=per_row, replace=False)
            cols.extend(int(s * SHARD_WIDTH + c) for c in local)
        fld.import_bits([row] * len(cols), cols)
        expected[row] = set(cols)
    return fld, expected


def tiny_engine(holder, n_keep_planes, n_shards, tier=None, **tier_kw):
    """Engine whose leaf cache holds only `n_keep_planes` planes, so every
    sweep over more planes than that evicts (and demotes, when a tier
    config enables the manager)."""
    plane_bytes = n_shards * WORDS_PER_ROW * 4
    if tier is None:
        tier_kw.setdefault("host_bytes", 1 << 28)
        tier_kw.setdefault("prefetch_interval", 0)
        tier = TierConfig(**tier_kw)
    return ShardedQueryEngine(
        holder,
        config=EngineConfig(leaf_cache_bytes=n_keep_planes * plane_bytes),
        tier_config=tier,
    )


def sweep(engine, index, calls, shards, rows):
    return [int(np.asarray(engine.count_async(index, calls[r], shards)))
            for r in rows]


# ------------------------------------------------------- plane-section codec


class TestPlaneCodec:
    def _roundtrip(self, holder, cols):
        idx = holder.create_index_if_not_exists("codec")
        fld = idx.create_field_if_not_exists(f"f{len(cols)}_{hash(tuple(cols)) & 0xFFFF}")
        if len(cols):
            fld.import_bits([0] * len(cols), sorted(int(c) for c in cols))
        frag = holder.fragment("codec", fld.name, "standard", 0)
        if frag is None:  # empty row: decode of an empty bitmap
            from pilosa_tpu.storage.bitmap import Bitmap

            data = Bitmap().to_bytes()
            got = decode_plane_words(data, N_WORDS64)
            assert not got.any()
            return
        frag.storage.optimize()  # settle forms (runs/bitmaps where smaller)
        data, fp = frag.row_compressed(0)
        want = frag.plane_np(0)
        got = decode_plane_words(data, N_WORDS64).view(np.uint32)
        np.testing.assert_array_equal(got, want)
        assert fp == (frag.incarnation, frag.generation)

    def test_array_containers(self, holder):
        rng = np.random.default_rng(3)
        self._roundtrip(holder, rng.choice(SHARD_WIDTH, 700, replace=False))

    def test_run_containers(self, holder):
        self._roundtrip(
            holder,
            list(range(1000, 9000)) + list(range(70000, 70100))
            + [0, 63, 64, SHARD_WIDTH - 1])

    def test_bitmap_containers(self, holder):
        rng = np.random.default_rng(4)
        self._roundtrip(holder, rng.choice(1 << 17, 40000, replace=False))

    def test_word_boundary_bits(self, holder):
        # Run endpoints landing exactly on 64-bit word edges exercise the
        # first/middle/last mask arithmetic.
        self._roundtrip(holder, list(range(64, 256)) + [63, 256, 319])

    def test_empty(self, holder):
        self._roundtrip(holder, [])

    def test_trailing_bytes_ignored(self, holder):
        fld, _ = plant(holder, n_shards=1, n_rows=1)
        frag = holder.fragment("i", "f", "standard", 0)
        data, _ = frag.row_compressed(0)
        got = decode_plane_words(data + b"opslog-junk", N_WORDS64)
        np.testing.assert_array_equal(
            got, decode_plane_words(data, N_WORDS64))

    @pytest.mark.parametrize(
        "mutate",
        [
            lambda d: d[:4],  # truncated header
            lambda d: b"XX" + d[2:],  # bad magic
            lambda d: d[: len(d) // 2],  # truncated payload
        ],
    )
    def test_corrupt_raises_typed(self, holder, mutate):
        fld, _ = plant(holder, n_shards=1, n_rows=1)
        frag = holder.fragment("i", "f", "standard", 0)
        data, _ = frag.row_compressed(0)
        with pytest.raises(CorruptFragmentError):
            decode_plane_words(mutate(data), N_WORDS64)

    def test_container_beyond_plane_raises(self, holder):
        # A container key past the plane's words is corruption, not a
        # silent truncation.
        from pilosa_tpu.storage.bitmap import Bitmap

        b = Bitmap(np.array([5], dtype=np.uint64))
        data = b.to_bytes()
        with pytest.raises(CorruptFragmentError):
            decode_plane_words(data, 0)

    def test_partial_plane_container_decodes(self):
        """Exotic SHARD_WIDTH < 2^16: the plane is smaller than one
        container, whose in-plane bits must decode (and bits beyond the
        plane must raise, not scatter out of bounds)."""
        from pilosa_tpu.storage.bitmap import Bitmap

        n_words = 8  # a 512-bit plane
        b = Bitmap(np.array([0, 5, 64, 511], dtype=np.uint64))
        got = decode_plane_words(b.to_bytes(), n_words)
        want = np.zeros(n_words, dtype=np.uint64)
        want[0] = (1 << 0) | (1 << 5)
        want[1] = 1
        want[7] = 1 << 63
        np.testing.assert_array_equal(got, want)
        with pytest.raises(CorruptFragmentError):
            decode_plane_words(
                Bitmap(np.array([512], dtype=np.uint64)).to_bytes(), n_words)
        # Run form beyond the plane is equally typed corruption.
        dense = Bitmap(np.arange(500, 520, dtype=np.uint64))
        dense.optimize()
        with pytest.raises(CorruptFragmentError):
            decode_plane_words(dense.to_bytes(), n_words)


# --------------------------------------------------- demote/promote (host)


class TestHostTierRoundTrip:
    def test_repromotion_is_bit_exact_vs_cold_gather(self, holder):
        n_rows, n_shards = 8, 2
        fld, expected = plant(holder, n_shards, n_rows)
        shards = tuple(range(n_shards))
        calls = {r: parse(f"Row(f={r})").calls[0] for r in range(n_rows)}
        engine = tiny_engine(holder, 3, n_shards)
        try:
            # Cold sweep (evicts+demotes), then re-sweep from the tier.
            got1 = sweep(engine, "i", calls, shards, range(n_rows))
            engine.tier.drain()
            base = dict(engine.counters)
            got2 = sweep(engine, "i", calls, shards, range(n_rows))
            assert got1 == got2 == [len(expected[r]) for r in range(n_rows)]
            assert engine.counters["leaf_misses"] == base["leaf_misses"], \
                "a warm tier must absorb every HBM miss"
            assert engine.counters["leaf_tier_hits"] > base["leaf_tier_hits"]

            # Fingerprint-equality check on the actual device planes: the
            # promoted tensor must be byte-identical to a cold gather by a
            # tierless engine.
            cold = ShardedQueryEngine(
                holder, config=EngineConfig(),
                tier_config=TierConfig(host_bytes=0, disk_bytes=0))
            try:
                for r in range(n_rows):
                    leaf = Leaf("f", "standard", r)
                    a = np.asarray(engine._gather_leaf("i", leaf, shards))
                    b = np.asarray(cold._gather_leaf("i", leaf, shards))
                    np.testing.assert_array_equal(a, b)
            finally:
                cold.close()
        finally:
            engine.close()

    def test_delta_fold_on_promotion_matches_regather(self, holder):
        n_rows, n_shards = 8, 2
        fld, expected = plant(holder, n_shards, n_rows)
        shards = tuple(range(n_shards))
        calls = {r: parse(f"Row(f={r})").calls[0] for r in range(n_rows)}
        engine = tiny_engine(holder, 3, n_shards)
        try:
            sweep(engine, "i", calls, shards, range(n_rows))
            engine.tier.drain()
            # Interleaved writes to every plane — including demoted ones.
            for r in range(n_rows):
                col = (r * 977) % SHARD_WIDTH
                if fld.set_bit(r, col):
                    expected[r].add(col)
                rm = next(iter(expected[r]))
                fld.clear_bit(r, rm)
                expected[r].discard(rm)
            base = dict(engine.counters)
            got = sweep(engine, "i", calls, shards, range(n_rows))
            assert got == [len(expected[r]) for r in range(n_rows)]
            # Planes whose journals stayed within the delta bound must not
            # have paid a full regather: folds (demoted) or delta hits
            # (still resident) only.
            assert engine.counters["leaf_misses"] == base["leaf_misses"]
            assert engine.tier.counters["delta_folds"] > 0
        finally:
            engine.close()

    def test_journal_overflow_walks_that_shard_only(self, tmp_path):
        h = Holder(str(tmp_path / "ovf"), delta_journal_ops=8)
        h.open()
        try:
            fld, expected = plant(h, 2, 4)
            shards = (0, 1)
            calls = {r: parse(f"Row(f={r})").calls[0] for r in range(4)}
            engine = tiny_engine(h, 1, 2)
            try:
                sweep(engine, "i", calls, shards, range(4))
                engine.tier.drain()
                # Blow past the journal bound on row 0 / shard 0 only.
                for k in range(16):
                    col = 64 * k
                    if fld.set_bit(0, col):
                        expected[0].add(col)
                got = sweep(engine, "i", calls, shards, range(4))
                assert got == [len(expected[r]) for r in range(4)]
                assert engine.tier.counters["shard_walks"] >= 1
            finally:
                engine.close()
        finally:
            h.close()

    def test_recreated_index_never_serves_stale_blob(self, holder):
        fld, _ = plant(holder, 2, 4)
        shards = (0, 1)
        calls = {r: parse(f"Row(f={r})").calls[0] for r in range(4)}
        engine = tiny_engine(holder, 1, 2)
        try:
            sweep(engine, "i", calls, shards, range(4))
            engine.tier.drain()
            holder.delete_index("i")
            idx = holder.create_index("i")
            f2 = idx.create_field("f")
            f2.set_bit(0, 5)
            f2.set_bit(0, SHARD_WIDTH + 9)
            got = int(np.asarray(engine.count_async("i", calls[0], shards)))
            assert got == 2
        finally:
            engine.close()

    @pytest.mark.parametrize("tiered", [True, False],
                             ids=["tiered", "drop_regather"])
    def test_batched_sweeps_over_three_times_the_budget(
            self, holder, monkeypatch, tiered):
        """18 planes under a leaf and a stack budget of 6, counted in
        rotating batches of 6 with the memo off (a repeat would be a dict
        read): once the tier is warm no sweep walks a live container
        again, nor does a write to every seventh plane; without the tier
        every touch of a sweep does."""
        monkeypatch.setenv("PILOSA_MEMO_ENTRIES", "0")
        n_rows, n_shards, sweeps, batch = 18, 2, 4, 6
        fld, expected = plant(holder, n_shards, n_rows, per_row=512, seed=17)
        shards = tuple(range(n_shards))
        calls = {r: parse(f"Row(f={r})").calls[0] for r in range(n_rows)}
        budget = n_rows * n_shards * WORDS_PER_ROW * 4 // 3
        engine = ShardedQueryEngine(
            holder,
            # One device: a plane is its shards' words and no padding.
            config=EngineConfig(mesh_devices=1, leaf_cache_bytes=budget,
                                stack_cache_bytes=budget),
            tier_config=TierConfig(host_bytes=(1 << 28) if tiered else 0,
                                   disk_bytes=0, prefetch_interval=0))

        def sweep_batched(s):
            # Same planes, another batch composition each sweep, so that
            # no stack key repeats.
            rot = [(r + s) % n_rows for r in range(n_rows)]
            for g in range(0, n_rows, batch):
                grp = rot[g:g + batch]
                got = engine.count_batch(
                    "i", [calls[r] for r in grp], shards).tolist()
                assert got == [len(expected[r]) for r in grp]
            if tiered:
                engine.tier.drain()

        try:
            sweep_batched(sweeps)
            base = dict(engine.counters)
            for s in range(sweeps):
                sweep_batched(s)
            moved = {k: engine.counters[k] - base[k]
                     for k in ("leaf_misses", "leaf_tier_hits", "leaf_hits")}
            # Every touch of a plane is a hit, a promotion or a walk.
            assert sum(moved.values()) == sweeps * n_rows
            if not tiered:
                assert engine.tier is None
                assert moved["leaf_misses"] > 0 and moved["leaf_tier_hits"] == 0
                return
            assert moved["leaf_misses"] == 0
            assert moved["leaf_tier_hits"] > 0
            for r in range(0, n_rows, 7):
                col = r * 31 % SHARD_WIDTH
                if fld.set_bit(r, col):
                    expected[r].add(col)
            engine.tier.drain()
            pre = dict(engine.counters)
            assert sweep(engine, "i", calls, shards, range(n_rows)) == [
                len(expected[r]) for r in range(n_rows)]
            assert engine.counters["leaf_misses"] == pre["leaf_misses"]
            # Rows 7 and 14 were demoted when written: their journals
            # fold as they are promoted.
            assert engine.tier.snapshot()["delta_folds"] > 0
        finally:
            engine.close()

    def test_inclusive_host_tier_skips_unchanged_recapture(self, holder):
        """Steady-state read churn: evict → promote → evict again with no
        writes in between must not re-serialize the plane."""
        fld, _ = plant(holder, 2, 8)
        shards = (0, 1)
        calls = {r: parse(f"Row(f={r})").calls[0] for r in range(8)}
        engine = tiny_engine(holder, 2, 2)
        try:
            sweep(engine, "i", calls, shards, range(8))
            engine.tier.drain()
            sweep(engine, "i", calls, shards, range(8))
            engine.tier.drain()
            assert engine.tier.counters["demotions_skipped"] > 0
        finally:
            engine.close()


# ------------------------------------------------------------- concurrency


class TestConcurrency:
    def test_no_torn_plane_during_demotion_churn(self, holder):
        """Queries racing demotions (the background worker serializing
        live containers), forced demote churn, and concurrent writes must
        see every plane at SOME valid state — counts on the unwritten
        rows are always exact, never torn.

        Device dispatch stays on ONE thread (concurrent sharded dispatch
        on the 8-device CPU test mesh is a jax-level hazard the scheduler
        serializes in production); the concurrency under test is the tier
        manager's demote worker + direct demote churn + fragment writes
        against that query stream."""
        n_rows, n_shards = 10, 2
        fld, expected = plant(holder, n_shards, n_rows)
        shards = tuple(range(n_shards))
        calls = {r: parse(f"Row(f={r})").calls[0] for r in range(n_rows)}
        engine = tiny_engine(holder, 2, n_shards)
        stop = threading.Event()
        errors = []

        def demote_churn():
            # Re-queue every key for demotion constantly, including keys
            # that are HBM-resident or mid-promotion.
            while not stop.is_set():
                for r in range(n_rows):
                    engine.tier.demote(("i", Leaf("f", "standard", r),
                                        shards))
                time.sleep(0.001)

        def write_churn():
            # Writes land on rows 2.. only, so rows 0/1 keep a stable
            # expected count while their planes still churn through the
            # tiers.
            k = 0
            while not stop.is_set():
                fld.set_bit(2 + (k % (n_rows - 2)), (k * 131) % SHARD_WIDTH)
                k += 1
                time.sleep(0.0005)

        threads = [threading.Thread(target=demote_churn),
                   threading.Thread(target=write_churn)]
        for t in threads:
            t.start()
        try:
            deadline = time.monotonic() + 3.0
            while time.monotonic() < deadline and not errors:
                for r in range(n_rows):
                    got = int(np.asarray(
                        engine.count_async("i", calls[r], shards)))
                    if r < 2 and got != len(expected[r]):
                        errors.append((r, got, len(expected[r])))
                    elif got < len(expected[r]):  # writes only ADD bits
                        errors.append((r, got, len(expected[r])))
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
            engine.close()
        assert not errors, errors[:3]


# ---------------------------------------------------------------- disk tier


class TestDiskTier:
    def _spill_engine(self, holder, tmp_path, host_planes=1):
        plane_bytes = 2 * WORDS_PER_ROW * 4
        # Host tier big enough for ~1 compressed plane only, so demotions
        # cascade to disk. Compressed planes here are ~2-3 KiB.
        return tiny_engine(
            holder, 1, 2,
            tier=TierConfig(host_bytes=4096, disk_bytes=1 << 22,
                            disk_path=str(tmp_path / "spill"),
                            prefetch_interval=0))

    def test_disk_round_trip_bit_exact(self, holder, tmp_path):
        n_rows = 6
        fld, expected = plant(holder, 2, n_rows)
        shards = (0, 1)
        calls = {r: parse(f"Row(f={r})").calls[0] for r in range(n_rows)}
        engine = self._spill_engine(holder, tmp_path)
        try:
            got1 = sweep(engine, "i", calls, shards, range(n_rows))
            engine.tier.drain()
            snap = engine.tier.snapshot()
            assert snap["demotions_disk"] > 0
            assert os.listdir(tmp_path / "spill")
            got2 = sweep(engine, "i", calls, shards, range(n_rows))
            assert got1 == got2 == [len(expected[r]) for r in range(n_rows)]
            assert engine.tier.snapshot()["promotions_disk"] > 0
        finally:
            engine.close()

    def test_corrupt_spill_regathers_not_errors(self, holder, tmp_path):
        n_rows = 6
        fld, expected = plant(holder, 2, n_rows)
        shards = (0, 1)
        calls = {r: parse(f"Row(f={r})").calls[0] for r in range(n_rows)}
        engine = self._spill_engine(holder, tmp_path)
        try:
            sweep(engine, "i", calls, shards, range(n_rows))
            engine.tier.drain()
            spill_dir = tmp_path / "spill"
            files = sorted(os.listdir(spill_dir))
            assert files
            for name in files:  # flip bytes in EVERY spill file
                p = spill_dir / name
                raw = bytearray(p.read_bytes())
                raw[len(raw) // 2] ^= 0xFF
                p.write_bytes(bytes(raw))
            got = sweep(engine, "i", calls, shards, range(n_rows))
            assert got == [len(expected[r]) for r in range(n_rows)]
            snap = engine.tier.snapshot()
            # Every corrupted file was detected exactly once and deleted
            # (the re-sweep's own evictions may re-spill under the same
            # deterministic names — those are fresh, valid images).
            assert snap["corrupt_spills"] == len(files)
        finally:
            engine.close()

    def test_missing_spill_file_regathers(self, holder, tmp_path):
        n_rows = 6
        fld, expected = plant(holder, 2, n_rows)
        shards = (0, 1)
        calls = {r: parse(f"Row(f={r})").calls[0] for r in range(n_rows)}
        engine = self._spill_engine(holder, tmp_path)
        try:
            sweep(engine, "i", calls, shards, range(n_rows))
            engine.tier.drain()
            for name in os.listdir(tmp_path / "spill"):
                os.remove(tmp_path / "spill" / name)
            got = sweep(engine, "i", calls, shards, range(n_rows))
            assert got == [len(expected[r]) for r in range(n_rows)]
        finally:
            engine.close()

    def test_disk_budget_evicts_oldest_spill(self, holder, tmp_path):
        fld, _ = plant(holder, 2, 8)
        shards = (0, 1)
        calls = {r: parse(f"Row(f={r})").calls[0] for r in range(8)}
        engine = tiny_engine(
            holder, 1, 2,
            tier=TierConfig(host_bytes=4096, disk_bytes=6000,
                            disk_path=str(tmp_path / "spill"),
                            prefetch_interval=0))
        try:
            sweep(engine, "i", calls, shards, range(8))
            engine.tier.drain()
            snap = engine.tier.snapshot()
            assert snap["disk_bytes"] <= 6000
            assert snap["disk_evictions"] > 0
        finally:
            engine.close()


# ----------------------------------------------------- predictive prefetch


class TestPrefetch:
    def test_hot_index_promoted_before_query(self, holder):
        n_rows = 6
        fld, expected = plant(holder, 2, n_rows)
        shards = (0, 1)
        calls = {r: parse(f"Row(f={r})").calls[0] for r in range(n_rows)}
        traffic = {"n": 1}
        engine = ShardedQueryEngine(
            holder,
            config=EngineConfig(
                leaf_cache_bytes=4 * n_rows * 2 * WORDS_PER_ROW * 4),
            tier_config=TierConfig(host_bytes=1 << 28,
                                   prefetch_interval=0.01,
                                   prefetch_batch=8),
            traffic_fn=lambda: {"i": traffic["n"]})
        try:
            for r in range(n_rows):
                engine.tier.demote(("i", Leaf("f", "standard", r), shards))
            engine.tier.drain()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                traffic["n"] += 1
                if engine.tier.snapshot()["prefetch_promotions"] >= n_rows:
                    break
                time.sleep(0.02)
            assert engine.tier.snapshot()["prefetch_promotions"] >= n_rows
            base = dict(engine.counters)
            got = sweep(engine, "i", calls, shards, range(n_rows))
            assert got == [len(expected[r]) for r in range(n_rows)]
            # Every plane was already HBM-resident: zero query-path work.
            assert engine.counters["leaf_misses"] == base["leaf_misses"]
            assert engine.counters["leaf_tier_hits"] == base["leaf_tier_hits"]
            assert engine.tier.snapshot()["prefetch_hits"] >= 1
        finally:
            engine.close()

    def test_cold_index_not_promoted(self, holder):
        fld, _ = plant(holder, 2, 4)
        shards = (0, 1)
        engine = ShardedQueryEngine(
            holder,
            config=EngineConfig(leaf_cache_bytes=1 << 26),
            tier_config=TierConfig(host_bytes=1 << 28,
                                   prefetch_interval=0.01),
            traffic_fn=lambda: {"other-index": 1})  # never increases
        try:
            for r in range(4):
                engine.tier.demote(("i", Leaf("f", "standard", r), shards))
            engine.tier.drain()
            time.sleep(0.2)
            assert engine.tier.snapshot()["prefetch_promotions"] == 0
        finally:
            engine.close()

    def test_prefetch_never_evicts(self):
        m = TierManager(holder=None, config=TierConfig(
            host_bytes=1 << 20, prefetch_interval=0))
        promoted = []
        m.bind(promote_fn=lambda k: promoted.append(k) or True,
               headroom_fn=lambda: 0,  # no free HBM
               resident_fn=lambda k: False)
        # Seed a fake host entry and run one sweep body inline.
        from pilosa_tpu.tier.manager import _PlaneEntry

        with m._lock:
            m._host[("i", Leaf("f", "standard", 0), (0,))] = _PlaneEntry(
                [(0, 0)], [b"x"])
        # One manual sweep: headroom 0 → nothing promoted.
        m.config.prefetch_interval = 0.01
        m._stop.clear()
        t = threading.Thread(target=m._prefetch_loop, daemon=True)
        t.start()
        time.sleep(0.1)
        m.close()
        assert promoted == []


# ------------------------------- engine byte-cache policies (satellites)


class TestByteCachePolicies:
    def test_oversized_entry_admitted_alone_and_counted(self, holder):
        plant(holder, 1, 1)
        engine = ShardedQueryEngine(
            holder, tier_config=TierConfig(host_bytes=0, disk_bytes=0))
        try:
            cache, used, budget = {}, 0, 100
            evicted = []
            with engine._lock:
                used = engine._byte_cache_put(
                    cache, "a", ((), np.zeros(40, np.uint8)), budget, used,
                    "leaf_evictions", evicted)
                used = engine._byte_cache_put(
                    cache, "b", ((), np.zeros(40, np.uint8)), budget, used,
                    "leaf_evictions", evicted)
                used = engine._byte_cache_put(
                    cache, "huge", ((), np.zeros(500, np.uint8)), budget,
                    used, "leaf_evictions", evicted)
            # Admitted ALONE: everything else evicted, accounting exact.
            assert list(cache) == ["huge"]
            assert used == 500
            assert engine.counters["oversized_admits"] == 1
            assert evicted == ["a", "b"]
            # The next insert immediately evicts back under budget.
            with engine._lock:
                used = engine._byte_cache_put(
                    cache, "c", ((), np.zeros(60, np.uint8)), budget, used,
                    "leaf_evictions", evicted)
            assert "huge" not in cache and used == 60
            assert "huge" in evicted
        finally:
            engine.close()

    def test_memo_and_aux_eviction_counters(self, holder):
        plant(holder, 1, 4)
        engine = ShardedQueryEngine(
            holder,
            config=EngineConfig(memo_entries=2, aux_memo_entries=2),
            tier_config=TierConfig(host_bytes=0, disk_bytes=0))
        try:
            shards = (0,)
            for r in range(4):
                engine.count("i", parse(f"Row(f={r})").calls[0], shards)
            assert engine.counters["memo_evictions"] >= 2
            for k in range(4):
                engine._aux_store((("k", k), ("fp",)), ("fp",), k)
            assert engine.counters["aux_evictions"] >= 2
        finally:
            engine.close()


# ------------------------------------------- budgets + config resolution


class TestBudgetResolution:
    def _mk(self, holder, **kw):
        return ShardedQueryEngine(
            holder, tier_config=TierConfig(host_bytes=0, disk_bytes=0), **kw)

    def test_engine_config_budgets_apply(self, holder):
        plant(holder, 1, 1)
        engine = self._mk(holder, config=EngineConfig(
            leaf_cache_bytes=111, stack_cache_bytes=222, memo_entries=33,
            aux_memo_entries=44))
        try:
            assert engine.budgets["leaf_cache_bytes"] == 111
            assert engine.budgets["stack_cache_bytes"] == 222
            assert engine.budgets["memo_entries"] == 33
            assert engine.budgets["aux_memo_entries"] == 44
        finally:
            engine.close()

    def test_legacy_env_beats_config(self, holder, monkeypatch):
        plant(holder, 1, 1)
        monkeypatch.setenv("PILOSA_LEAF_CACHE_BYTES", "777")
        monkeypatch.setenv("PILOSA_MEMO_ENTRIES", "0")
        engine = self._mk(holder, config=EngineConfig(
            leaf_cache_bytes=111, memo_entries=33))
        try:
            assert engine.budgets["leaf_cache_bytes"] == 777
            # env can express "0 entries"; config 0 means auto.
            assert engine.budgets["memo_entries"] == 0
        finally:
            engine.close()

    def test_tier_hbm_bytes_splits_device_budget(self, holder):
        plant(holder, 1, 1)
        engine = ShardedQueryEngine(
            holder,
            tier_config=TierConfig(hbm_bytes=1 << 20, host_bytes=0,
                                   disk_bytes=0))
        try:
            assert engine.budgets["leaf_cache_bytes"] == 1 << 19
            assert engine.budgets["stack_cache_bytes"] == 1 << 19
        finally:
            engine.close()

    def test_explicit_engine_budget_beats_hbm_split(self, holder):
        plant(holder, 1, 1)
        engine = ShardedQueryEngine(
            holder, config=EngineConfig(leaf_cache_bytes=12345),
            tier_config=TierConfig(hbm_bytes=1 << 20, host_bytes=0,
                                   disk_bytes=0))
        try:
            assert engine.budgets["leaf_cache_bytes"] == 12345
            assert engine.budgets["stack_cache_bytes"] == 1 << 19
        finally:
            engine.close()

    def test_tier_config_validate(self):
        with pytest.raises(ValueError):
            TierConfig(host_bytes=-1).validate()
        with pytest.raises(ValueError):
            TierConfig(prefetch_interval=-0.1).validate()
        with pytest.raises(ValueError):
            TierConfig(prefetch_batch=0).validate()
        assert not TierConfig(host_bytes=0, disk_bytes=0).enabled()
        assert TierConfig(host_bytes=1).enabled()
        # Disk-only needs a path to be usable.
        assert not TierConfig(host_bytes=0, disk_bytes=1).enabled()
        assert TierConfig(host_bytes=0, disk_bytes=1, disk_path="/x").enabled()

    def test_config_toml_env_flags(self, tmp_path, monkeypatch):
        from pilosa_tpu.config import Config

        p = tmp_path / "c.toml"
        p.write_text(
            "[tier]\nhbm-bytes = 10\nhost-bytes = 20\ndisk-bytes = 30\n"
            'disk-path = "/tmp/sp"\nprefetch-interval = 0.5\n'
            "prefetch-batch = 9\n"
            "[engine]\nleaf-cache-bytes = 40\nstack-cache-bytes = 50\n"
            "memo-entries = 60\naux-memo-entries = 70\n")
        cfg = Config.load(str(p))
        assert (cfg.tier.hbm_bytes, cfg.tier.host_bytes,
                cfg.tier.disk_bytes) == (10, 20, 30)
        assert cfg.tier.disk_path == "/tmp/sp"
        assert cfg.tier.prefetch_interval == 0.5
        assert cfg.tier.prefetch_batch == 9
        assert cfg.engine.leaf_cache_bytes == 40
        assert cfg.engine.aux_memo_entries == 70
        # env beats file
        monkeypatch.setenv("PILOSA_TPU_TIER_HOST_BYTES", "21")
        monkeypatch.setenv("PILOSA_TPU_ENGINE_MEMO_ENTRIES", "61")
        cfg = Config.load(str(p))
        assert cfg.tier.host_bytes == 21
        assert cfg.engine.memo_entries == 61
        # flags beat env
        cfg = Config.load(str(p), flags={"tier_host_bytes": 22,
                                         "engine_memo_entries": 62})
        assert cfg.tier.host_bytes == 22
        assert cfg.engine.memo_entries == 62
        # round-trips through to_toml
        dumped = cfg.to_toml()
        assert "[tier]" in dumped and "host-bytes = 22" in dumped
        assert "leaf-cache-bytes = 40" in dumped

    def test_cli_flags_parse(self):
        from pilosa_tpu.cli import build_parser

        ns = build_parser().parse_args([
            "server", "--tier-hbm-bytes", "1", "--tier-host-bytes", "2",
            "--tier-disk-bytes", "3", "--tier-disk-path", "/s",
            "--tier-prefetch-interval", "0.25", "--tier-prefetch-batch",
            "5", "--engine-leaf-cache-bytes", "6",
            "--engine-stack-cache-bytes", "7", "--engine-memo-entries",
            "8", "--engine-aux-memo-entries", "9"])
        assert ns.tier_hbm_bytes == 1 and ns.tier_host_bytes == 2
        assert ns.tier_disk_bytes == 3 and ns.tier_disk_path == "/s"
        assert ns.tier_prefetch_interval == 0.25
        assert ns.tier_prefetch_batch == 5
        assert ns.engine_leaf_cache_bytes == 6
        assert ns.engine_stack_cache_bytes == 7
        assert ns.engine_memo_entries == 8
        assert ns.engine_aux_memo_entries == 9


# ------------------------------------------------- scheduler traffic signal


def test_scheduler_traffic_evicts_by_recency_not_count():
    """A full traffic table must evict the least-recently-touched index,
    never the lowest lifetime count — otherwise newly-created busy
    indexes would perpetually evict each other while idle-but-
    historically-hot indexes squat the table."""
    from pilosa_tpu.sched import QueryScheduler, SchedulerConfig

    sched = QueryScheduler(SchedulerConfig())
    sched._index_traffic_max = 4
    for i in range(4):
        for _ in range(100):
            sched.note_index(f"old{i}")
    # Two new actively-queried indexes alternate; the OLD idle entries
    # must be evicted, and the active pair must both survive.
    for _ in range(5):
        sched.note_index("a")
        sched.note_index("b")
    t = sched.index_traffic()
    assert t["a"] == 5 and t["b"] == 5, t
    assert len(t) == 4


# ----------------------------------------------------- server observability


def test_debug_vars_tier_group_and_budgets(tmp_path):
    from pilosa_tpu.server.server import Server
    from pilosa_tpu.tier import TierConfig as TC

    s = Server(data_dir=str(tmp_path / "node"), cache_flush_interval=0,
               member_monitor_interval=0,
               tier_config=TC(host_bytes=1 << 24, disk_bytes=1 << 20))
    s.open()
    try:
        # Disk path defaulted under the data dir.
        assert s.executor.tier_config.disk_path.endswith("tier-spill")
        # Traffic signal wired scheduler → executor → engine.
        assert s.executor.tier_traffic_fn is not None
        s.api.create_index("dv")
        s.api.create_field("dv", "f")
        s.api.query("dv", "Set(3, f=1)")
        s.api.query("dv", "Count(Row(f=1))")
        with urllib.request.urlopen(
                f"http://localhost:{s.port}/debug/vars") as r:
            dv = json.load(r)
        tier = dv["tier"]
        for key in ("host_bytes", "host_entries", "disk_bytes",
                    "demotions_host", "promotions_host", "delta_folds",
                    "prefetch_promotions", "prefetch_hits",
                    "corrupt_spills", "host_budget", "disk_budget"):
            assert key in tier, key
        budgets = dv["engine_budgets"]
        for key in ("leaf_cache_bytes", "stack_cache_bytes",
                    "memo_entries", "aux_memo_entries"):
            assert key in budgets, key
        # The scheduler's traffic counters rode the query above.
        assert dv["scheduler"]["index_traffic"].get("dv", 0) >= 1
        # Diagnostics aggregates include the tier group.
        info = s.diagnostics.gather()
        assert "tierHostBytes" in info
        assert "tierPromotions" in info
    finally:
        s.close()
