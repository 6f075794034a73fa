"""The engine's fingerprint cache: `_fingerprint` asks a view's fragments
for their generations once per write epoch and serves that walk to every
row of the view until the epoch moves.

What is held here: the cached answer is the one a walk would give, after
every mutation path and after every way a view gains or loses a fragment;
it engages as often as it should (counts, no timing); a walk that
overlaps a write is not trusted afterwards; and an entry pins no storage.
"""

import gc
import os
import shutil
import sys
import threading
import time
import weakref

import numpy as np
import pytest

from pilosa_tpu.cluster.hash import ModHasher
from pilosa_tpu.cluster.node import Cluster, Node
from pilosa_tpu.cluster.topology import HolderCleaner
from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.parallel import engine as engine_mod
from pilosa_tpu.parallel.engine import Leaf, ShardedQueryEngine
from pilosa_tpu.pql.parser import parse
from pilosa_tpu.translate import TranslateStore

from .test_delta import MUTATIONS
from .test_misc import _FakeServer

SHARDS = tuple(range(4))
LEAF = Leaf("f", "standard", 0)


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


@pytest.fixture
def engine(holder):
    e = ShardedQueryEngine(holder)
    yield e
    e.close()


def plant(holder, shards=SHARDS, field="f"):
    """Index `i`, a set field, one bit of row 0 in every shard (bit 0 of
    shard 0 among them, which MUTATIONS' clear_bit clears)."""
    fld = holder.create_index_if_not_exists("i").create_field_if_not_exists(
        field)
    for s in shards:
        fld.set_bit(0, s * SHARD_WIDTH)
    return fld


def walk(engine, leaf=LEAF, shards=SHARDS, index="i"):
    """What the fragments say now, past the cache."""
    return engine._leaf_fragments(index, leaf, shards)[1]


def cached(engine, leaf=LEAF, shards=SHARDS, index="i"):
    return engine._fingerprint(index, leaf, shards)


def grew(engine, before):
    return {k: engine.counters[k] - before[k] for k in ("fp_hits", "fp_walks")}


# ------------------------------------------- (a) every mutation path


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_cached_fingerprint_follows_every_mutation_path(holder, engine, name):
    """test_delta's audit again, one level up: after each path the cache
    answers what a walk answers, and not what it answered before."""
    plant(holder)
    before = cached(engine)
    assert cached(engine) is before  # served, the same object
    MUTATIONS[name](holder.fragment("i", "f", "standard", 0))
    after = cached(engine)
    assert after == walk(engine)
    assert after != before, f"{name} left the fingerprint as it was"
    assert after[1:] == before[1:]  # the other shards' fragments stand


class _SpyEpoch:
    """A fragment's epoch that notes the fragment's generation at every
    bump, then bumps."""

    def __init__(self, frag):
        self.frag, self.real, self.seen = frag, frag.epoch, []

    def bump(self):
        self.seen.append(self.frag.generation)
        self.real.bump()


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_every_mutation_path_moves_its_generation_before_the_epoch(
        holder, name):
    """The order the cache is exact by: whoever sees the epoch moved finds
    the generation moved already, and no generation moves after the
    path's last bump. The other way round, a walk between the two would
    be kept under the NEW epoch with the OLD generation."""
    plant(holder)
    frag = holder.fragment("i", "f", "standard", 0)
    g0 = frag.generation
    frag.epoch = spy = _SpyEpoch(frag)
    MUTATIONS[name](frag)
    assert spy.seen and min(spy.seen) > g0
    assert spy.seen[-1] == frag.generation


# ------------------------- (b) fragments that come and go unmutated


def test_a_fragment_created_in_a_view_is_seen(holder, engine):
    fld = plant(holder, shards=(0, 1, 2))
    before = cached(engine)
    assert before[3] == -1
    # What a peer's create-shard message does: no bit is set.
    fld.view("standard").create_fragment_if_not_exists(3, broadcast=False)
    after = cached(engine)
    assert after == walk(engine)
    assert after[3] != -1 and after[:3] == before[:3]


def test_a_view_created_and_deleted_is_seen(holder, engine):
    fld = plant(holder)
    leaf = Leaf("f", "standard_2018", 0)
    assert cached(engine, leaf) == (-1,) * 4
    view = fld.create_view_if_not_exists("standard_2018")
    view.create_fragment_if_not_exists(1, broadcast=False)
    seen = cached(engine, leaf)
    assert seen == walk(engine, leaf) and seen[1] != -1
    fld.delete_view("standard_2018")
    assert cached(engine, leaf) == walk(engine, leaf) == (-1,) * 4
    # The files stayed, so the view comes back with its fragment.
    fld.create_view_if_not_exists("standard_2018")
    back = cached(engine, leaf)
    assert back == walk(engine, leaf)
    assert back[1] != -1 and back != seen  # a new incarnation


def test_clean_holder_dropping_a_fragment_is_seen(holder, engine):
    plant(holder)
    before = cached(engine)
    nodes = [Node(id="me"), Node(id="other")]
    cluster = Cluster(node=nodes[0], nodes=nodes, hasher=ModHasher())
    removed = HolderCleaner(_FakeServer(holder, cluster)).clean_holder()
    assert removed  # the test is about a fragment that went
    after = cached(engine)
    assert after == walk(engine)
    assert after.count(-1) == len(removed) and after != before


def test_a_field_deleted_and_recreated_is_seen(holder, engine):
    plant(holder)
    before = cached(engine)
    idx = holder.index("i")
    idx.delete_field("f")
    assert cached(engine) == walk(engine) == (-1,) * 4
    plant(holder)
    after = cached(engine)
    assert after == walk(engine)
    assert all(a != b for a, b in zip(after, before))  # new incarnations


def test_a_field_created_onto_a_directory_with_views_is_seen(holder, engine):
    """Restored files, or a delete_field whose rmtree failed: the new
    field opens with fragments that no mutation announced."""
    fld = plant(holder)
    leaf = Leaf("g", "standard", 0)
    assert cached(engine, leaf) == (-1,) * 4
    shutil.copytree(fld.path, os.path.join(holder.index("i").path, "g"))
    holder.index("i").create_field("g")
    got = cached(engine, leaf)
    assert got == walk(engine, leaf) and -1 not in got


def test_an_index_deleted_and_recreated_is_seen(holder, engine):
    plant(holder)
    before = cached(engine)
    holder.delete_index("i")
    walks = engine.counters["fp_walks"]
    assert cached(engine) == (-1,) * 4
    assert cached(engine) == (-1,) * 4
    # No index, no epoch to say when that changes: never kept.
    assert engine.counters["fp_walks"] == walks + 2
    assert not any(k[0] == "i" and e[0] == -1
                   for k, e in engine._fp_cache.items())
    plant(holder)
    after = cached(engine)
    assert after == walk(engine)
    assert all(a != b for a, b in zip(after, before))


# ------------------------------------------- (c) how often it engages


def test_one_walk_serves_every_row_of_a_view_until_a_write(holder, engine):
    """TopN's 48 candidate rows over 64 shards: one walk, 47 hits, all the
    same object; a Set makes the next probe walk once."""
    shards = tuple(range(64))
    fld = holder.create_index("i").create_field("f")
    fld.set_bit(0, 63 * SHARD_WIDTH + 5)
    leaves = [Leaf("f", "standard", r) for r in range(48)]
    c0 = dict(engine.counters)
    fps = [engine._fingerprint("i", leaf, shards) for leaf in leaves]
    assert grew(engine, c0) == {"fp_hits": 47, "fp_walks": 1}
    assert all(fp is fps[0] for fp in fps)
    assert fps[0] == walk(engine, leaves[0], shards)

    fld.set_bit(7, 12 * SHARD_WIDTH)  # creates shard 12's fragment too
    c1 = dict(engine.counters)
    again = [engine._fingerprint("i", leaf, shards) for leaf in leaves]
    assert grew(engine, c1) == {"fp_hits": 47, "fp_walks": 1}
    assert again[0] == walk(engine, leaves[0], shards) != fps[0]
    # Another view, another shard tuple: entries of their own.
    c2 = dict(engine.counters)
    engine._fingerprint("i", Leaf("f", "standard_2018", 0), shards)
    engine._fingerprint("i", leaves[0], shards[:32])
    engine._fingerprint("i", leaves[1], shards[:32])
    assert grew(engine, c2) == {"fp_hits": 1, "fp_walks": 2}


def test_the_counters_reach_debug_vars(holder, engine):
    plant(holder)
    cached(engine), cached(engine)
    snap = engine.snapshot()
    assert (snap["fp_walks"], snap["fp_hits"]) == (1, 1)


def test_the_cache_is_bounded(holder, engine, monkeypatch):
    monkeypatch.setattr(engine_mod, "_FP_CACHE_ENTRIES", 4)
    fld = plant(holder)
    for n in range(1, 5):
        cached(engine, shards=SHARDS[:n])
    fld.set_bit(3, 3)
    cached(engine, shards=SHARDS[:1])  # walked again: now the newest
    cached(engine, Leaf("g", "standard", 0))  # a fifth entry
    assert len(engine._fp_cache) == 4
    keys = [k[3] for k in engine._fp_cache if k[1] == "f"]
    assert keys == [SHARDS[:3], SHARDS[:4], SHARDS[:1]]  # oldest walk went


# ------------------------- (d) a write between the token and the walk


def test_a_walk_that_overlapped_a_write_is_not_trusted(
        holder, engine, monkeypatch):
    """The epoch is read before the walk. A write that lands between the
    two leaves an entry whose fingerprint is newer than its token: right,
    and not believed by the next probe, which walks again."""
    fld = plant(holder)
    real = engine._leaf_fragments
    late = []

    def walk_after_a_write(index, leaf, shards):
        if late:
            fld.set_bit(0, late.pop() * SHARD_WIDTH + 9)
        return real(index, leaf, shards)

    monkeypatch.setattr(engine, "_leaf_fragments", walk_after_a_write)
    token0 = engine._epoch_token("i")
    late.append(2)
    got = cached(engine)
    assert got == walk(engine)  # it holds the write already
    assert engine._fp_cache[("i", "f", "standard", SHARDS)] == (token0, got)
    assert engine._epoch_token("i") != token0
    c0 = dict(engine.counters)
    assert cached(engine) == got
    assert grew(engine, c0) == {"fp_hits": 0, "fp_walks": 1}
    assert cached(engine) is cached(engine)  # and now it is believed


def test_a_refresh_stamps_what_its_fragments_say(holder, engine, monkeypatch):
    """_gather_leaf reads data only on a refresh, looks the fragments up
    only there, and stamps the plane with THEIR fingerprint: a second
    write whose epoch bump has not landed yet (generation first, epoch
    last) is in the plane and in its stamp, though the epoch's
    fingerprint does not know of it."""
    fld = plant(holder)
    engine._gather_leaf("i", LEAF, SHARDS)
    frag = holder.fragment("i", "f", "standard", 1)
    assert fld.set_bit(0, SHARD_WIDTH + 77)  # makes the resident plane stale
    at_epoch = cached(engine)
    monkeypatch.setattr(frag, "epoch", None)  # a writer short of its bump
    assert fld.set_bit(0, SHARD_WIDTH + 78)
    assert cached(engine) is at_epoch != walk(engine)
    arr = np.asarray(engine._gather_leaf("i", LEAF, SHARDS))
    np.testing.assert_array_equal(arr[1], frag.plane_np(0))
    assert engine._leaf_cache[("i", LEAF, SHARDS)][0] == walk(engine)


def test_readers_under_writers_never_see_less_than_was_acknowledged(
        holder, engine):
    """More threads than cores, a short switch interval, half a second:
    a fingerprint handed out never lacks a write acknowledged before it
    was asked for, and the two counters lose no update."""
    fld = plant(holder)
    frags = [holder.fragment("i", "f", "standard", s) for s in SHARDS]
    acked = [f.generation for f in frags]  # by shard, written by its writer
    stop = threading.Event()
    calls, wrong = [], []
    real, walked, mu = engine._leaf_fragments, [0], threading.Lock()

    def counted_walk(index, leaf, shards):
        with mu:
            walked[0] += 1
        return real(index, leaf, shards)

    engine._leaf_fragments = counted_walk

    def writer(s):
        k = 1
        while not stop.is_set():
            fld.set_bit(1 + k % 7, s * SHARD_WIDTH + k)
            acked[s] = frags[s].generation  # after the write returned
            k += 1

    def reader():
        n = 0
        while not stop.is_set():
            floor = list(acked)
            fp = engine._fingerprint("i", Leaf("f", "standard", n % 5), SHARDS)
            n += 1
            late = [s for s in SHARDS if fp[s][1] < floor[s]]
            if late:
                wrong.append((fp, floor))
        calls.append(n)

    n_readers = 2 * (os.cpu_count() or 4)
    threads = [threading.Thread(target=writer, args=(s,)) for s in SHARDS]
    threads += [threading.Thread(target=reader) for _ in range(n_readers)]
    c0 = dict(engine.counters)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []
    assert len(calls) == n_readers and sum(calls) > n_readers
    did = grew(engine, c0)
    # Walks are counted under the lock; a hit takes none, so its count
    # may lose a bump between two threads and never gain one.
    assert did["fp_walks"] == walked[0] > 0
    assert 0 < did["fp_hits"] <= sum(calls) - walked[0]
    engine._leaf_fragments = real
    assert cached(engine) == walk(engine)  # quiet again: the last word


# --------------------------------------- (e) through the executor


def test_interleaved_writes_and_reads_match_the_reference(holder):
    """Set / Count / filtered TopN / BSI Sum through the executor, memo
    on: every answer is the reference's, at every step."""
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("g")
    idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    ex = Executor(holder, translate_store=TranslateStore().open(), workers=0)
    rng = np.random.default_rng(29)
    n_cols = 3 * SHARD_WIDTH
    rows = {"f": {r: set() for r in range(4)}, "g": {r: set() for r in range(3)}}
    vals = {}
    try:
        for step in range(40):
            col = int(rng.integers(n_cols))
            field = "fg"[step % 2]
            row = int(rng.integers(len(rows[field])))
            ex.execute("i", f"Set({col}, {field}={row})")
            rows[field][row].add(col)
            if step % 3 == 0:
                vals[col] = int(rng.integers(1000))
                ex.execute("i", f"SetValue(col={col}, v={vals[col]})")
            a, b = int(rng.integers(4)), int(rng.integers(3))
            for _ in range(2):  # the second from whatever was kept
                assert ex.execute(
                    "i", f"Count(Intersect(Row(f={a}), Row(g={b})))"
                ) == [len(rows["f"][a] & rows["g"][b])]
                assert ex.execute("i", f"Count(Row(f={a}))") == [
                    len(rows["f"][a])]
                top = ex.execute("i", f"TopN(f, Row(g={b}), n=4)")[0]
                want = {r: len(cols & rows["g"][b])
                        for r, cols in rows["f"].items()}
                assert {p.id: p.count for p in top} == {
                    r: n for r, n in want.items() if n}
                inside = [v for c, v in vals.items() if c in rows["g"][b]]
                assert ex.execute("i", f"Sum(Row(g={b}), field=v)")[
                    0].to_dict() == {"value": sum(inside),
                                     "count": len(inside)}
        c = ex.engine.counters
        assert c["fp_hits"] > c["fp_walks"] > 0
        assert c["memo_hits"] > 0 and c["count_dispatches"] > 0
    finally:
        ex.close()


# ------------------------------------------- (f) nothing is pinned


def test_an_entry_holds_no_fragment(holder, engine):
    """A deleted field's fragments are collectable with the cache full of
    entries about them."""
    plant(holder)
    for n in range(1, 5):
        cached(engine, shards=SHARDS[:n])
    engine.count("i", parse("Row(f=0)").calls[0], list(SHARDS))
    refs = [weakref.ref(holder.fragment("i", "f", "standard", s))
            for s in SHARDS]
    assert len(engine._fp_cache) >= 4
    holder.index("i").delete_field("f")
    gc.collect()
    assert [r() for r in refs] == [None] * 4
