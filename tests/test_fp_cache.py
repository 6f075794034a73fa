"""The engine's staleness check: a cache entry carries the stamp of its
view's change journal (core/fragment.py ChangeJournal), `_fingerprint`
reads that stamp and touches no fragment, and a stale entry asks the
journal what was written since.

What is held here: what the engine serves after every mutation path and
after every way a view gains or loses a fragment is what the fragments
hold; the journal answers as often as it should and the walk over the
fragments is taken only where it cannot (counts, no timing); a stamp is
read before the data it covers; and the journal pins no storage.
"""

import gc
import io
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
from pilosa_tpu.constants import SHARD_WIDTH, WORDS_PER_ROW
from pilosa_tpu.core import fragment as fragment_mod
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.fragment import ALL_ROWS, ChangeJournal, Fragment
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.obs import trace as obs_trace
from pilosa_tpu.parallel.engine import (
    DELTA_MIN_UPDATES, Leaf, ShardedQueryEngine)
from pilosa_tpu.pql.parser import parse
from pilosa_tpu.translate import TranslateStore

from .test_delta import MUTATIONS, unfolded
from .test_misc import _FakeServer

SHARDS = tuple(range(4))
LEAF = Leaf("f", "standard", 0)
# The rows MUTATIONS' paths write between them (BSI planes included).
ROWS = tuple(range(10))


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
    """What the fragments say now: the per-shard pairs of a walk."""
    return engine._leaf_fragments(index, leaf, shards)[1]


def cached(engine, leaf=LEAF, index="i"):
    """The fingerprint the caches compare: the stamp of the leaf's view."""
    return engine._fingerprint(index, (leaf,))


def journal_of(holder, leaf=LEAF, index="i"):
    return holder.index(index).field(leaf.field).view(leaf.view).journal


def served(engine, leaf=LEAF, shards=SHARDS, index="i"):
    """The plane the engine serves, whatever it kept of it."""
    return np.asarray(engine._gather_leaf(index, leaf, shards))[:len(shards)]


def truth(holder, leaf=LEAF, shards=SHARDS, index="i"):
    """The same plane read from the fragments, past every cache."""
    out = np.zeros((len(shards), WORDS_PER_ROW), np.uint32)
    for i, s in enumerate(shards):
        frag = holder.fragment(index, leaf.field, leaf.view, s)
        if frag is not None:
            out[i] = frag.plane_np(leaf.row)
    return out


def grew(engine, before, keys=("fp_journal_reads", "fp_walks")):
    return {k: engine.counters[k] - before[k] for k in keys}


# ------------------------------------------- (a) every mutation path


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_cached_fingerprint_follows_every_mutation_path(holder, engine, name):
    """test_delta's audit again, one level up: after each path the
    fingerprint has moved within its incarnation, and every plane the
    engine had resident is served as the fragments hold it now."""
    plant(holder)
    leaves = [Leaf("f", "standard", r) for r in ROWS]
    for leaf in leaves:
        served(engine, leaf)  # resident, and stamped before the write
    before, pairs = cached(engine), walk(engine)
    assert cached(engine) == before
    MUTATIONS[name](holder.fragment("i", "f", "standard", 0))
    after = cached(engine)
    assert after != before, f"{name} left the fingerprint as it was"
    assert after[0][0] == before[0][0] and after[0][1] > before[0][1]
    assert walk(engine)[1:] == pairs[1:]  # the other shards' fragments stand
    for leaf in leaves:
        np.testing.assert_array_equal(served(engine, leaf), truth(holder, leaf))


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
    """The order the memo is exact by: whoever sees the epoch moved finds
    the generation moved already, and no generation moves after the
    path's last bump. (That the journal stands between the two is held by
    test_delta's audit.)"""
    plant(holder)
    frag = holder.fragment("i", "f", "standard", 0)
    g0 = frag.generation
    frag.epoch = spy = _SpyEpoch(frag)
    MUTATIONS[name](frag)
    assert spy.seen and min(spy.seen) > g0
    assert spy.seen[-1] == frag.generation


# ------------------------- (b) fragments that come and go unmutated


def said_all(journal, old, new, shard):
    """The journal logged ALL_ROWS for `shard` between the two stamps."""
    return any(e[1:] == (shard, ALL_ROWS, None)
               for e in journal.since(old[0], new[0]))


def test_a_fragment_created_in_a_view_is_seen(holder, engine):
    fld = plant(holder, shards=(0, 1, 2))
    served(engine)
    before, pairs = cached(engine), walk(engine)
    assert pairs[3] == -1
    # What a peer's create-shard message does: no bit is set.
    fld.view("standard").create_fragment_if_not_exists(3, broadcast=False)
    after = cached(engine)
    assert after != before
    assert said_all(journal_of(holder), before, after, 3)
    assert walk(engine)[3] != -1 and walk(engine)[:3] == pairs[:3]
    c0 = dict(engine.counters)
    np.testing.assert_array_equal(served(engine), truth(holder))
    assert grew(engine, c0)["fp_walks"] == 1  # ALL_ROWS: the safe rung


def test_a_view_created_and_deleted_is_seen(holder, engine):
    fld = plant(holder)
    leaf = Leaf("f", "standard_2018", 0)
    assert cached(engine, leaf) == (-1,)
    assert not served(engine, leaf).any()
    view = fld.create_view_if_not_exists("standard_2018")
    view.create_fragment_if_not_exists(1, broadcast=False).set_bit(0, SHARD_WIDTH + 3)
    seen = cached(engine, leaf)
    assert seen == (view.journal.stamp,) != (-1,)
    np.testing.assert_array_equal(served(engine, leaf), truth(holder, leaf))
    assert served(engine, leaf).any()
    fld.delete_view("standard_2018")
    assert cached(engine, leaf) == (-1,)
    assert not served(engine, leaf).any()
    # The files stayed, so the view comes back with its fragment.
    fld.create_view_if_not_exists("standard_2018")
    back = cached(engine, leaf)
    assert back[0] != -1 and back[0][0] != seen[0][0]  # a new incarnation
    np.testing.assert_array_equal(served(engine, leaf), truth(holder, leaf))
    assert served(engine, leaf).any()


def test_clean_holder_dropping_a_fragment_is_seen(holder, engine):
    plant(holder)
    served(engine)
    before = cached(engine)
    nodes = [Node(id="me"), Node(id="other")]
    cluster = Cluster(node=nodes[0], nodes=nodes, hasher=ModHasher())
    removed = HolderCleaner(_FakeServer(holder, cluster)).clean_holder()
    assert removed  # the test is about a fragment that went
    after = cached(engine)
    assert after != before
    gone = [s for s in SHARDS if walk(engine)[s] == -1]
    assert len(gone) == len(removed)
    assert all(said_all(journal_of(holder), before, after, s) for s in gone)
    got = served(engine)
    np.testing.assert_array_equal(got, truth(holder))
    assert not got[gone].any() and got.any()


def test_a_field_deleted_and_recreated_is_seen(holder, engine):
    plant(holder)
    assert served(engine).any()
    before = cached(engine)
    idx = holder.index("i")
    idx.delete_field("f")
    assert cached(engine) == (-1,)
    assert not served(engine).any()
    plant(holder).set_bit(0, 77)
    after = cached(engine)
    assert after[0][0] != before[0][0]  # a new incarnation
    np.testing.assert_array_equal(served(engine), truth(holder))


def test_a_field_created_onto_a_directory_with_views_is_seen(holder, engine):
    """Restored files, or a delete_field whose rmtree failed: the new
    field opens with fragments that no mutation announced."""
    fld = plant(holder)
    leaf = Leaf("g", "standard", 0)
    assert cached(engine, leaf) == (-1,)
    assert not served(engine, leaf).any()
    shutil.copytree(fld.path, os.path.join(holder.index("i").path, "g"))
    holder.index("i").create_field("g")
    assert cached(engine, leaf) != (-1,)
    got = served(engine, leaf)
    np.testing.assert_array_equal(got, truth(holder, leaf))
    assert got.any() and -1 not in walk(engine, leaf)


def test_an_index_deleted_and_recreated_is_seen(holder, engine):
    plant(holder)
    assert served(engine).any()
    before = cached(engine)
    holder.delete_index("i")
    # No index, no view, no journal: nothing to compare a stamp with, and
    # the plane that was resident is not believed.
    assert cached(engine) == (-1,)
    assert not served(engine).any()
    walks = engine.counters["fp_walks"]
    plant(holder).set_bit(0, 2 * SHARD_WIDTH + 9)
    after = cached(engine)
    assert after[0][0] != before[0][0]
    np.testing.assert_array_equal(served(engine), truth(holder))
    assert engine.counters["fp_walks"] == walks + 1


# ------------------------------------------- (c) how often it engages


def test_no_walk_serves_any_row_of_a_view_before_or_after_a_write(
        holder, engine, monkeypatch):
    """TopN's 48 candidate rows over 64 shards: their fingerprint is one
    stamp, read with no fragment touched; a Set moves it, and reading it
    again touches none either."""
    shards = tuple(range(64))
    fld = holder.create_index("i").create_field("f")
    fld.set_bit(0, 63 * SHARD_WIDTH + 5)
    leaves = [Leaf("f", "standard", r) for r in range(48)]
    walked = []
    real = engine._leaf_fragments
    monkeypatch.setattr(engine, "_leaf_fragments",
                        lambda *a: walked.append(a) or real(*a))
    monkeypatch.setattr(holder, "fragment", lambda *a: walked.append(a))
    fp = engine._fingerprint("i", leaves)
    assert fp == (journal_of(holder).stamp,)
    assert all(engine._fingerprint("i", (leaf,)) == fp for leaf in leaves)

    fld.set_bit(7, 12 * SHARD_WIDTH)  # creates shard 12's fragment too
    c0 = dict(engine.counters)
    again = engine._fingerprint("i", leaves)
    assert again != fp and again[0][0] == fp[0][0]
    # Another view: a stamp of its own (there is none: -1).
    assert engine._fingerprint(
        "i", leaves[:2] + [Leaf("f", "standard_2018", 0)]) == again + (-1,)
    assert walked == []
    assert grew(engine, c0) == {"fp_journal_reads": 0, "fp_walks": 0}


def test_the_counters_reach_debug_vars(holder, engine):
    fld = plant(holder)
    served(engine)
    fld.set_bit(5, 1)  # another row: the plane is republished
    served(engine)
    snap = engine.snapshot()
    assert (snap["fp_journal_reads"], snap["leaf_republished"],
            snap["fp_walks"], snap["stack_republished"]) == (1, 1, 0, 0)


def test_the_journal_is_bounded(holder, engine, monkeypatch):
    """Between the bound and twice the bound of entries are kept; a stamp
    older than the oldest is told "cannot say", and the plane that carries
    it falls to the walk and is served exactly."""
    monkeypatch.setattr(fragment_mod, "_JOURNAL_ENTRIES", 4)
    fld = plant(holder)
    journal = journal_of(holder)
    served(engine)
    old = journal.stamp
    for n in range(1, 30):
        fld.set_bit(3, n)
        assert 1 <= len(journal._log) < 8
        recent = (old[0], journal.stamp[1] - 3)
        assert [e[0] for e in journal.since(recent, journal.stamp)] == [
            recent[1] + 1, recent[1] + 2, recent[1] + 3]
    assert journal.since(old, journal.stamp) is None
    assert journal.since(journal.stamp, journal.stamp) == ()
    c0 = dict(engine.counters)
    np.testing.assert_array_equal(served(engine), truth(holder))
    assert grew(engine, c0)["fp_walks"] == 1
    # The walk found the row unwritten: nothing moved, and it is fresh.
    assert engine.counters["leaf_delta_hits"] == c0["leaf_delta_hits"] + 1
    assert engine.counters["delta_bytes"] == c0["delta_bytes"]
    assert engine._leaf_cache[("i", LEAF, SHARDS)][0] == journal.stamp


# ------------------------- (d) a write between the stamp and the data


def test_a_walk_that_overlapped_a_write_is_not_trusted(
        holder, engine, monkeypatch):
    """The stamp is read before the fragments. A write that lands between
    the two leaves a plane newer than its stamp: right, and not believed
    by the next probe, which asks the journal, is told the cell, and reads
    its words again."""
    fld = plant(holder)
    real = engine._leaf_fragments
    late = []

    def walk_after_a_write(index, leaf, shards):
        if late:
            fld.set_bit(0, late.pop() * SHARD_WIDTH + 9)
        return real(index, leaf, shards)

    monkeypatch.setattr(engine, "_leaf_fragments", walk_after_a_write)
    stamp0 = cached(engine)
    late.append(2)
    got = served(engine)
    np.testing.assert_array_equal(got, truth(holder))  # it holds the write
    assert (engine._leaf_cache[("i", LEAF, SHARDS)][0],) == stamp0
    assert cached(engine) != stamp0
    c0 = dict(engine.counters)
    np.testing.assert_array_equal(served(engine), got)
    assert grew(engine, c0, ("fp_walks", "leaf_delta_hits", "leaf_hits")) == {
        "fp_walks": 0, "leaf_delta_hits": 1, "leaf_hits": 0}
    c1 = dict(engine.counters)
    served(engine)  # and now it is believed
    assert grew(engine, c1, ("leaf_hits", "fp_journal_reads")) == {
        "leaf_hits": 1, "fp_journal_reads": 0}


def test_a_refresh_stamps_what_the_journal_said_before_its_data(
        holder, engine, monkeypatch):
    """A writer short of its epoch bump (generation, journal, THEN epoch)
    is in the journal already: the fingerprint has moved though the epoch
    has not, and a refresh puts the write in the plane and stamps the
    plane with the stamp that covers it."""
    fld = plant(holder)
    served(engine)
    frag = holder.fragment("i", "f", "standard", 1)
    assert fld.set_bit(0, SHARD_WIDTH + 77)  # makes the resident plane stale
    at_epoch, token = cached(engine), engine._epoch_token("i")
    monkeypatch.setattr(frag, "epoch", None)  # a writer short of its bump
    assert fld.set_bit(0, SHARD_WIDTH + 78)
    assert engine._epoch_token("i") == token
    assert cached(engine) != at_epoch
    arr = served(engine)
    np.testing.assert_array_equal(arr[1], frag.plane_np(0))
    assert (engine._leaf_cache[("i", LEAF, SHARDS)][0],) == cached(engine)


def test_readers_under_writers_never_see_less_than_was_acknowledged(
        holder, engine):
    """More threads than cores, a short switch interval, half a second:
    a fingerprint handed out never lacks a write acknowledged before it
    was asked for, no entry is lost between writers of one view, and
    nobody walks."""
    fld = plant(holder)
    journal = journal_of(holder)
    acked = [journal.stamp[1]] * len(SHARDS)  # by shard, by its writer
    wrote = [0] * len(SHARDS)
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
            assert fld.set_bit(1 + k % 7, s * SHARD_WIDTH + k)
            acked[s] = journal.stamp[1]  # after the write returned
            wrote[s] = k
            k += 1

    def reader():
        n = 0
        while not stop.is_set():
            floor = max(acked)
            fp = engine._fingerprint("i", (Leaf("f", "standard", n % 5),))
            n += 1
            if fp[0][1] < floor:
                wrong.append((fp, floor))
        calls.append(n)

    n_readers = 2 * (os.cpu_count() or 4)
    threads = [threading.Thread(target=writer, args=(s,)) for s in SHARDS]
    threads += [threading.Thread(target=reader) for _ in range(n_readers)]
    seq0 = journal.stamp[1]
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
    # One entry a write, none lost between four writers under four
    # mutexes: the journal's own lock.
    assert journal.stamp[1] - seq0 == sum(wrote) > 0
    assert journal._log[-1][0] == journal.stamp[1]
    seqs = [e[0] for e in journal._log]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert grew(engine, c0)["fp_walks"] == walked[0] == 0
    engine._leaf_fragments = real
    np.testing.assert_array_equal(served(engine), truth(holder))


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
        assert c["fp_journal_reads"] > c["fp_walks"]
        assert c["leaf_republished"] > 0 and c["leaf_delta_hits"] > 0
        assert c["memo_hits"] > 0 and c["count_dispatches"] > 0
    finally:
        ex.close()


# ------------------------------------------- (f) nothing is pinned


def test_a_journal_holds_no_fragment(holder, engine):
    """A deleted field's fragments are collectable with its journal alive
    and full of entries about them, and with the engine's caches full of
    planes stamped by it."""
    plant(holder)
    journal = journal_of(holder)
    for n in range(1, 5):
        served(engine, shards=SHARDS[:n])
        holder.index("i").field("f").set_bit(n, n)
    engine.count("i", parse("Row(f=0)").calls[0], list(SHARDS))
    refs = [weakref.ref(holder.fragment("i", "f", "standard", s))
            for s in SHARDS]
    assert len(journal._log) >= 8 and len(engine._leaf_cache) >= 4
    holder.index("i").delete_field("f")
    gc.collect()
    assert [r() for r in refs] == [None] * 4
    assert not any(isinstance(x, Fragment) for e in journal._log for x in e)


# ------------- (g) the journal's three answers, and what each one costs


def spans_of(fn):
    """Names of the spans `fn` opened under a sampled trace."""
    trace = obs_trace.Trace("test")
    token = obs_trace.activate(trace)
    try:
        fn()
    finally:
        obs_trace.deactivate(token)
    return [s.name for s in trace.spans]


def test_a_republished_leaf_touches_no_fragment(holder, engine, monkeypatch):
    """A write to ANOTHER row of the view: the stale plane is fresh again
    from inside the probe: one journal read, `leaf_republished`, no delta,
    no `gather` span, no gate, and not one fragment looked up."""
    fld = plant(holder)
    before = served(engine)
    entry = engine._leaf_cache[("i", LEAF, SHARDS)]
    assert fld.set_bit(5, 2 * SHARD_WIDTH + 1)
    touched = []
    monkeypatch.setattr(holder, "fragment", lambda *a: touched.append(a))
    monkeypatch.setattr(engine, "_leaf_fragments",
                        lambda *a: touched.append(a))
    c0 = dict(engine.counters)
    names = spans_of(lambda: np.testing.assert_array_equal(
        served(engine), before))
    assert touched == [] and "gather" not in names
    assert grew(engine, c0, (
        "leaf_republished", "leaf_delta_hits", "fp_journal_reads",
        "fp_walks", "leaf_hits", "delta_bytes")) == {
        "leaf_republished": 1, "leaf_delta_hits": 0, "fp_journal_reads": 1,
        "fp_walks": 0, "leaf_hits": 0, "delta_bytes": 0}
    assert not engine._building
    now = engine._leaf_cache[("i", LEAF, SHARDS)]
    assert (now[0],) == cached(engine) != (entry[0],)
    assert now[1] is entry[1] and now[2] is entry[2]
    c1 = dict(engine.counters)
    served(engine)
    assert grew(engine, c1, ("leaf_hits", "fp_journal_reads")) == {
        "leaf_hits": 1, "fp_journal_reads": 0}


def test_a_named_cell_is_the_only_fragment_touched(holder, engine, monkeypatch):
    """A write to the plane's own row in one shard: the journal names the
    cell, and the delta looks up that shard's fragment and no other."""
    fld = plant(holder)
    served(engine)
    assert fld.set_bit(0, 2 * SHARD_WIDTH + 640)
    assert fld.set_bit(0, 2 * SHARD_WIDTH + 7)   # the same cell twice
    assert fld.set_bit(3, SHARD_WIDTH + 1)       # another row
    touched = []
    real = holder.fragment
    monkeypatch.setattr(holder, "fragment",
                        lambda *a: touched.append(a[3]) or real(*a))
    c0 = dict(engine.counters)
    got = []
    names = spans_of(lambda: got.append(served(engine)))
    assert touched == [2] and "gather" in names
    np.testing.assert_array_equal(got[0], truth(holder))
    assert grew(engine, c0, ("leaf_delta_hits", "fp_walks",
                             "leaf_republished", "full_refresh_bytes")) == {
        "leaf_delta_hits": 1, "fp_walks": 0, "leaf_republished": 0,
        "full_refresh_bytes": 0}
    # Two words, as (row, col, value) int32 triples padded to a power of 2
    # of at least DELTA_MIN_UPDATES.
    assert engine.counters["delta_bytes"] - c0["delta_bytes"] == (
        DELTA_MIN_UPDATES * 3 * 4)


def test_a_republished_stack_touches_no_fragment(holder, engine, monkeypatch):
    fld = plant(holder)
    leaves = [Leaf("f", "standard", r) for r in range(3)]
    stack = engine._stacked_leaf_tensor("i", leaves, SHARDS, pad=True)
    assert fld.set_bit(7, 3 * SHARD_WIDTH + 2)  # no row of the stack
    touched = []
    monkeypatch.setattr(holder, "fragment", lambda *a: touched.append(a))
    c0 = dict(engine.counters)
    names = spans_of(lambda: engine._stacked_leaf_tensor(
        "i", leaves, SHARDS, pad=True))
    assert engine._stacked_leaf_tensor(
        "i", leaves, SHARDS, pad=True) is stack
    assert touched == [] and names == ["engine.stack"]
    assert grew(engine, c0, ("stack_republished", "stack_delta_hits",
                             "stack_hits", "fp_walks")) == {
        "stack_republished": 1, "stack_delta_hits": 0, "stack_hits": 1,
        "fp_walks": 0}


def test_a_stale_stack_patches_the_named_cells_only(holder, engine, monkeypatch):
    fld = plant(holder)
    leaves = [Leaf("f", "standard", r) for r in range(3)]
    engine._stacked_leaf_tensor("i", leaves, SHARDS, pad=True)
    assert fld.set_bit(1, 3 * SHARD_WIDTH + 2)
    assert fld.set_bit(0, SHARD_WIDTH + 65)  # leaf 0: the pad row follows
    assert fld.set_bit(9, 5)
    touched = []
    real = holder.fragment
    monkeypatch.setattr(holder, "fragment",
                        lambda *a: touched.append(a[3]) or real(*a))
    c0 = dict(engine.counters)
    got = unfolded(engine._stacked_leaf_tensor(
        "i", leaves, SHARDS, pad=True))
    assert sorted(touched) == [1, 3]
    want = np.stack([truth(holder, leaf) for leaf in leaves + leaves[:1]])
    np.testing.assert_array_equal(got[:, :len(SHARDS)], want)
    assert grew(engine, c0, ("stack_delta_hits", "stack_misses",
                             "fp_walks")) == {
        "stack_delta_hits": 1, "stack_misses": 0, "fp_walks": 0}


def _overflow(holder, fld):
    for n in range(1, 2 * fragment_mod._JOURNAL_ENTRIES + 2):
        fld.set_bit(8, n)


def _recreated_fragment(holder, fld):
    """The cleaner drops shard 1's fragment, a write makes it again."""
    nodes = [Node(id="me"), Node(id="other")]
    cluster = Cluster(node=nodes[0], nodes=nodes, hasher=ModHasher())
    assert HolderCleaner(_FakeServer(holder, cluster)).clean_holder()
    fld.set_bit(0, SHARD_WIDTH + 4321)


def _remade_field(holder, fld):
    holder.index("i").delete_field("f")
    plant(holder).set_bit(0, 3 * SHARD_WIDTH + 11)


def _read_from(holder, fld):
    src = Fragment(None, "i", "f", "standard", 2)
    src.open()
    src.set_bit(0, 2 * SHARD_WIDTH + 123)
    buf = io.BytesIO()
    src.write_to(buf)
    buf.seek(0)
    holder.fragment("i", "f", "standard", 2).read_from(buf)


def _migrate(holder, fld):
    src = Fragment(None, "i", "f", "standard", 3)
    src.open()
    src.set_bit(0, 3 * SHARD_WIDTH + 456)
    holder.fragment("i", "f", "standard", 3).migrate_install(
        src.storage.to_bytes())


CANNOT_SAY = {
    "journal_overflow": _overflow,
    "recreated_fragment": _recreated_fragment,
    "remade_field": _remade_field,
    "read_from": _read_from,
    "migrate_invalidate": _migrate,
}


@pytest.mark.parametrize("name", sorted(CANNOT_SAY))
def test_where_the_journal_cannot_say_the_walk_does(
        holder, engine, monkeypatch, name):
    """Each way the journal loses the thread: the stale leaf falls to the
    walk over the fragments (`fp_walks` +1, once), a stale stack is built
    again of its members, and leaf, stack, Count and the TopN matrix are
    the fragments' own."""
    monkeypatch.setattr(fragment_mod, "_JOURNAL_ENTRIES", 8)
    fld = plant(holder)
    leaves = [Leaf("f", "standard", r) for r in range(3)]
    call = parse("Row(f=0)").calls[0]
    served(engine)
    engine.count("i", call, SHARDS)
    CANNOT_SAY[name](holder, fld)
    c0 = dict(engine.counters)
    np.testing.assert_array_equal(served(engine), truth(holder))
    assert grew(engine, c0, ("fp_walks", "leaf_republished")) == {
        "fp_walks": 1, "leaf_republished": 0}
    want = [truth(holder, leaf) for leaf in leaves]
    stack = unfolded(engine._stacked_leaf_tensor("i", leaves, SHARDS))
    np.testing.assert_array_equal(stack[:, :len(SHARDS)], np.stack(want))
    bits = [int(np.bitwise_count(w).sum()) for w in want]
    assert engine.count("i", call, SHARDS) == bits[0] > 0
    counts = engine.topn_shard_counts("i", "f", [0, 1, 2], SHARDS)[0]
    assert counts.sum(axis=1).tolist() == bits
    # Quiet again: everything is served as it stands.
    c1 = dict(engine.counters)
    served(engine)
    engine._stacked_leaf_tensor("i", leaves, SHARDS)
    assert grew(engine, c1, ("fp_walks", "fp_journal_reads", "leaf_hits",
                             "stack_hits")) == {
        "fp_walks": 0, "fp_journal_reads": 0, "leaf_hits": 1, "stack_hits": 1}


def test_a_stack_whose_journal_cannot_say_is_built_again(
        holder, engine, monkeypatch):
    monkeypatch.setattr(fragment_mod, "_JOURNAL_ENTRIES", 8)
    fld = plant(holder)
    leaves = [Leaf("f", "standard", r) for r in range(3)]
    engine._stacked_leaf_tensor("i", leaves, SHARDS)
    _overflow(holder, fld)
    fld.set_bit(1, 99)
    c0 = dict(engine.counters)
    got = unfolded(engine._stacked_leaf_tensor("i", leaves, SHARDS))
    np.testing.assert_array_equal(
        got[:, :len(SHARDS)], np.stack([truth(holder, l) for l in leaves]))
    # Its three member planes walk, each for itself; the stack does not.
    assert grew(engine, c0, ("stack_misses", "stack_delta_hits",
                             "fp_walks")) == {
        "stack_misses": 1, "stack_delta_hits": 0, "fp_walks": 3}


def test_the_journal_alone():
    """ChangeJournal by itself: stamps, `since`, `changed`, ALL_ROWS, the
    first write of a cell, another journal's stamp."""
    j = ChangeJournal()
    s0 = j.stamp
    assert s0 == (j.incarnation, 0) and j.since(s0, s0) == ()
    j.note(2, 5, (9, 0))
    j.note(3, 6, (8, 4))
    s2 = j.stamp
    j.note(2, 5, (9, 1))
    s3 = j.stamp
    assert s3 == (j.incarnation, 3)
    assert j.since(s0, s3) == [(1, 2, 5, (9, 0)), (2, 3, 6, (8, 4)),
                               (3, 2, 5, (9, 1))]
    assert j.since(s0, s2) == j.since(s0, s3)[:2]
    assert j.since(s2, s3) == [(3, 2, 5, (9, 1))]
    assert j.since(s3, s2) == ()  # an entry newer than the question
    assert j.changed(s0, s3, {5: None}) == {(2, 5): (9, 0)}  # the first
    assert j.changed(s2, s3, {5: None}) == {(2, 5): (9, 1)}
    assert j.changed(s0, s3, {7: None}) == {}
    assert j.changed(s0, s3, (5, 6)) == {(2, 5): (9, 0), (3, 6): (8, 4)}
    j.note(1, ALL_ROWS, None)
    assert j.changed(s0, j.stamp, {7: None}) is None
    assert j.changed(s0, s3, {7: None}) == {}  # up to s3 it could say
    other = ChangeJournal()
    assert other.incarnation != j.incarnation
    assert j.since(other.stamp, j.stamp) is None
    assert j.since(s0, other.stamp) is None
    assert j.since(-1, j.stamp) is None and j.since(s0, -1) is None


# ------------------- (h) journal against walk, threads against threads


def test_journal_and_forced_walk_agree_under_writers_and_readers(holder):
    """Two writers and four readers at a time, a switch interval of ten
    microseconds, step after step. While they run, every plane and count
    a reader is served holds each write acknowledged before it asked and
    nothing that was never written. After each step a leaf, a stack and a
    TopN count matrix served through the journal equal those of an engine
    whose journal never answers (so that it walks every time) and the
    fragments' own."""
    n_rows, steps, per_step = 6, 12, 24
    fld = plant(holder)
    for r in range(n_rows):
        fld.set_bit(r, r)
    by_journal, by_walk = ShardedQueryEngine(holder), ShardedQueryEngine(holder)
    by_walk._changed = lambda *a: None
    leaves = [Leaf("f", "standard", r) for r in range(n_rows)]
    rows = list(range(n_rows))
    src = parse("Row(f=0)").calls[0]
    rng = np.random.default_rng(37)
    acked = set()  # (row, column), added after the write returned
    tried = set()  # added before it was made
    wrong = []

    def word_bit(col):
        return (col % SHARD_WIDTH) // 32, np.uint32(1 << (col % 32))

    def check(plane, row, floor, ceiling):
        """`plane` (S, W) of `row` holds `floor` and nothing past `ceiling`."""
        for r, col in floor:
            if r == row:
                w, b = word_bit(col)
                if not plane[col // SHARD_WIDTH, w] & b:
                    wrong.append(("lost", row, col))
        have = int(np.bitwise_count(plane).sum())
        if have > sum(1 for r, _ in ceiling if r == row):
            wrong.append(("phantom", row, have))

    def writer(cols):
        for row, col in cols:
            tried.add((row, col))
            fld.set_bit(row, col)
            acked.add((row, col))

    def reader(k, stop):
        while not stop.is_set():
            floor = set(acked)
            row = k % n_rows
            plane = served(by_journal, leaves[row])
            check(plane, row, floor, set(tried))
            floor = set(acked)
            stack = unfolded(by_journal._stacked_leaf_tensor(
                "i", leaves, SHARDS))[:, :len(SHARDS)]
            ceiling = set(tried)
            for r in rows:
                check(stack[r], r, floor, ceiling)
            floor = set(acked)
            counts = by_journal.topn_shard_counts("i", "f", rows, SHARDS)[0]
            ceiling = set(tried)
            for r in rows:
                lo = sum(1 for rr, _ in floor if rr == r)
                hi = sum(1 for rr, _ in ceiling if rr == r)
                if not lo <= int(counts[r].sum()) <= hi:
                    wrong.append(("count", r, lo, int(counts[r].sum()), hi))
            k += 1

    for r in range(n_rows):
        acked.add((r, r)), tried.add((r, r))
    for s in SHARDS:
        acked.add((0, s * SHARD_WIDTH)), tried.add((0, s * SHARD_WIDTH))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for step in range(steps):
            # Half the rows a step: the others' planes go stale unwritten.
            some = rng.choice(n_rows, n_rows // 2, replace=False)
            cols = [(int(rng.choice(some)),
                     int(rng.integers(len(SHARDS) * SHARD_WIDTH)))
                    for _ in range(2 * per_step)]
            stop = threading.Event()
            writers = [threading.Thread(target=writer, args=(cols[w::2],))
                       for w in range(2)]
            readers = [threading.Thread(target=reader, args=(k, stop))
                       for k in range(4)]
            for t in readers + writers:
                t.start()
            for t in writers:
                t.join(timeout=60)
            stop.set()
            for t in readers:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in writers + readers)
            assert wrong == []
            # Quiet: the three, by journal, by walk, from the fragments.
            for leaf in leaves:
                want = truth(holder, leaf)
                np.testing.assert_array_equal(served(by_journal, leaf), want)
                np.testing.assert_array_equal(served(by_walk, leaf), want)
            want = np.stack([truth(holder, leaf) for leaf in leaves])
            for eng in (by_journal, by_walk):
                got = unfolded(eng._stacked_leaf_tensor("i", leaves, SHARDS))
                np.testing.assert_array_equal(got[:, :len(SHARDS)], want)
            a = by_journal.topn_shard_counts("i", "f", rows, SHARDS, src)
            b = by_walk.topn_shard_counts("i", "f", rows, SHARDS, src)
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, y)
            np.testing.assert_array_equal(
                a[0], np.bitwise_count(want).sum(axis=2))
    finally:
        sys.setswitchinterval(interval)
        by_journal.close()
        by_walk.close()
    cj, cw = by_journal.counters, by_walk.counters
    assert cj["fp_walks"] == 0 and cj["fp_journal_reads"] > 0
    assert cj["leaf_republished"] > 0 and cj["leaf_delta_hits"] > 0
    assert cw["fp_walks"] > 0 and cw["leaf_republished"] == 0
