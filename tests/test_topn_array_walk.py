"""The batched TopN runners work on (rows, shards) arrays (executor.py
`_rank_matrix`, `_replay_topn`); the per-shard rung (`_execute_topn_shard`,
i.e. `Fragment.top` with `opt.src`) is the reference they are held to, for
every option, shard by shard and pair by pair."""

import itertools
import json
import sys
import threading
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import executor as ex_mod
from pilosa_tpu import failpoints
from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.core import cache as cache_mod
from pilosa_tpu.core.cache import (
    LRUCache, NopCache, Pair, RankCache, add_pairs, sort_pairs,
)
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.parallel import EngineConfig
from pilosa_tpu.pql.parser import parse
from pilosa_tpu.translate import TranslateStore

N_ROWS = 40
SPAN = 2048  # columns used per shard


def _fill(holder, n_shards, seed):
    """Seeded random fragments that hold every case the replay has to get
    right: rows close to the src (a tanimoto of 50 splits them), rows of a
    few bits (a threshold of 5 splits them), pairs of rows with the same
    columns (ties in cache counts AND in intersections), rows missing from
    the odd shards' caches, and a last shard the src has no bit in (an
    all-zero column of intersections, a src count of 0)."""
    rng = np.random.default_rng(seed)
    idx = holder.create_index("i")
    f = idx.create_field("f")
    g = idx.create_field("g")
    rows, cols, src_cols = [], [], []
    for s in range(n_shards):
        src = rng.choice(SPAN, 600, replace=False)
        if n_shards == 1 or s < n_shards - 1:
            src_cols.extend(int(s * SHARD_WIDTH + x) for x in src)
        rest = np.setdiff1d(np.arange(SPAN), src)
        for row in range(N_ROWS):
            if row % 5 == 4 and s % 2 == 1:
                continue  # not in this shard's cache
            if row % 4 == 0:  # near the src: coefficient 35-75%
                own = np.concatenate([
                    rng.choice(src, int(rng.integers(250, 500)), replace=False),
                    rng.choice(rest, int(rng.integers(20, 120)), replace=False)])
            elif row % 4 == 1:  # a few bits, around threshold=5
                own = rng.choice(SPAN, int(rng.integers(1, 12)), replace=False)
            elif row % 4 == 2:  # far from the src
                own = rng.choice(SPAN, int(rng.integers(40, 400)), replace=False)
            else:  # the same columns as the row before: a tie
                own = prev
            prev = own
            rows.extend([row] * len(own))
            cols.extend(int(s * SHARD_WIDTH + x) for x in own)
    f.import_bits(rows, cols)
    g.import_bits([3] * len(src_cols), src_cols)
    for row in range(0, N_ROWS, 2):
        f.row_attr_store.set_attrs(row, {"category": "even"})
    return holder


@pytest.fixture(scope="module", params=[1, 3, 8], ids=lambda s: f"{s}shards")
def served(request):
    holder = Holder(None)
    holder.open()
    _fill(holder, request.param, seed=3400 + request.param)
    # One engine for the module's cases, so that a program compiles once:
    # built HERE, before conftest's per-test tracker could close it under
    # the next case, and with serial gathers, so that no case starts a
    # pool thread for the leak guard to find.
    ex = Executor(holder, translate_store=TranslateStore().open(), workers=0,
                  engine_config=EngineConfig(gather_workers=1))
    assert ex.engine is not None
    yield ex, list(range(request.param))
    ex.close()
    holder.close()


def _rung(ex, call, shards):
    """The per-shard rung: Fragment.top with the src materialised, shard by
    shard, merged as the reduce merges."""
    out = []
    for s in shards:
        out = add_pairs(out, ex._execute_topn_shard("i", call, s))
    return [(p.id, p.count) for p in sort_pairs(out)]


def _batched(ex, call, shards):
    walks, replays = ex.topn_array_walks, ex.topn_shard_replays
    got = ex._execute_topn_shards("i", call, shards, ExecOptions())
    assert ex.topn_array_walks == walks + 1, "the batched runner did not run"
    assert ex.topn_shard_replays == replays
    return [(p.id, p.count) for p in got]


OPTIONS = list(itertools.product(
    (0, 1, 3, 10), (1, 5), (0, 50), (False, True)))


def _query(n, threshold, tanimoto, attr, extra=""):
    q = f"TopN(f, Row(g=3), n={n}, threshold={threshold}"
    if tanimoto:
        q += f", tanimotoThreshold={tanimoto}"
    if attr:
        q += ', attrName="category", attrValues=["even"]'
    return q + extra + ")"


@pytest.mark.parametrize(
    "n,threshold,tanimoto,attr", OPTIONS,
    ids=[f"n{n}-thr{t}-tan{tan}-{'attr' if a else 'noattr'}"
         for n, t, tan, a in OPTIONS])
def test_batched_runner_matches_per_shard_rung(served, n, threshold,
                                               tanimoto, attr):
    ex, shards = served
    call = parse(_query(n, threshold, tanimoto, attr)).calls[0]
    got = _batched(ex, call, shards)
    assert got == _rung(ex, call, shards)
    assert got, "the case selects nothing: the parity would be vacuous"
    if attr:
        assert all(r % 2 == 0 for r, _ in got)


PHASE2_IDS = {
    "ids": "[0,1,2,3,4,8,12,13,17,19,36,39]",
    "ids-twice": "[0,0,4,13]",  # a row named twice counts twice, both ways
    "ids-absent": "[4,9,999]",  # rows some shards (or all) do not hold
}


@pytest.mark.parametrize("ids", list(PHASE2_IDS), ids=list(PHASE2_IDS))
@pytest.mark.parametrize(
    "threshold,tanimoto,attr",
    [(1, 0, False), (5, 0, False), (1, 50, False), (5, 50, True),
     (1, 0, True)],
    ids=["plain", "thr5", "tan50", "thr5-tan50-attr", "attr"])
def test_batched_phase2_matches_per_shard_rung(served, ids, threshold,
                                               tanimoto, attr):
    ex, shards = served
    q = _query(0, threshold, tanimoto, attr, f", ids={PHASE2_IDS[ids]}")
    call = parse(q).calls[0]
    assert _batched(ex, call, shards) == _rung(ex, call, shards)


@pytest.mark.parametrize("n,threshold,tanimoto,attr", [
    (3, 1, 0, False), (10, 5, 0, True), (0, 1, 50, False), (3, 1, 50, True)])
def test_chunk_boundary_matches_per_shard_rung(served, monkeypatch, n,
                                               threshold, tanimoto, attr):
    """A chunk budget of 16 rows splits the 40 candidates over three device
    programs; the replay sees one (rows, shards) array all the same."""
    ex, shards = served
    monkeypatch.setenv("PILOSA_TOPN_CHUNK_BYTES",
                       str(16 * len(shards) * 32768 * 4))
    assert ex_mod._topn_chunk(len(shards)) == 16
    calls = []
    real = ex._topn_counts_laddered
    monkeypatch.setattr(
        ex, "_topn_counts_laddered",
        lambda *a: calls.append(len(a[2])) or real(*a))
    call = parse(_query(n, threshold, tanimoto, attr)).calls[0]
    got = _batched(ex, call, shards)
    assert got == _rung(ex, call, shards) and got
    assert len(calls) >= 2 and max(calls) <= 16, calls


def test_whole_topn_matches_per_shard_rung_end_to_end(served):
    """Both phases through execute(): two array walks, the answer of the
    per-shard rung (forced by an engine that refuses the src)."""
    ex, shards = served
    q = "TopN(f, Row(g=3), n=5, threshold=2)"
    walks = ex.topn_array_walks
    got = [(p.id, p.count) for p in ex.execute("i", q)[0]]
    assert ex.topn_array_walks == walks + 2
    real = ex.engine.supports
    ex.engine.supports = lambda call, *a, **kw: (
        call.name != "Row" or call.args.get("g") is None) and real(
            call, *a, **kw)
    try:
        want = [(p.id, p.count) for p in ex.execute("i", q)[0]]
    finally:
        ex.engine.supports = real
    assert ex.topn_array_walks == walks + 2, "the rung is no array walk"
    assert got == want and len(got) == 5


# ------------------------------------------------------- the replay, alone

def _heap_reference(cnt, count, cand, src, n, min_threshold, tanimoto):
    """Fragment.top's loop over one shard's candidates, on plain ints."""
    import heapq
    import math

    accepted = np.zeros(len(cnt), bool)
    heap = []
    min_tan, max_tan = src * tanimoto / 100.0, (
        src * 100.0 / tanimoto if tanimoto else 0.0)
    for j in range(len(cnt)):
        if not cand[j]:
            continue
        c, k = int(cnt[j]), int(count[j])
        if tanimoto and (min_tan > 0 or max_tan > 0) and (
                c <= min_tan or c >= max_tan):
            continue
        if n == 0 or len(heap) < n:
            if k == 0:
                continue
            if tanimoto:
                if math.ceil(k * 100.0 / (c + src - k)) <= tanimoto:
                    continue
            elif k < min_threshold:
                continue
            heapq.heappush(heap, k)
            accepted[j] = True
            continue
        if heap[0] < min_threshold or c < heap[0]:
            break
        if k < heap[0]:
            continue
        heapq.heappush(heap, k)
        accepted[j] = True
    return accepted


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("tanimoto", [0, 30, 70])
def test_replay_matches_the_heap_on_random_cells(seed, tanimoto):
    """_replay_topn against the loop it replaces, without an engine: small
    numbers, so ties, zeros and thresholds that stop a shard are common."""
    rng = np.random.default_rng(340000 + seed)
    shards, ranks = 6, 14
    cnt = -np.sort(-rng.integers(0, 12, (shards, ranks)), axis=1)
    count = np.minimum(rng.integers(0, 12, (shards, ranks)), cnt)
    count[:, rng.integers(0, ranks)] = 0
    src = np.maximum(rng.integers(0, 25, shards), count.max(axis=1))
    src[0] = 0  # a shard the src has no bit in
    count[0] = 0
    for n in (0, 1, 2, 5, 14):
        for min_threshold in (1, 3):
            # Some cells an attr filter took; the rest by the cache count.
            cand = (rng.random((shards, ranks)) < 0.85) & (
                cnt > 0 if tanimoto else cnt >= min_threshold)
            # What a cell that is no candidate reads must not matter.
            seen = np.where(cand, count, rng.integers(0, 99, count.shape))
            got = ex_mod._replay_topn(
                cnt, seen, cand, src, n, min_threshold, tanimoto)
            for s in range(shards):
                want = _heap_reference(
                    cnt[s], count[s], cand[s], int(src[s]), n,
                    min_threshold, tanimoto)
                assert got[s].tolist() == want.tolist(), (
                    n, min_threshold, s)


def test_rank_matrix_pads_short_rankings_with_no_candidates():
    short = RankCache()
    short.add(7, 3)
    full = RankCache()
    for r, c in ((1, 5), (2, 5), (3, 9)):
        full.add(r, c)
    ids, cnt = ex_mod._rank_matrix(
        [full.top_arrays(), short.top_arrays(), NopCache().top_arrays()])
    assert ids.tolist() == [[3, 1, 2], [7, 0, 0], [0, 0, 0]]
    assert cnt.tolist() == [[9, 5, 5], [3, 0, 0], [0, 0, 0]]
    ids, cnt = ex_mod._rank_matrix([])
    assert ids.shape == cnt.shape == (0, 0)


# ------------------------------------------------------ the caches' arrays

@pytest.mark.parametrize("cache_cls", [RankCache, LRUCache, NopCache])
def test_every_cache_answers_top_arrays_as_it_answers_top(cache_cls):
    cache = cache_cls() if cache_cls is NopCache else cache_cls(50)
    for r, c in ((4, 10), (9, 10), (1, 30), (6, 2)):
        cache.add(r, c)
    ids, counts = cache.top_arrays()
    assert ids.dtype == counts.dtype == np.int64
    assert list(zip(ids.tolist(), counts.tolist())) == [
        (p.id, p.count) for p in cache.top()]
    if cache_cls is not NopCache:
        assert ids.tolist() == [1, 4, 9, 6]  # count down, then id up
    cache.clear()
    assert len(cache.top_arrays()[0]) == 0


def test_rank_cache_arrays_are_dropped_with_the_sorted_list():
    cache = RankCache(2)
    for r, c in ((1, 5), (2, 7), (3, 6)):
        cache.add(r, c)
    assert cache.top_arrays()[0].tolist() == [2, 3]  # trimmed as top() is
    first = cache.top_arrays()
    assert cache.top_arrays() is first  # kept, not rebuilt per read
    assert [(p.id, p.count) for p in cache.top()] == [(2, 7), (3, 6)]
    assert cache._pairs[0] is first  # top()'s Pairs, made from the arrays
    cache.add(3, 9)
    assert cache._pairs is None and cache._arrays is None
    assert cache.top_arrays()[0].tolist() == [3, 2]
    cache.add(3, 0)  # a row that emptied leaves the ranking
    assert cache.top_arrays()[0].tolist() == [2]


def _tied_counts(n, seed):
    """{row: count} of `n` rows whose counts tie in runs: a few distinct
    counts, so most of the order is the tie-break on the id."""
    rng = np.random.default_rng(seed)
    ids = rng.choice(1 << 40, n, replace=False)
    counts = rng.integers(1, max(2, n // 40), n)
    return dict(zip(ids.tolist(), counts.tolist()))


@pytest.mark.parametrize("n,max_entries", [
    (48, 50000), (48, 20), (8208, 50000), (8208, 5000)])
def test_the_numpy_ranking_keeps_sort_pairs_order_and_trim(n, max_entries):
    entries = _tied_counts(n, seed=n + max_entries)
    want = sort_pairs([Pair(id=i, count=c) for i, c in entries.items()])
    want = [(p.id, p.count) for p in want[:max_entries]]
    cache = RankCache(max_entries)
    for i, c in entries.items():
        cache.add(i, c)
    ids, counts = cache.top_arrays()
    assert list(zip(ids.tolist(), counts.tolist())) == want
    assert [(p.id, p.count) for p in cache.top()] == want
    assert cache.entries == dict(want)  # trimmed as the ranking is
    lru = LRUCache(max(n, max_entries))
    for i, c in entries.items():
        lru.add(i, c)
    assert [(p.id, p.count) for p in lru.top()][:max_entries] == want


def test_a_batched_topn_after_a_set_makes_no_pair_in_the_rank_cache(
        monkeypatch):
    """The ranking a write dropped is rebuilt on arrays alone: of the
    Pairs made in core/cache.py, none come from the rank cache."""
    holder = Holder(None)
    holder.open()
    idx = holder.create_index("i")
    f = idx.create_field("f")
    idx.create_field("g")
    ex = Executor(holder, translate_store=TranslateStore().open(), workers=0)
    makers = []

    def counting_pair(*args, **kw):
        makers.append(sys._getframe(1).f_code.co_name)
        return Pair(*args, **kw)

    try:
        rng = np.random.default_rng(44)
        for row in range(30):
            f.import_bits([row] * (row + 1),
                          rng.choice(4096, row + 1, replace=False).tolist())
        for col in (1, 7, 4000):
            ex.execute("i", f"Set({col}, g=2)")
        q = "TopN(f, Row(g=2), n=5)"
        ex.execute("i", q)
        monkeypatch.setattr(cache_mod, "Pair", counting_pair)
        rebuilds, walks = cache_mod.rank_rebuilds, ex.topn_array_walks
        ex.execute("i", "Set(1, f=3)")
        got = [(p.id, p.count) for p in ex.execute("i", q)[0]]
        assert ex.topn_array_walks == walks + 2  # both phases on arrays
        assert cache_mod.rank_rebuilds == rebuilds + 1  # it did re-rank
        assert set(makers) <= {"add_pairs"}, makers
        monkeypatch.setattr(cache_mod, "Pair", Pair)
        assert got == _rung(ex, parse(q).calls[0], [0]) and got
    finally:
        ex.close()
        holder.close()


def test_a_write_between_a_rebuilds_snapshot_and_its_publish(monkeypatch):
    """The publish guard: a rebuild that a write overtook hands its
    ranking to its own caller and keeps nothing, so the next reader ranks
    the counts after the write, and a trim does not drop the write."""
    cache = RankCache(3)
    for r, c in ((1, 5), (2, 7), (3, 6), (4, 1)):
        cache.add(r, c)
    rank = cache_mod.rank_entries

    def overtaken(entries):
        got = rank(entries)
        cache.add(5, 9)  # lands after the snapshot, before the publish
        return got

    monkeypatch.setattr(cache_mod, "rank_entries", overtaken)
    ids, counts = cache.invalidate()
    assert ids.tolist() == [2, 3, 1]  # the caller's: before the write
    assert cache._arrays is None
    assert cache.entries[5] == 9 and len(cache.entries) == 5  # not trimmed
    monkeypatch.setattr(cache_mod, "rank_entries", rank)
    ids, counts = cache.top_arrays()
    assert list(zip(ids.tolist(), counts.tolist())) == [(5, 9), (2, 7), (3, 6)]
    assert cache.top_arrays() is cache._arrays  # this one was kept
    assert cache.entries == {5: 9, 2: 7, 3: 6}


def test_rank_rebuilds_counts_a_rebuild_not_a_read():
    cache = RankCache(10)
    cache.add(1, 4)
    cache.add(2, 4)
    rebuilds, rows = cache_mod.rank_rebuilds, cache_mod.rank_rows_sorted
    mine = cache_mod.thread_rank_rebuilds()
    cache.add(3, 8)
    cache.top_arrays()
    cache.top_arrays()
    assert cache_mod.rank_rebuilds == rebuilds + 1
    assert cache_mod.rank_rows_sorted == rows + 3
    assert cache_mod.thread_rank_rebuilds() == mine + 1


def test_rank_rebuilds_reach_debug_vars_and_the_rank_span():
    from pilosa_tpu.server.client import InternalClient
    from pilosa_tpu.server.server import Server

    srv = Server(cache_flush_interval=0, member_monitor_interval=0)
    srv.open()
    try:
        host = f"localhost:{srv.port}"
        client = InternalClient()
        idx = srv.holder.create_index("t")
        idx.create_field("f").import_bits([1, 1, 2], [0, 5, 9])
        idx.create_field("g").import_bits([3, 3], [5, 9])

        def executor_vars():
            with urllib.request.urlopen(f"http://{host}/debug/vars") as r:
                return json.load(r)["executor"]

        was = executor_vars()
        client.query(host, "t", "Set(9, f=1)")
        got = client.query(host, "t", "TopN(f, Row(g=3), n=2)")["results"]
        assert got == [[{"id": 1, "count": 2}, {"id": 2, "count": 1}]]
        now = executor_vars()
        assert now["rank_rebuilds"] == was["rank_rebuilds"] + 1
        assert now["rank_rows_sorted"] == was["rank_rows_sorted"] + 2
        with urllib.request.urlopen(
                f"http://{host}/debug/traces?limit=1") as r:
            spans = json.load(r)["traces"][0]["spans"]
        rank = [sp for sp in spans if sp["name"] == "topn.rank"]
        assert [sp["tags"]["rebuilt"] for sp in rank] == [1]
    finally:
        srv.close()


def test_a_set_between_two_topns_shows_in_the_arrays():
    holder = Holder(None)
    holder.open()
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("g")
    ex = Executor(holder, translate_store=TranslateStore().open(), workers=0)
    try:
        for col in (0, 1, 2):
            ex.execute("i", f"Set({col}, f=10)")
        for col in (1, 2, 3, 4):
            ex.execute("i", f"Set({col}, g=5)")
        ex.execute("i", "Set(1, f=20)")
        q = "TopN(f, Row(g=5), n=2)"
        assert [(p.id, p.count) for p in ex.execute("i", q)[0]] == [
            (10, 2), (20, 1)]
        frag = holder.fragment("i", "f", "standard", 0)
        ids, counts = frag.top_arrays()
        assert dict(zip(ids.tolist(), counts.tolist())) == {10: 3, 20: 1}
        for col in (2, 3, 4):
            ex.execute("i", f"Set({col}, f=20)")
        ex.execute("i", "Set(5, f=30)")  # a row the first TopN never saw
        ex.execute("i", "Set(5, g=5)")
        ids, counts = frag.top_arrays()
        assert list(zip(ids.tolist(), counts.tolist())) == [
            (20, 4), (10, 3), (30, 1)]
        assert [(p.id, p.count) for p in ex.execute("i", q)[0]] == [
            (20, 4), (10, 2)]
        assert [(p.id, p.count)
                for p in ex.execute("i", "TopN(f, Row(g=5), n=3)")[0]] == [
            (20, 4), (10, 2), (30, 1)]
    finally:
        ex.close()
        holder.close()


def test_a_reader_of_the_arrays_never_raises_while_a_writer_adds():
    """No lock on this path (a lock taken tens of times a TopN cost 75 ms
    of it: PERF.md, PR 29): a reader racing a writer sees an older ranking
    or rebuilds, and each ranking it sees is whole."""
    cache = RankCache(64)
    for r in range(32):
        cache.add(r, r + 1)
    stop = threading.Event()
    errors = []

    def read():
        try:
            while not stop.is_set():
                ids, counts = cache.top_arrays()
                assert len(ids) == len(counts)
                assert (np.diff(counts) <= 0).all(), counts
                assert len(np.unique(ids)) == len(ids)
        except Exception as e:  # noqa: BLE001 - the test's whole point
            errors.append(e)

    readers = [threading.Thread(target=read) for _ in range(3)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # hand the interpreter over mid-rebuild
    for t in readers:
        t.start()
    try:
        rng = np.random.default_rng(34)
        for i in range(20000):
            cache.add(int(rng.integers(0, 200)), int(rng.integers(0, 50)))
            if i % 500 == 0:
                cache.invalidate()  # trims to 64, swaps entries
    finally:
        stop.set()
        for t in readers:
            t.join(10)
        sys.setswitchinterval(switch)
    assert not errors, errors[0]
    assert not any(t.is_alive() for t in readers)


# ------------------------------------------------------------ the counters

def test_counters_walks_per_topn_and_replays_only_on_the_rung():
    holder = Holder(None)
    holder.open()
    idx = holder.create_index("i")
    f = idx.create_field("f")
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=100))
    rng = np.random.default_rng(11)
    for row in range(6):
        for shard in (0, 1):
            for c in rng.choice(4096, 60 + 13 * row, replace=False):
                f.set_bit(row, shard * SHARD_WIDTH + int(c))
    for col in range(0, 200, 3):
        v.set_value(col, col % 70)
    ex = Executor(holder, translate_store=TranslateStore().open(), workers=0)
    try:
        assert (ex.topn_array_walks, ex.topn_shard_replays) == (0, 0)
        ex.execute("i", "Count(Row(f=1))")
        ex.execute("i", "TopN(f, n=3)")  # no src: the host rank cache
        assert (ex.topn_array_walks, ex.topn_shard_replays) == (0, 0)
        assert ex.execute("i", "TopN(f, Row(f=0), n=3)")[0]
        assert (ex.topn_array_walks, ex.topn_shard_replays) == (2, 0)
        q = "TopN(f, Range(v > 10), n=3)"  # a src with no host twin
        healthy = ex.execute("i", q)[0]
        assert healthy
        assert (ex.topn_array_walks, ex.topn_shard_replays) == (4, 0)
        f.set_bit(0, 8003)
        f.clear_bit(0, 8003)  # memo-bust
        failpoints.configure("device-dispatch", "error")
        degraded = ex.execute("i", q)[0]
        assert [(p.id, p.count) for p in degraded] == [
            (p.id, p.count) for p in healthy]
        # Both phases fell to the per-shard rung, two shards each.
        assert (ex.topn_array_walks, ex.topn_shard_replays) == (4, 4)
    finally:
        failpoints.reset()
        ex.close()
        holder.close()
