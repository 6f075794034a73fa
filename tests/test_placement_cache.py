"""What placement keeps across queries (cluster/node.py, cluster/hash.py,
executor._shard_owners) against the uncached math, which is kept HERE:
FNV-1a 64 over name + big-endian shard mod partition_n, jump hash, replicas
on consecutive ring nodes, the next_nodes/migrated override of a live
rebalance, and the executor's choice of an owner under `exclude` and the
breaker. Every answer must equal it on a cold and on a warm cache, across
every way the topology can change and under threads."""

import struct
import sys
import threading
import time

import pytest

from pilosa_tpu.cluster.hash import JmpHasher, ModHasher
from pilosa_tpu.cluster.node import Cluster, Node
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.errors import PilosaError
from pilosa_tpu.executor import Executor

MASK64 = (1 << 64) - 1
SHARDS = list(range(301))


# ----------------------------------------------------- the uncached math


def ref_partition(index, shard, partition_n):
    h = 14695981039346656037
    for b in index.encode() + struct.pack(">Q", shard):
        h = ((h ^ b) * 1099511628211) & MASK64
    return h % partition_n


def ref_jump(key, n):
    b, j = -1, 0
    while j < n:
        b = j
        key = (key * 2862933555777941757 + 1) & MASK64
        j = int(float(b + 1) * (float(1 << 31) / float((key >> 33) + 1)))
    return b


def ref_owners(cluster, index, shard):
    """Owner ids in placement order, from the cluster's state as it stands."""
    nodes = cluster.nodes
    if cluster.next_nodes is not None and (index, shard) in cluster.migrated:
        nodes = cluster.next_nodes
    if not nodes:
        return []
    p = ref_partition(index, shard, cluster.partition_n)
    if isinstance(cluster.hasher, ModHasher):
        first = p % len(nodes)
    else:
        first = ref_jump(p, len(nodes))
    replica_n = min(cluster.replica_n, len(nodes)) or 1
    return [nodes[(first + i) % len(nodes)].id for i in range(replica_n)]


def ref_assign(cluster, index, shards, exclude=(), allowed=lambda nid: True):
    me = cluster.node.id
    local, remote = [], {}
    for shard in shards:
        ids = ref_owners(cluster, index, shard)
        if me in ids and me not in exclude:
            local.append(shard)
            continue
        owner = next((i for i in ids if i not in exclude and allowed(i)), None)
        if owner is None:
            raise PilosaError(f"no available node owns shard {shard}")
        remote.setdefault(owner, []).append(shard)
    return local, remote


# ------------------------------------------------------------- fixtures


@pytest.fixture
def holder():
    h = Holder(None)
    h.open()
    yield h
    h.close()


def make(holder, n_nodes=3, replica_n=1, hasher=None, me=0):
    nodes = [Node(id=f"n{i}", uri=f"n{i}") for i in range(n_nodes)]
    cluster = Cluster(node=nodes[me], nodes=nodes, replica_n=replica_n,
                      hasher=hasher or JmpHasher())
    return cluster, Executor(holder, cluster=cluster, workers=0)


def check(ex, index="i", shards=SHARDS, **kw):
    """The executor's assignment equals the reference's, twice (the second
    is served from what the first kept, where anything may be kept)."""
    want = ref_assign(ex.cluster, index, shards, **kw)
    kw.pop("allowed", None)
    for _ in range(2):
        got = ex._assign_shards(index, list(shards), **kw)
        assert got == want
    return want


# --------------------------------------------------------- (a) parity


@pytest.mark.parametrize("hasher", [JmpHasher, ModHasher])
@pytest.mark.parametrize("replica_n", [1, 2, 3])
@pytest.mark.parametrize("n_nodes", [1, 2, 3, 5])
@pytest.mark.parametrize("index", ["i", "zipf", "a-much-longer_index.name-0123456789", "索引"])
def test_parity_with_the_uncached_math(holder, index, n_nodes, replica_n, hasher):
    cluster, ex = make(holder, n_nodes, replica_n, hasher(), me=n_nodes // 2)
    for _ in range(2):  # cold, then from the memo of the pure halves
        for shard in SHARDS:
            got = cluster.shard_nodes(index, shard)
            assert [n.id for n in got] == ref_owners(cluster, index, shard)
            assert cluster.partition(index, shard) == ref_partition(
                index, shard, cluster.partition_n)
    check(ex, index)
    # Lists that share length, first and last shard with one already kept
    # (the cheap key) but not the middle, a sparse list and a single shard.
    swapped = SHARDS[:100] + [100_000] + SHARDS[101:]
    for shards in (swapped, SHARDS[::7], [299], SHARDS):
        check(ex, index, shards)
    ex.close()


def test_the_callers_list_is_its_own(holder):
    cluster, ex = make(holder, 1)
    asked = list(range(64))
    first, _ = ex._assign_shards("i", asked)
    first.append(-1)
    asked.append(-2)
    second, remote = ex._assign_shards("i", list(range(64)))
    assert second == list(range(64)) and remote == {}
    assert ex._assign_shards("i", []) == ([], {})
    owners = cluster.shard_nodes("i", 3)
    owners.clear()
    assert [n.id for n in cluster.shard_nodes("i", 3)] == ["n0"]
    ex.close()


def test_the_memos_of_the_pure_halves_are_bounded(monkeypatch):
    from pilosa_tpu.cluster import hash as hash_mod, node as node_mod

    monkeypatch.setattr(hash_mod, "MEMO_ENTRIES", 8)
    monkeypatch.setattr(node_mod, "MEMO_ENTRIES", 8)
    nodes = [Node(id=f"n{i}") for i in range(3)]
    cluster = Cluster(node=nodes[0], nodes=nodes)
    for shard in (2 ** 40, *range(40)):
        assert [n.id for n in cluster.shard_nodes("i", shard)] == ref_owners(
            cluster, "i", shard)
        assert len(cluster._shard_hashes) <= 8
        assert len(cluster.hasher._kept) <= 8


# ------------------------------------- (b) every change of the topology


def _moving_shard(cluster, index="i"):
    """A shard whose owners differ between `nodes` and `next_nodes`."""
    for shard in SHARDS:
        was = ref_owners(cluster, index, shard)
        cluster.migrated.add((index, shard))
        then = ref_owners(cluster, index, shard)
        cluster.migrated.discard((index, shard))
        if was != then:
            return shard
    raise AssertionError("no shard moves between the two topologies")


def _grown(cluster):
    return cluster.nodes + [Node(id="n9", uri="n9")]


def assign_nodes(c, warm):
    c.nodes = [c.nodes[0], c.nodes[2]]


def add_node(c, warm):
    c.add_node(Node(id="n1b", uri="n1b"))


def remove_node(c, warm):
    assert c.remove_node("n2")


def append_in_place(c, warm):
    c.nodes.append(Node(id="n7", uri="n7"))


def startup_id_rewrite_and_sort(c, warm):
    # server.py's static-hosts start-up: the id becomes the uri, the node
    # list is built again around it and sorted.
    c.node.id = "localhost:10101"
    c.nodes = [c.node]
    c.add_node(Node(id="localhost:10103"))
    c.add_node(Node(id="localhost:10102"))
    c.nodes = sorted(c.nodes, key=lambda n: n.id)


def id_rewrite_and_sort_in_place(c, warm):
    c.node.id = "zz" if c.node is c.nodes[0] else "a0"  # so that it moves
    c.nodes.sort(key=lambda n: n.id)


def begin_rebalance(c, warm):
    c.begin_rebalance(_grown(c), committed=[("i", s) for s in range(0, 300, 3)])


def apply_cutover(c, warm):
    c.begin_rebalance(_grown(c))
    warm()
    c.apply_cutover("i", _moving_shard(c))


def revert_cutover(c, warm):
    c.begin_rebalance(_grown(c))
    shard = _moving_shard(c)
    c.apply_cutover("i", shard)
    warm()
    c.revert_cutover("i", shard)


def migrated_in_place(c, warm):
    c.begin_rebalance(_grown(c))
    warm()
    c.migrated.add(("i", _moving_shard(c)))


def commit(c, warm):
    c.begin_rebalance(_grown(c), committed=[("i", s) for s in range(150)])
    warm()
    c.commit_topology()


def abort_rebalance(c, warm):
    c.begin_rebalance(_grown(c), committed=[("i", s) for s in range(150)])
    warm()
    assert c.abort_rebalance()


def adopt_topology(c, warm):
    assert c.adopt_topology_if_ahead(_grown(c), c.routing_epoch + 5)


def replica_n(c, warm):
    c.replica_n = 2


def partition_n_and_hasher(c, warm):
    c.partition_n = 16
    c.hasher = ModHasher()


MUTATIONS = [
    assign_nodes, add_node, remove_node, append_in_place,
    startup_id_rewrite_and_sort, id_rewrite_and_sort_in_place,
    begin_rebalance, apply_cutover, revert_cutover, migrated_in_place,
    commit, abort_rebalance, adopt_topology, replica_n,
    partition_n_and_hasher,
]


@pytest.mark.parametrize("me", [0, 2])
@pytest.mark.parametrize("mutate", MUTATIONS, ids=lambda f: f.__name__)
def test_the_next_assignment_is_the_new_placement(holder, mutate, me):
    cluster, ex = make(holder, 3, replica_n=1, me=me)
    last = [check(ex)]
    assert ex.assign_hits >= 1, "the cache was not warm before the change"

    def warm():  # a mutation of several steps checks, and so warms, between
        last[0] = check(ex)

    mutate(cluster, warm)
    assert check(ex) != last[0], "the last step changed no placement: void"
    for shard in SHARDS[::17]:
        assert [n.id for n in cluster.shard_nodes("i", shard)] == ref_owners(
            cluster, "i", shard)
        assert ex._serves_shard("i", shard) == (
            cluster.node.id in ref_owners(cluster, "i", shard))
    ex.close()


# --------------------- (c) exclude and the breaker, asked on a warm cache


def test_exclude_and_an_open_breaker_are_honoured_on_a_warm_cache(holder):
    cluster, ex = make(holder, 3, replica_n=2, me=0)
    check(ex)
    walks = ex.assign_walks
    asked = []
    allow = cluster.health.allow_request

    def counting(node_id):
        asked.append(node_id)
        return allow(node_id)

    cluster.health.allow_request = counting
    # Once a candidate and round, though nothing about placement is new.
    # n0 holds two of the three pairs of neighbours; (n1, n2) is asked of
    # n1, once an assignment though it names a hundred shards.
    check(ex)
    assert asked == ["n1", "n1"]
    check(ex, exclude={"n1"})
    check(ex, exclude={"n0"})
    cluster.health.force_down("n1")
    try:
        n_asked = len(asked)
        down = check(ex, allowed=lambda nid: nid != "n1")
        assert "n1" not in down[1] and down[1]["n2"]
        assert asked[n_asked:] == ["n1", "n2"] * 2, "the breaker is asked live"
        # Excluded and down: the shards only those two hold have no owner.
        with pytest.raises(PilosaError, match="no available node"):
            ex._assign_shards("i", list(SHARDS), exclude={"n2"})
    finally:
        cluster.health.force_up("n1")
    check(ex)
    assert ex.assign_walks == walks, "an admission decision cost a walk"
    ex.close()


def test_one_node_never_asks_the_breaker(holder):
    cluster, ex = make(holder, 1)
    cluster.health.allow_request = lambda nid: pytest.fail("asked")
    assert check(ex) == (SHARDS, {})
    with pytest.raises(PilosaError, match="no available node"):
        ex._assign_shards("i", list(SHARDS), exclude={"n0"})
    ex.close()


# ------------------------------------------------------- (d) threads


def _flip_cutover(cluster):
    cluster.begin_rebalance(_grown(cluster))
    shard = _moving_shard(cluster)
    a = ref_assign(cluster, "i", SHARDS)
    cluster.apply_cutover("i", shard)
    b = ref_assign(cluster, "i", SHARDS)

    def flip(i):
        (cluster.revert_cutover if i % 2 else cluster.apply_cutover)("i", shard)

    return a, b, flip


def _flip_nodes(cluster):
    small, large = list(cluster.nodes), _grown(cluster)
    a = ref_assign(cluster, "i", SHARDS)
    cluster.nodes = large
    b = ref_assign(cluster, "i", SHARDS)

    def flip(i):
        cluster.nodes = small if i % 2 else large

    return a, b, flip


def _flip_begin_and_abort(cluster):
    grown = _grown(cluster)
    committed = [("i", s) for s in SHARDS[::2]]
    a = ref_assign(cluster, "i", SHARDS)
    cluster.begin_rebalance(grown, committed=committed)
    b = ref_assign(cluster, "i", SHARDS)

    def flip(i):
        if i % 2:
            cluster.abort_rebalance()
        else:
            cluster.begin_rebalance(grown, committed=committed)

    return a, b, flip


def _owner_of_each(assignment, me):
    local, remote = assignment
    owner = dict.fromkeys(local, me)
    for node_id, shards in remote.items():
        owner.update(dict.fromkeys(shards, node_id))
    return owner


# whole: the assignment is one of the two placements as a whole. A list's
# owners are worked out from one copy of `nodes`, and a cutover moves one
# shard. While a rebalance is in flight they are read shard by shard, as
# they always were (the epoch re-check after the gather is for that), so
# begin/abort, which moves many shards at once, holds shard by shard.
@pytest.mark.parametrize("flipper,whole", [(_flip_cutover, True),
                                           (_flip_nodes, True),
                                           (_flip_begin_and_abort, False)],
                         ids=lambda v: getattr(v, "__name__", None))
def test_eight_threads_assign_while_the_topology_flips(holder, flipper, whole):
    cluster, ex = make(holder, 3, replica_n=1, me=1)
    check(ex)
    a, b, flip = flipper(cluster)
    assert a != b
    owner_a, owner_b = _owner_of_each(a, "n1"), _owner_of_each(b, "n1")
    stop = threading.Event()
    wrong, counts = [], [0] * 8

    def sound(got):
        if whole:
            return got == a or got == b
        owner = _owner_of_each(got, "n1")
        return sorted(owner) == SHARDS and all(
            owner[s] in (owner_a[s], owner_b[s]) for s in SHARDS)

    def assign(k):
        while not stop.is_set():
            got = ex._assign_shards("i", list(SHARDS))
            if not sound(got):
                wrong.append(got)
                return
            counts[k] += 1

    threads = [threading.Thread(target=assign, args=(k,)) for k in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 1.0
        i = 0
        while time.monotonic() < deadline:
            i += 1
            flip(i)
        stop.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not wrong, "an assignment that is neither placement"
    assert i > 10 and all(counts), (i, counts)
    # Flipping over: the answer is the final placement, and stays it.
    check(ex)
    ex.close()


# ------------------------------------------------------ (e) the counters


def test_counters_hits_and_walks(holder):
    cluster, ex = make(holder, 3)
    assert (ex.assign_hits, ex.assign_walks) == (0, 0)
    ex._assign_shards("i", list(SHARDS))
    assert (ex.assign_hits, ex.assign_walks) == (0, 1)
    for _ in range(5):
        ex._assign_shards("i", list(SHARDS))
    assert (ex.assign_hits, ex.assign_walks) == (5, 1)
    ex._assign_shards("j", list(SHARDS))            # another index
    ex._assign_shards("i", list(SHARDS[:64]))       # another list
    assert (ex.assign_hits, ex.assign_walks) == (5, 3)
    cluster.add_node(Node(id="n1b"))                # a topology change: one
    for _ in range(4):
        ex._assign_shards("i", list(SHARDS))
    assert (ex.assign_hits, ex.assign_walks) == (8, 4)
    # A rebalance in flight keeps nothing: every assignment walks.
    cluster.begin_rebalance(_grown(cluster))
    for _ in range(3):
        ex._assign_shards("i", list(SHARDS))
    assert (ex.assign_hits, ex.assign_walks) == (8, 7)
    cluster.abort_rebalance()
    ex._assign_shards("i", list(SHARDS))            # the old witness again
    assert (ex.assign_hits, ex.assign_walks) == (9, 7)
    ex.close()


def test_kept_lists_are_bounded(holder):
    from pilosa_tpu.executor import _OWNERS_KEPT

    cluster, ex = make(holder, 2)
    for k in range(3 * _OWNERS_KEPT):
        assert ex._assign_shards(f"i{k}", [0, 1, 2]) == ref_assign(
            cluster, f"i{k}", [0, 1, 2])
        assert len(ex._owners_kept) <= _OWNERS_KEPT
    ex.close()


def test_debug_vars_executor_group_counts_a_query_once(tmp_path):
    import json
    import urllib.request

    from pilosa_tpu.server.server import Server

    s = Server(data_dir=str(tmp_path / "node"), cache_flush_interval=0,
               member_monitor_interval=0)
    s.open()
    try:
        s.api.create_index("dv")
        s.api.create_field("dv", "f")
        s.api.import_bits("dv", "f", 0, [1, 1], [2, 3])

        def group():
            with urllib.request.urlopen(
                    f"http://localhost:{s.port}/debug/vars") as r:
                return json.load(r)["executor"]

        def count(row):
            req = urllib.request.Request(
                f"http://localhost:{s.port}/index/dv/query",
                data=f"Count(Row(f={row}))".encode(), method="POST")
            with urllib.request.urlopen(req) as r:
                return json.load(r)["results"][0]

        assert count(1) == 2
        first = group()
        assert first["assign_walks"] >= 1
        assert count(2) == 0 and count(3) == 0
        second = group()
        assert second["assign_walks"] == first["assign_walks"]
        assert second["assign_hits"] >= first["assign_hits"] + 2
    finally:
        s.close()
