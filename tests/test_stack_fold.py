"""A resident stack of fewer than 8 shards a device is kept folded onto the
sublanes (parallel/mesh.py stack_fold: (U, S, W) stored as (U, S*k, W//k)).

Every program that reads a stack is held to a numpy reference written
here, at shard counts on both sides of the fold and on a mesh of four
virtual devices; a Set on a ranked row goes through the stale-stack
scatter into the folded stack (first and last word of a shard, and the
words on either side of every fold boundary); `folded_launches` says where
the fold engages; and from 8 shards a device up the program signatures
are what they were before there was a fold.
"""

import jax
import numpy as np
import pytest

from pilosa_tpu.constants import SHARD_WIDTH, WORDS_PER_ROW
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.parallel import EngineConfig
from pilosa_tpu.parallel.engine import Leaf, ShardedQueryEngine
from pilosa_tpu.parallel.mesh import default_mesh, stack_fold
from pilosa_tpu.pql.parser import parse

from .test_delta import unfolded

W = WORDS_PER_ROW
F_ROWS, G_ROWS = 6, 3
V_MAX = 100
# (shards, devices): 1..7 fold on one device, 8 and 9 do not; on four
# devices 4 shards are one a device (k = 8) and 8 are two (k = 4).
LAYOUTS = [(1, 1), (2, 1), (3, 1), (4, 1), (5, 1), (7, 1), (8, 1), (9, 1),
           (4, 4), (8, 4)]
FOLDS = [8, 4, 8, 2, 8, 8, 1, 1, 8, 4]
# The words the draw uses: both ends of a shard, both sides of every
# boundary of every fold (k = 2, 4, 8), and a few between.
EDGES = sorted({0, 1, W - 1} | {j * W // 8 + d for j in range(1, 8)
                                for d in (-1, 0)})
HOT = np.array(sorted(set(EDGES) | {77, 4095, 4097, 9000, 20001, 30000}))


def layout_id(layout):
    return f"{layout[0]}shards-{layout[1]}dev"


def popcount(planes):
    return np.bitwise_count(planes).astype(np.int64)


def columns_of(planes):
    """Global column ids of an (S, W) plane's bits, ascending."""
    bits = np.unpackbits(
        np.ascontiguousarray(planes).view(np.uint8), bitorder="little")
    return np.flatnonzero(bits)


def draw(rng, rows, n_shards, density):
    """(rows, S, W) dense planes with bits in the HOT words only."""
    planes = np.zeros((rows, n_shards, W), np.uint32)
    bits = rng.random((rows, n_shards, len(HOT), 32)) < density
    words = (bits << np.arange(32, dtype=np.uint64)).sum(axis=-1)
    planes[:, :, HOT] = words.astype(np.uint32)
    return planes


def fill(holder, rng, n_shards):
    """Fields f (ranked rows), g (filters), v (an int field) and their
    numpy truth: F, G as dense planes, V as (columns, values)."""
    idx = holder.create_index("i")
    truth = {}
    for name, rows, density in (("f", F_ROWS, 0.3), ("g", G_ROWS, 0.5)):
        planes = draw(rng, rows, n_shards, density)
        for r in range(rows):
            cols = columns_of(planes[r])
            idx.create_field_if_not_exists(name).import_bits(
                np.full(len(cols), r, np.uint64), cols.astype(np.uint64))
        truth[name.upper()] = planes
    hot_cols = ((np.arange(n_shards)[:, None, None] * W + HOT[None, :, None])
                * 32 + np.arange(32)).reshape(-1)
    cols = rng.choice(hot_cols, size=120 * n_shards, replace=False)
    vals = rng.integers(1, V_MAX, len(cols))
    idx.create_field("v", FieldOptions(type="int", min=0, max=V_MAX)) \
        .import_value(cols.astype(np.uint64), vals)
    truth["V"] = (cols, vals)
    return truth


class World:
    def __init__(self, path, layout):
        self.n_shards, self.n_devices = layout
        self.shards = tuple(range(self.n_shards))
        self.holder = Holder(str(path))
        self.holder.open()
        self.truth = fill(self.holder, np.random.default_rng(39), self.n_shards)
        self.engine = self.new_engine()

    def new_engine(self):
        return ShardedQueryEngine(
            self.holder, mesh=default_mesh(jax.devices()[:self.n_devices]),
            config=EngineConfig(gather_workers=1))

    def filter(self, pql):
        """(the parsed call, its (S, W) truth plane) of a filter over g."""
        G = self.truth["G"]
        plane = {"Row(g=0)": G[0], "Row(g=2)": G[2],
                 "Intersect(Row(g=0), Row(g=1))": G[0] & G[1]}[pql]
        return parse(pql).calls[0], plane


@pytest.fixture(scope="module", params=LAYOUTS, ids=layout_id)
def world(request, tmp_path_factory):
    w = World(tmp_path_factory.mktemp("fold"), request.param)
    yield w
    w.engine.close()
    w.holder.close()


def test_the_fold_is_a_function_of_the_shards_a_device_holds():
    assert [stack_fold(s, d) for s, d in LAYOUTS] == FOLDS
    assert [stack_fold(s, 1) for s in (6, 12, 64, 256)] == [4, 1, 1, 1]
    # Padded to a device multiple first: 5 shards on 4 devices are 2 each.
    assert stack_fold(5, 4) == 4 and stack_fold(256, 4) == 1


def test_the_stack_is_stored_folded_and_holds_the_same_words(world):
    leaves = [Leaf("f", "standard", r) for r in range(F_ROWS)]
    stack = world.engine._stacked_leaf_tensor("i", leaves, world.shards)
    k = stack_fold(world.n_shards, world.n_devices)
    s_padded = -(-world.n_shards // world.n_devices) * world.n_devices
    assert stack.shape == (F_ROWS, s_padded * k, W // k)
    assert bp.fold_of(stack) == k
    # A device's block is its own shards' words, all of their sublane rows.
    assert stack.sharding.shard_shape(stack.shape) == (
        F_ROWS, s_padded // world.n_devices * k, W // k)
    got = unfolded(stack)
    assert got.shape == (F_ROWS, s_padded, W)
    np.testing.assert_array_equal(got[:, :world.n_shards], world.truth["F"])
    assert not got[:, world.n_shards:].any()


def test_topn_shard_counts_of_rows_alone(world):
    rows = [4, 0, 5, 2]  # not in canonical order: the answer follows
    counts, inter, src = world.engine.topn_shard_counts(
        "i", "f", rows, world.shards)
    np.testing.assert_array_equal(
        counts, popcount(world.truth["F"][rows]).sum(axis=2))
    assert inter is None and src is None


@pytest.mark.parametrize("need_row_counts", [True, False])
@pytest.mark.parametrize("pql", ["Row(g=0)", "Intersect(Row(g=0), Row(g=1))"])
def test_topn_shard_counts_under_a_filter(world, pql, need_row_counts):
    call, plane = world.filter(pql)
    rows = [1, 3, 0] if need_row_counts else [5, 1, 2, 3, 4]
    counts, inter, src = world.engine.topn_shard_counts(
        "i", "f", rows, world.shards, call, need_row_counts=need_row_counts)
    F = world.truth["F"][rows]
    np.testing.assert_array_equal(inter, popcount(F & plane[None]).sum(axis=2))
    np.testing.assert_array_equal(src, popcount(plane).sum(axis=1))
    if need_row_counts:
        np.testing.assert_array_equal(counts, popcount(F).sum(axis=2))
    else:
        assert counts is None


@pytest.mark.parametrize("pql", [None, "Row(g=2)"])
def test_topn_counts(world, pql):
    rows = [2, 5, 0, 1]
    F = world.truth["F"][rows]
    call = None
    if pql is not None:
        call, plane = world.filter(pql)
        F = F & plane[None]
    got = world.engine.topn_counts("i", "f", rows, world.shards, call)
    np.testing.assert_array_equal(got, popcount(F).sum(axis=(1, 2)))


PAIRS = [(0, 0), (1, 2), (5, 1), (3, 0), (1, 2)]


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_count_batch_setops(world, form, monkeypatch):
    """One group of the batcher's fused Count, in the XLA form and through
    the Pallas gather kernel (interpret mode here), to which a folded
    stack is S*k shards of W//k words."""
    monkeypatch.setenv("PILOSA_PALLAS_BATCH", "1" if form == "pallas" else "0")
    engine = world.new_engine()
    try:
        calls = [parse(f"Intersect(Row(f={a}), Row(g={b}))").calls[0]
                 for a, b in PAIRS]
        got = engine.count_batch("i", calls, world.shards)
        F, G = world.truth["F"], world.truth["G"]
        assert got.tolist() == [int(popcount(F[a] & G[b]).sum())
                                for a, b in PAIRS]
        c = engine.counters
        assert c["count_dispatches"] == 1
        assert c["gather_kernel_dispatches"] == (form == "pallas")
        assert c["folded_launches"] == (
            stack_fold(world.n_shards, world.n_devices) > 1)
    finally:
        engine.close()


def test_bitmap_batch_returns_whole_planes(world):
    trios = [(0, 1), (4, 2), (2, 0)]
    calls = [parse(f"Union(Row(f={a}), Row(g={b}))").calls[0]
             for a, b in trios]
    rows = world.engine.bitmap_batch("i", calls, world.shards)
    F, G = world.truth["F"], world.truth["G"]
    for (a, b), row in zip(trios, rows):
        np.testing.assert_array_equal(
            np.sort(np.asarray(row.columns(), dtype=np.int64)),
            columns_of(F[a] | G[b]))


@pytest.mark.parametrize("pql", [None, "Row(g=0)"])
@pytest.mark.parametrize("kind", ["sum", "min", "max"])
def test_bsi_val_count(world, kind, pql):
    cols, vals = world.truth["V"]
    call = None
    if pql is not None:
        call, plane = world.filter(pql)
        flat = plane.reshape(-1)
        keep = (flat[cols >> 5] >> (cols & 31).astype(np.uint32)) & 1 == 1
        assert 0 < keep.sum() < len(cols)
        vals = vals[keep]
    depth = world.holder.index("i").field("v").bsi_group("v").bit_depth()
    out = world.engine.bsi_val_count("i", "v", kind, depth, world.shards, call)
    if kind == "sum":
        want = [int(((vals >> i) & 1).sum()) for i in range(depth)]
        assert out.tolist() == want + [len(vals)]
    else:
        bits, count = out
        best = vals.min() if kind == "min" else vals.max()
        assert (bp.compose_bits(bits), count) == (
            int(best), int((vals == best).sum()))


# ------------------------------------------------- the stale-stack scatter


def executor(holder, n_devices):
    ex = Executor(holder, workers=0, engine_config=EngineConfig(
        gather_workers=1, mesh_devices=n_devices))
    assert ex.engine.n_devices == n_devices
    return ex


def top(F, plane):
    counts = popcount(F & plane[None]).sum(axis=(1, 2))
    order = sorted((r for r in range(len(counts)) if counts[r]),
                   key=lambda r: (-counts[r], r))
    return [(r, int(counts[r])) for r in order]


WORDS = {
    "first": [0], "last": [W - 1],
    # c = j * W//k for every k, and the word before each.
    "boundaries": [w for w in EDGES if w not in (0, 1, W - 1)],
}


@pytest.mark.parametrize("which", sorted(WORDS))
@pytest.mark.parametrize("layout", [(1, 1), (2, 1), (3, 1), (4, 1), (8, 1),
                                    (4, 4)], ids=layout_id)
def test_a_set_on_a_ranked_row_is_scattered_into_the_folded_stack(
        tmp_path, layout, which):
    n_shards, n_devices = layout
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    ex = executor(holder, n_devices)
    try:
        truth = fill(holder, np.random.default_rng(7), n_shards)
        F, plane = truth["F"], truth["G"][0]
        pql = f"TopN(f, Row(g=0), n={F_ROWS})"
        pairs = lambda: [(p.id, p.count) for p in ex.execute("i", pql)[0]]
        assert pairs() == top(F, plane)
        c0 = dict(ex.engine.counters)
        # A bit the filter holds and the row lacks, in each word named, in
        # every shard: the row's count moves, so the ranking does.
        row = top(F, plane)[-1][0]
        n_set = 0
        for shard in range(n_shards):
            for w in WORDS[which]:
                free = plane[shard, w] & ~F[row, shard, w]
                assert free, (shard, w)
                bit = int(free).bit_length() - 1
                col = shard * SHARD_WIDTH + w * 32 + bit
                assert ex.execute("i", f"Set({col}, f={row})") == [True]
                F[row, shard, w] |= np.uint32(1 << bit)
                n_set += 1
        assert pairs() == top(F, plane)
        c1 = ex.engine.counters
        assert c1["stack_delta_hits"] > c0["stack_delta_hits"]
        assert c1["stack_misses"] == c0["stack_misses"]
        assert c1["fp_walks"] == c0["fp_walks"]
        # And what is resident is what a rebuild would give.
        leaves = [Leaf("f", "standard", r) for r in range(F_ROWS)]
        stack = ex.engine._stacked_leaf_tensor(
            "i", leaves, tuple(range(n_shards)), pad=True)
        got = unfolded(stack)
        np.testing.assert_array_equal(got[:F_ROWS, :n_shards], F)
        np.testing.assert_array_equal(got[F_ROWS:], got[:1].repeat(
            stack.shape[0] - F_ROWS, axis=0))
        assert n_set == n_shards * len(WORDS[which])
    finally:
        ex.close()
        holder.close()


# ------------------------------------------- where the fold engages, and not


@pytest.fixture(scope="module")
def sparse64(tmp_path_factory):
    """64 shards, two ranked rows and a filter, a bit or two a shard."""
    holder = Holder(str(tmp_path_factory.mktemp("fold64")))
    holder.open()
    idx = holder.create_index("i")
    base = np.arange(64, dtype=np.uint64) * np.uint64(SHARD_WIDTH)
    f, g = idx.create_field("f"), idx.create_field("g")
    f.import_bits(np.zeros(64, np.uint64), base + np.uint64(3))
    f.import_bits(np.ones(64, np.uint64), base + np.uint64(W * 32 - 1))
    g.import_bits(np.zeros(128, np.uint64), np.concatenate(
        [base + np.uint64(3), base + np.uint64(W * 32 - 1)]))
    idx.create_field("v", FieldOptions(type="int", min=0, max=V_MAX)) \
        .import_value(base + np.uint64(3), np.arange(64) + 1)
    yield holder
    holder.close()


def run_every_reader(engine, n_shards):
    shards = tuple(range(n_shards))
    flt = parse("Row(g=0)").calls[0]
    a = engine.topn_shard_counts("i", "f", [0, 1], shards, flt)
    b = engine.topn_counts("i", "f", [0, 1], shards)
    c = engine.topn_counts("i", "f", [0, 1], shards, flt)
    d = engine.count_batch("i", [parse(
        f"Intersect(Row(f={r}), Row(g=0))").calls[0] for r in (0, 1)], shards)
    e = engine.bitmap_batch("i", [parse(
        f"Union(Row(f={r}), Row(g=0))").calls[0] for r in (0, 1)], shards)
    depth = engine.holder.index("i").field("v").bsi_group("v").bit_depth()
    f = engine.bsi_val_count("i", "v", "sum", depth, shards, flt)
    assert a[0].tolist() == a[1].tolist() == [[1] * n_shards] * 2
    assert b.tolist() == c.tolist() == d.tolist() == [n_shards] * 2
    assert [len(r.columns()) for r in e] == [2 * n_shards] * 2
    assert f[depth] == n_shards


@pytest.mark.parametrize("n_shards,grows", [(1, True), (8, False),
                                            (64, False)])
def test_folded_launches_counts_launches_over_folded_stacks(
        sparse64, n_shards, grows):
    engine = ShardedQueryEngine(
        sparse64, mesh=default_mesh(jax.devices()[:1]),
        config=EngineConfig(gather_workers=1))
    try:
        assert engine.snapshot()["folded_launches"] == 0
        run_every_reader(engine, n_shards)
        # topn_shard_counts is two programs; the four others one each.
        assert engine.snapshot()["folded_launches"] == (7 if grows else 0)
    finally:
        engine.close()


def test_program_signatures_at_64_shards_are_the_parents(sparse64):
    """From 8 shards a device up nothing of a signature says "fold": these
    are the tuples the engine built its programs under before the fold."""
    engine = ShardedQueryEngine(
        sparse64, mesh=default_mesh(jax.devices()[:1]),
        config=EngineConfig(gather_workers=1))
    try:
        run_every_reader(engine, 64)
        leaf = (("leaf", 0),)
        depth = sparse64.index("i").field("v").bsi_group("v").bit_depth()
        setop = parse("Intersect(Row(f=0), Row(g=0))").calls[0]
        union = parse("Union(Row(f=0), Row(g=0))").calls[0]
        sig_of = lambda call: engine._compile("i", call)[0].plan.sig_tuple
        assert set(engine._count_fns) == {
            ("topn_shard", 64, 2),
            ("topn_shard_src", leaf, 64, 2),
            ("topn", 64, 2),
            ("topn_src", leaf, 64, 2),
            ("count_batch_setops", sig_of(setop), 64, 2, 4, 0),
            ("bsi", "sum", depth, 64, leaf),
        }
        assert set(engine._bitmap_fns) == {
            ("bitmap_batch", sig_of(union), 64, 2, 4)}
        stacks = [e[1] for e in engine._stack_cache.values()]
        assert stacks and all(s.shape[1:] == (64, W) for s in stacks)
    finally:
        engine.close()


def test_the_shard_count_of_a_signature_decides_the_fold(sparse64):
    """A signature says nothing of the fold and needs not: it holds
    len(shards), an engine's device count is fixed, and stack_fold is a
    function of the two, so no signature is shared by two forms of a
    stack (and jax.jit keys its traces on the shape besides)."""
    engine = ShardedQueryEngine(
        sparse64, mesh=default_mesh(jax.devices()[:1]),
        config=EngineConfig(gather_workers=1))
    try:
        for n_shards in (1, 2, 64):
            run_every_reader(engine, n_shards)
        sigs = set(engine._count_fns) | set(engine._bitmap_fns)
        assert len(sigs) == 21
        assert not any("fold" in repr(sig) for sig in sigs)
        folds = {}
        for (_, _, shards, _), entry in engine._stack_cache.items():
            folds.setdefault(len(shards), set()).add(bp.fold_of(entry[1]))
        assert folds == {1: {8}, 2: {4}, 64: {1}}
    finally:
        engine.close()


# ------------------------------------ the host's side of a launch over a stack


def test_the_rows_of_a_launch_are_worked_out_once(sparse64):
    """A TopN over thousands of rows asks for the same chunks at every
    query: canonical order, leaves and the way back are kept by the ids
    asked for, and the same objects serve every later launch."""
    engine = ShardedQueryEngine(
        sparse64, mesh=default_mesh(jax.devices()[:1]),
        config=EngineConfig(gather_workers=1))
    try:
        rows, leaves, sel = engine._topn_rows("f", [7, 3, 7, 1])
        assert rows == (1, 3, 7)
        assert leaves == tuple(Leaf("f", "standard", r) for r in rows)
        assert sel.tolist() == [2, 1, 2, 0]
        again = engine._topn_rows("f", np.array([7, 3, 7, 1]))
        assert all(a is b for a, b in zip(again, (rows, leaves, sel)))
        # Another order or another field is another entry.
        assert engine._topn_rows("f", [1, 3, 7])[2].tolist() == [0, 1, 2]
        assert engine._topn_rows("g", [7, 3, 7, 1])[1][0].field == "g"
        assert len(engine._topn_rows_memo) == 3
        none = engine._topn_rows("f", [])
        assert none[:2] == ((), ()) and none[2].tolist() == []
    finally:
        engine.close()


def test_the_rows_memo_is_dropped_whole_at_its_bound(sparse64, monkeypatch):
    monkeypatch.setattr(ShardedQueryEngine, "_TOPN_ROWS_MEMO_ENTRIES", 4)
    engine = ShardedQueryEngine(
        sparse64, mesh=default_mesh(jax.devices()[:1]),
        config=EngineConfig(gather_workers=1))
    try:
        for r in range(4):
            engine._topn_rows("f", [r])
        assert len(engine._topn_rows_memo) == 4
        assert engine._topn_rows("f", [9])[0] == (9,)
        assert len(engine._topn_rows_memo) == 1
    finally:
        engine.close()


@pytest.mark.parametrize("ids", [[1, 0], [0, 1, 1, 0], [1]],
                         ids=["unsorted", "repeated", "one"])
def test_answers_follow_the_order_asked_for(sparse64, ids):
    engine = ShardedQueryEngine(
        sparse64, mesh=default_mesh(jax.devices()[:1]),
        config=EngineConfig(gather_workers=1))
    try:
        shards = tuple(range(8))
        flt = parse("Row(g=0)").calls[0]
        for _ in range(2):  # the second from the memos
            counts, inter, src = engine.topn_shard_counts(
                "i", "f", ids, shards, flt)
            assert counts.shape == inter.shape == (len(ids), 8)
            assert counts.tolist() == inter.tolist() == [[1] * 8] * len(ids)
            assert src.tolist() == [2] * 8
            assert engine.topn_counts("i", "f", ids, shards, flt).tolist() \
                == [8] * len(ids)
    finally:
        engine.close()


def test_a_stack_keeps_where_its_rows_lie(tmp_path):
    """A stale stack asks its views' journals about the rows it holds. The
    map from row to place is worked out when the stack is built and kept
    with it: a republish and a delta hand the same map on."""
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    engine = ShardedQueryEngine(
        holder, mesh=default_mesh(jax.devices()[:1]),
        config=EngineConfig(gather_workers=1))
    try:
        fill(holder, np.random.default_rng(3), 2)
        fld = holder.index("i").field("f")
        shards = (0, 1)
        leaves = [Leaf("f", "standard", r) for r in (0, 1, 2)] \
            + [Leaf("g", "standard", 1)]
        key = ("i", tuple(leaves), shards, 4)
        get = lambda: engine._stacked_leaf_tensor(
            "i", leaves, shards, pad=True)
        get()
        by_view = engine._stack_cache[key][2]
        assert by_view == {("f", "standard"): {0: [(0,)], 1: [(1,)],
                                               2: [(2,)]},
                           ("g", "standard"): {1: [(3,)]}}
        c0 = dict(engine.counters)
        assert fld.set_bit(5, 77)           # a row the stack does not hold
        get()
        assert fld.set_bit(1, SHARD_WIDTH + 77)   # one it holds
        got = unfolded(get())
        c1 = engine.counters
        assert (c1["stack_republished"] - c0["stack_republished"],
                c1["stack_delta_hits"] - c0["stack_delta_hits"],
                c1["stack_misses"] - c0["stack_misses"]) == (1, 1, 0)
        assert engine._stack_cache[key][2] is by_view
        assert got[1, 1, 77 // 32] >> (77 % 32) & 1
    finally:
        engine.close()
        holder.close()
