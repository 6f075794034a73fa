"""Sharded query engine tests on the virtual 8-device CPU mesh.

Verifies the fast path produces identical results to the per-shard
reference path, that leaf tensors are actually sharded over the mesh, and
that cache invalidation tracks fragment generations.
"""

import jax
import numpy as np
import pytest

from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.parallel.engine import ShardedQueryEngine
from pilosa_tpu.parallel.mesh import default_mesh
from pilosa_tpu.pql.parser import parse


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


@pytest.fixture
def ex(holder):
    return Executor(holder, workers=0)


def plant(holder, ex, n_shards=5):
    """Bits for f=1 in every shard, f=2 in even shards, g=3 sparse."""
    idx = holder.create_index_if_not_exists("i")
    idx.create_field_if_not_exists("f")
    idx.create_field_if_not_exists("g")
    rng = np.random.default_rng(3)
    expected = {}
    for name, row, density in [("f", 1, 0.001), ("f", 2, 0.0005), ("g", 3, 0.0008)]:
        cols = []
        for s in range(n_shards):
            if name == "f" and row == 2 and s % 2:
                continue
            local = np.flatnonzero(rng.random(4096) < density * 256)
            cols.extend(int(s * SHARD_WIDTH + c) for c in local)
        fld = idx.field(name)
        fld.import_bits([row] * len(cols), cols)
        expected[(name, row)] = set(cols)
    return expected


def test_devices_available():
    assert len(jax.devices()) == 8


def test_engine_count_matches_per_shard(holder, ex):
    expected = plant(holder, ex)
    engine = ShardedQueryEngine(holder)
    shards = list(range(5))
    call = parse("Intersect(Row(f=1), Row(g=3))").calls[0]
    want = len(expected[("f", 1)] & expected[("g", 3)])
    assert engine.count("i", call, shards) == want
    # Union / difference / xor.
    for name, op in [("Union", set.union), ("Difference", set.difference), ("Xor", set.symmetric_difference)]:
        c = parse(f"{name}(Row(f=1), Row(f=2))").calls[0]
        want = len(op(expected[("f", 1)], expected[("f", 2)]))
        assert engine.count("i", c, shards) == want, name


def test_engine_bitmap_matches(holder, ex):
    expected = plant(holder, ex)
    engine = ShardedQueryEngine(holder)
    call = parse("Union(Row(f=1), Row(g=3))").calls[0]
    row = engine.bitmap("i", call, list(range(5)))
    assert set(row.columns().tolist()) == expected[("f", 1)] | expected[("g", 3)]


def test_engine_leaf_is_sharded(holder, ex):
    plant(holder, ex, n_shards=8)
    engine = ShardedQueryEngine(holder)
    from pilosa_tpu.parallel.engine import Leaf

    arr = engine._gather_leaf("i", Leaf("f", "standard", 1), tuple(range(8)))
    assert arr.shape[0] == 8
    # Data must actually be distributed across all 8 devices.
    assert len({s.device for s in arr.addressable_shards}) == 8


def test_engine_mesh_devices_knob(holder, ex):
    """[engine] mesh-devices pins the engine to the first N local
    devices — per-node programs then carry no cross-device all-reduces
    (the CPU concurrent-rendezvous hazard, docs/multichip.md) — and
    results stay bit-exact."""
    from pilosa_tpu.parallel import EngineConfig

    expected = plant(holder, ex)
    engine = ShardedQueryEngine(holder, config=EngineConfig(mesh_devices=1))
    assert engine.n_devices == 1
    call = parse("Intersect(Row(f=1), Row(g=3))").calls[0]
    want = len(expected[("f", 1)] & expected[("g", 3)])
    assert engine.count("i", call, list(range(5))) == want


def test_engine_executor_integration(holder, ex):
    expected = plant(holder, ex)
    want = len(expected[("f", 1)] & expected[("g", 3)])
    res = ex.execute("i", "Count(Intersect(Row(f=1), Row(g=3)))")
    assert res == [want]
    row = ex.execute("i", "Intersect(Row(f=1), Row(g=3))")[0]
    assert set(row.columns().tolist()) == expected[("f", 1)] & expected[("g", 3)]


def test_engine_cache_invalidation(holder, ex):
    plant(holder, ex)
    res1 = ex.execute("i", "Count(Row(f=1))")[0]
    # Mutate a row; the cached leaf tensor must be refreshed.
    ex.execute("i", f"Set({3 * SHARD_WIDTH + 77}, f=1)")
    res2 = ex.execute("i", "Count(Row(f=1))")[0]
    assert res2 == res1 + 1


def test_engine_bsi_range(holder, ex):
    idx = holder.create_index_if_not_exists("i")
    idx.create_field_if_not_exists("v", FieldOptions(type="int", min=0, max=100))
    cols = [1, SHARD_WIDTH + 2, 2 * SHARD_WIDTH + 3, 3 * SHARD_WIDTH + 4]
    vals = [10, 20, 30, 40]
    idx.field("v").import_value(cols, vals)
    engine = ShardedQueryEngine(holder)
    call = parse("Range(v > 15)").calls[0]
    row = engine.bitmap("i", call, list(range(4)))
    assert row.columns().tolist() == cols[1:]
    call = parse("Range(15 < v < 35)").calls[0]
    assert engine.count("i", call, list(range(4))) == 2


def test_engine_topn_counts(holder, ex):
    expected = plant(holder, ex)
    engine = ShardedQueryEngine(holder)
    counts = engine.topn_counts("i", "f", [1, 2], list(range(5)))
    assert counts.tolist() == [len(expected[("f", 1)]), len(expected[("f", 2)])]
    src = parse("Row(g=3)").calls[0]
    counts = engine.topn_counts("i", "f", [1, 2], list(range(5)), src_call=src)
    assert counts.tolist() == [
        len(expected[("f", 1)] & expected[("g", 3)]),
        len(expected[("f", 2)] & expected[("g", 3)]),
    ]


def test_engine_padding_non_divisible(holder, ex):
    """5 shards on 8 devices: padded slots must not affect results."""
    expected = plant(holder, ex, n_shards=5)
    engine = ShardedQueryEngine(holder)
    call = parse("Row(f=1)").calls[0]
    assert engine.count("i", call, list(range(5))) == len(expected[("f", 1)])


def test_engine_count_batch_setops(holder, ex):
    """Vectorized batched counts match single-query counts, across batch
    sizes that exercise the pow2 padding (Q=1, 3, 5) and leaf dedup."""
    expected = plant(holder, ex)
    engine = ShardedQueryEngine(holder)
    shards = list(range(5))
    queries = [
        "Intersect(Row(f=1), Row(g=3))",
        "Intersect(Row(f=1), Row(f=2))",
        "Intersect(Row(f=2), Row(g=3))",
        "Intersect(Row(f=1), Row(g=3))",  # duplicate of the first
        "Intersect(Row(g=3), Row(f=1))",
    ]
    calls = [parse(q).calls[0] for q in queries]
    singles = [engine.count("i", c, shards) for c in calls]
    for q in (1, 3, 5):
        got = engine.count_batch("i", calls[:q], shards)
        assert got.tolist() == singles[:q], q
    # Same structure, different rows: correct counts, and the second run of
    # the same batch shape must not compile any new program (cache keyed on
    # structure + deduped batch size, not row ids). The 4 duplicate queries
    # are memoized within the batch and fanned back out.
    more = [parse("Intersect(Row(f=2), Row(f=1))").calls[0]] * 4
    got = engine.count_batch("i", more + calls[:1], shards)
    want = engine.count("i", more[0], shards)
    assert got.tolist() == [want] * 4 + singles[:1]
    n_progs = len(engine._count_fns)
    got2 = engine.count_batch("i", more + calls[:1], shards)
    assert len(engine._count_fns) == n_progs
    assert got2.tolist() == got.tolist()


def test_engine_count_batch_async_and_stack_invalidation(holder, ex):
    """count_batch_async returns valid device results, and a mutation
    between batches refreshes the resident stacked leaf tensor."""
    import numpy as np

    expected = plant(holder, ex)
    engine = ShardedQueryEngine(holder)
    shards = list(range(5))
    calls = [
        parse("Intersect(Row(f=1), Row(g=3))").calls[0],
        parse("Intersect(Row(f=1), Row(f=2))").calls[0],
    ]
    singles = [engine.count("i", c, shards) for c in calls]
    fut = engine.count_batch_async("i", calls, shards)
    assert np.asarray(fut)[: len(calls)].tolist() == singles

    # Mutate a leaf that participates in the batch; the cached stack must
    # be rebuilt (generation fingerprint mismatch), not served stale.
    frag = holder.fragment("i", "f", "standard", 0)
    col = 777
    was_set = frag.bit(1, col)
    if was_set:
        frag.clear_bit(1, col)
        expected[("f", 1)].discard(col)
    else:
        frag.set_bit(1, col)
        expected[("f", 1)].add(col)
    after = engine.count_batch("i", calls, shards).tolist()
    want = [
        len(expected[("f", 1)] & expected[("g", 3)]),
        len(expected[("f", 1)] & expected[("f", 2)]),
    ]
    assert after == want


def test_engine_leaf_cache_eviction_under_tiny_budget(holder, ex, monkeypatch):
    """Leaf-cache eviction mid-gather must not crash or corrupt results
    (regression: fingerprint was read back through the evicting cache)."""
    monkeypatch.setenv("PILOSA_LEAF_CACHE_BYTES", "8192")
    monkeypatch.setenv("PILOSA_STACK_CACHE_BYTES", "8192")
    expected = plant(holder, ex)
    engine = ShardedQueryEngine(holder)
    counts = engine.topn_counts("i", "f", list(range(40)), [0])
    in_shard0 = lambda cols: sum(1 for c in cols if c < SHARD_WIDTH)
    assert counts[1] == in_shard0(expected[("f", 1)])
    assert counts[2] == in_shard0(expected[("f", 2)])
    # Repeat (stack cache path) and a batched count under the same budget.
    counts2 = engine.topn_counts("i", "f", list(range(40)), [0])
    assert counts2.tolist() == counts.tolist()
    calls = [parse("Intersect(Row(f=1), Row(f=2))").calls[0]] * 3
    got = engine.count_batch("i", calls, list(range(5)))
    want = len(expected[("f", 1)] & expected[("f", 2)])
    assert got.tolist() == [want] * 3


def test_engine_memo_skips_device_on_repeat(holder, ex):
    """Hot-query result memo: a repeat query is answered host-side (memo
    hit) and invalidated by fragment generation bumps."""
    expected = plant(holder, ex)
    engine = ShardedQueryEngine(holder)
    shards = list(range(5))
    call = parse("Intersect(Row(f=1), Row(g=3))").calls[0]
    want = len(expected[("f", 1)] & expected[("g", 3)])
    assert engine.count("i", call, shards) == want
    base = dict(engine.counters)
    assert engine.count("i", call, shards) == want
    assert engine.counters["memo_hits"] == base["memo_hits"] + 1
    # A write to any member fragment invalidates via generation.
    fld = holder.index("i").field("f")
    new_col = 777_777
    fld.set_bit(1, new_col)
    got = engine.count("i", call, shards)
    in_g3 = new_col in expected[("g", 3)]
    assert got == want + (1 if in_g3 else 0)


def test_topn_shard_counts_memo_and_invalidation(holder, ex):
    """Repeat TopN count-matrix requests are memo hits (any row order —
    canonical keying), and a write to a member fragment invalidates."""
    plant(holder, ex)
    engine = ShardedQueryEngine(holder)
    shards = list(range(5))
    rows = [2, 1]
    a1, _, _ = engine.topn_shard_counts("i", "f", rows, shards)
    base = dict(engine.counters)
    a2, _, _ = engine.topn_shard_counts("i", "f", [1, 2], shards)  # reordered
    assert engine.counters["memo_hits"] == base["memo_hits"] + 1
    import numpy as np

    np.testing.assert_array_equal(a1[0], a2[1])  # row 2
    np.testing.assert_array_equal(a1[1], a2[0])  # row 1
    # A write to row 1's fragment invalidates the entry.
    assert holder.fragment("i", "f", "standard", 0).set_bit(1, 5000)
    a3, _, _ = engine.topn_shard_counts("i", "f", rows, shards)
    assert int(a3[1].sum()) == int(a1[1].sum()) + 1
    assert engine.counters["memo_misses"] > base["memo_misses"]


def test_bsi_val_count_memo_and_invalidation(holder, ex):
    from pilosa_tpu.core.field import FieldOptions

    idx = holder.index("i") or holder.create_index("i")
    idx.create_field_if_not_exists("v", FieldOptions(type="int", min=0, max=1000))
    ex.execute("i", "SetValue(col=1, v=5)")
    ex.execute("i", "SetValue(col=2, v=7)")
    engine = ShardedQueryEngine(holder)
    depth = idx.field("v").bsi_group("v").bit_depth()
    counts1 = engine.bsi_val_count("i", "v", "sum", depth, [0])
    base = dict(engine.counters)
    counts2 = engine.bsi_val_count("i", "v", "sum", depth, [0])
    assert engine.counters["memo_hits"] == base["memo_hits"] + 1
    import numpy as np

    np.testing.assert_array_equal(counts1, counts2)
    ex.execute("i", "SetValue(col=3, v=9)")
    counts3 = engine.bsi_val_count("i", "v", "sum", depth, [0])
    assert int(counts3[depth]) == int(counts1[depth]) + 1


def test_gather_kernel_multi_device_shard_map(holder, ex, monkeypatch):
    """The Pallas gather kernel partitions over a multi-device mesh via
    shard_map + psum: batched counts forced onto the kernel (interpret
    mode on CPU) must equal the XLA-fallback singles on the 8-device
    mesh."""
    expected = plant(holder, ex, n_shards=8)
    engine = ShardedQueryEngine(holder)
    assert engine.n_devices == 8
    shards = list(range(8))
    pairs = [("f", 1, "g", 3), ("f", 1, "f", 2), ("f", 2, "g", 3)]
    calls = [
        parse(f"Intersect(Row({fa}={ra}), Row({fb}={rb}))").calls[0]
        for fa, ra, fb, rb in pairs
    ]
    singles = [engine.count("i", c, shards) for c in calls]
    # Anchor to planted ground truth so a bug shared by both device paths
    # cannot hide.
    want = [
        len(expected[(fa, ra)] & expected[(fb, rb)]) for fa, ra, fb, rb in pairs
    ]
    assert singles == want

    monkeypatch.setenv("PILOSA_PALLAS_BATCH", "1")
    kernel_engine = ShardedQueryEngine(holder)
    got = kernel_engine.count_batch("i", calls, shards)
    assert got.tolist() == singles


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR is the interface: unset, the cache lives
    at <checkout>/.jax_cache (a fixed path — it is part of the cache key);
    set, jax has already read it and the engine sets nothing."""
    import os

    import pilosa_tpu
    from pilosa_tpu.parallel import engine as engine_mod

    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(pilosa_tpu.__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        engine_mod._place_compile_cache()
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            checkout, ".jax_cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        jax.config.update("jax_compilation_cache_dir", "/placed/outside")
        engine_mod._place_compile_cache()
        assert jax.config.jax_compilation_cache_dir == "/placed/outside"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
