"""Pallas kernel tests.

Numerics run in interpret mode on the CPU backend against a numpy
popcount oracle. Whether the CHIP's compiler accepts the kernel is a
separate question the interpreter cannot answer, so the kernel is also
compiled ahead of time against a v5e topology description (libtpu, no
chip needed) over the deployment grid of shards-per-device x leaves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.constants import WORDS_PER_ROW
from pilosa_tpu.ops import pallas_kernels as pk


def np_popcount(x):
    return int(np.bitwise_count(np.ascontiguousarray(x)).sum())


RNG = np.random.default_rng(5)


def and_all(planes):
    out = planes[0]
    for p in planes[1:]:
        out = jnp.bitwise_and(out, p)
    return out


def gather_count(stacked, idxs, expr):
    return np.asarray(pk.batched_gather_expr_count(
        jnp.asarray(stacked), idxs, expr, interpret=True))


@pytest.mark.parametrize("u,s,w,q", [
    (5, 3, 256, 7),     # S below the sublane tile: one full-S block
    (4, 12, 1024, 5),   # ragged last S block (12 = 8 + 4), masked
    (4, 256, 256, 6),   # 256-shard geometry, W scaled down
])
def test_batched_gather_expr_count(u, s, w, q):
    # (U, S, W) stack; queries gather leaf pairs and count the intersection.
    stacked = RNG.integers(0, 1 << 32, (u, s, w), dtype=np.uint32)
    ia = RNG.integers(0, u, q).astype(np.int32)
    ib = RNG.integers(0, u, q).astype(np.int32)
    got = gather_count(stacked, (ia, ib), and_all)
    want = np.array(
        [np_popcount(stacked[ia[i]] & stacked[ib[i]]) for i in range(q)]
    )
    np.testing.assert_array_equal(got, want)


def test_batched_gather_expr_count_three_leaves():
    u, s, w, q = 4, 2, 128, 5
    stacked = RNG.integers(0, 1 << 32, (u, s, w), dtype=np.uint32)
    idxs = tuple(RNG.integers(0, u, q).astype(np.int32) for _ in range(3))

    def expr(planes):
        return jnp.bitwise_or(
            jnp.bitwise_and(planes[0], planes[1]),
            jnp.bitwise_and(planes[2], jnp.bitwise_not(planes[0])),
        )

    got = gather_count(stacked, idxs, expr)
    want = np.array([
        np_popcount(
            (stacked[idxs[0][i]] & stacked[idxs[1][i]])
            | (stacked[idxs[2][i]] & ~stacked[idxs[0][i]])
        )
        for i in range(q)
    ])
    np.testing.assert_array_equal(got, want)


def test_batched_gather_expr_count_w_chunked(monkeypatch):
    """When a leaf block exceeds the block budget the W axis chunks too
    (grid (Q, S blocks, W blocks) with accumulated partials) — results
    must not change."""
    monkeypatch.setattr(pk, "_GATHER_BLOCK_BYTES", 8 * 256 * 4)
    u, s, w, q = 6, 20, 1024, 5
    assert pk._gather_blocks(s, w, 2) == (8, 256, 128)
    stacked = RNG.integers(0, 1 << 32, (u, s, w), dtype=np.uint32)
    ia = RNG.integers(0, u, q).astype(np.int32)
    ib = RNG.integers(0, u, q).astype(np.int32)
    got = gather_count(stacked, (ia, ib), and_all)
    want = np.array([np_popcount(stacked[ia[i]] & stacked[ib[i]]) for i in range(q)])
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------- the chip's compiler


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    return topologies.get_topology_desc("v5e:2x2", "tpu").devices


# Once an engine test has switched the persistent compile cache on for the
# process, these compiles are written to it too, and the compile-only
# client cannot load an entry back: a second run warns on each read and
# compiles as if there were none.
aot = pytest.mark.filterwarnings(
    "ignore:Error reading persistent compilation cache entry")


def _compile_for(devices, s_per_device, l, q=64, u=8, fold=1):
    """Lower and compile the engine's kernel program for `devices` (one:
    the bare kernel; several: per device under shard_map with a psum, as
    parallel/engine.py builds it). Raises what the compiler raises.
    `fold`: the stack as it is stored below 8 shards a device,
    (U, S*k, W//k)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("shards",))
    n = len(devices)

    def local(stacked, *idxs):
        c = pk.batched_gather_expr_count(stacked, idxs, and_all,
                                         interpret=False)
        return jax.lax.psum(c, "shards") if n > 1 else c

    fn = local
    if n > 1:
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(None, "shards", None),) + (P(),) * l,
            out_specs=P(), check_vma=False)
    stacked = jax.ShapeDtypeStruct(
        (u, s_per_device * n * fold, WORDS_PER_ROW // fold), jnp.uint32,
        sharding=NamedSharding(mesh, P(None, "shards", None)))
    idx = jax.ShapeDtypeStruct((q,), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    return jax.jit(fn).lower(stacked, *([idx] * l)).compile()


@aot
@pytest.mark.parametrize("l", [2, 3, 8])
@pytest.mark.parametrize("s", [8, 64, 256, 1024])
def test_v5e_compiler_accepts_kernel(v5e, s, l):
    """The full 2^20-bit plane width at every deployment shard count: the
    PR 11 kernel compiled at 8 and 16 shards and was refused with
    `RESOURCE_EXHAUSTED ... memory space vmem` from 24 up."""
    _compile_for(v5e[:1], s, l)


@aot
@pytest.mark.parametrize("s,l", [(1, 2), (3, 2), (12, 3), (64, 48)])
def test_v5e_compiler_accepts_odd_shapes(v5e, s, l):
    # S below / not a multiple of the sublane tile, and a leaf count past
    # where the input ceiling starts shrinking the blocks.
    _compile_for(v5e[:1], s, l)


@aot
@pytest.mark.parametrize("s,l", [(8, 2), (64, 2), (256, 8)])
def test_v5e_compiler_accepts_kernel_on_four_devices(v5e, s, l):
    _compile_for(v5e, s, l)


@aot
@pytest.mark.parametrize("s,n", [(1, 1), (2, 1), (3, 1), (4, 1), (7, 1),
                                 (1, 4), (2, 4)])
def test_v5e_compiler_accepts_kernel_on_folded_stacks(v5e, s, n):
    """Below 8 shards a device the kernel is handed the stack as it is
    stored, (U, S*k, W//k): whole 8-row sublane blocks of 4096 words and
    up, on one device and under shard_map on four."""
    from pilosa_tpu.parallel.mesh import stack_fold

    k = stack_fold(s * n, n)
    assert k > 1 and (s * k) % 8 == 0
    _compile_for(v5e[:n], s, 2, fold=k)


# ----------------------------------------- the layout of a resident stack

LAYOUT_LIMIT_S = 120.0


@pytest.fixture(scope="module")
def v5e_or_skip():
    """The chip's devices for the layout checks alone, which skip where
    the topology cannot be described. The compile gates above take `v5e`
    and fail there: they are the only chip-less word that the kernel
    compiles, and a libtpu that stopped loading must not pass them."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc("v5e:2x2", "tpu").devices
    except jax.errors.JaxRuntimeError as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _within(seconds, fn):
    """fn() on a thread of its own, given up after `seconds`: the chip's
    compiler answers in a second or two or something is wrong with it."""
    import threading

    box = {}

    def work():
        try:
            box["value"] = fn()
        except BaseException as e:  # handed to the test's thread
            box["error"] = e

    th = threading.Thread(target=work, daemon=True)
    th.start()
    th.join(seconds)
    if th.is_alive():
        pytest.fail(f"the compile for v5e took over {seconds} s")
    if "error" in box:
        raise box["error"]
    return box["value"]


def _topn_program(tmp_path, n_shards):
    """The engine's own fused TopN program (AND + popcount + reduce over a
    resident stack under a filter row), built by a real call on one CPU
    device at `n_shards`: (the jitted function, the stack's fold)."""
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.parallel import EngineConfig
    from pilosa_tpu.parallel.engine import ShardedQueryEngine
    from pilosa_tpu.parallel.mesh import default_mesh, stack_fold
    from pilosa_tpu.pql.parser import parse

    holder = Holder(str(tmp_path / "data"))
    holder.open()
    engine = ShardedQueryEngine(
        holder, mesh=default_mesh(jax.devices()[:1]),
        config=EngineConfig(gather_workers=1))
    try:
        idx = holder.create_index("i")
        cols = np.arange(n_shards, dtype=np.uint64) << np.uint64(20)
        idx.create_field("f").import_bits(np.zeros(n_shards, np.uint64), cols)
        idx.create_field("g").import_bits(np.zeros(n_shards, np.uint64), cols)
        _, inter, _ = engine.topn_shard_counts(
            "i", "f", [0], range(n_shards), parse("Row(g=0)").calls[0],
            need_row_counts=False)
        assert inter.tolist() == [[1] * n_shards]
        (fn,) = [f for sig, f in engine._count_fns.items()
                 if sig[0] == "topn_shard_src"]
        return fn, stack_fold(n_shards, 1)
    finally:
        engine.close()
        holder.close()


def _stack_layout(fn, stack_at, stack_shape, n_shards, src_at=None):
    """(a device's block of the stack parameter as laid out, the copies
    of it, the collectives) in the program as the chip's compiler leaves
    it; `stack_at` and `src_at` are the shardings of the stack and of the
    filter's (S, W) plane (one chip: the same)."""
    import re

    stack = jax.ShapeDtypeStruct(stack_shape, jnp.uint32, sharding=stack_at)
    src = (jax.ShapeDtypeStruct((n_shards, WORDS_PER_ROW), jnp.uint32,
                                sharding=src_at or stack_at),)
    text = _within(LAYOUT_LIMIT_S,
                   lambda: fn.lower(stack, src).compile().as_text())
    entry = text[text.index("ENTRY"):]
    (param,) = re.findall(r"(%\S+) = (u32\[[\d,]+\]\{\S+) parameter\(0\)",
                          entry)
    copies = re.findall(r"copy(?:-start)?\(" + re.escape(param[0]) + r"[,)]",
                        entry)
    collectives = re.findall(
        r"\b(?:all-reduce|all-gather|all-to-all|collective-permute"
        r"|reduce-scatter)[-a-z]*\(", text)
    return param[1], copies, collectives


@aot
@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
def test_v5e_lays_a_folded_topn_stack_on_all_eight_sublanes(
        v5e_or_skip, tmp_path, n_shards):
    """The chip tiles a uint32 array's last two axes (8, 128). A stack
    (512, S, 32768) with S under 8 is laid out T(1,128), one sublane of
    eight in use a register, and a reshape inside the reading program
    does not change that: the layout belongs to the array as it is
    stored. Stored folded, (512, S*k, 32768/k), it is T(8,128), and the
    program reads it where it lies (docs/query-compiler.md, "The layout
    of a stack")."""
    from jax.sharding import SingleDeviceSharding

    fn, k = _topn_program(tmp_path, n_shards)
    one_chip = SingleDeviceSharding(v5e_or_skip[0])
    shape = (512, n_shards * k, WORDS_PER_ROW // k)
    layout, copies, _ = _stack_layout(fn, one_chip, shape, n_shards)
    assert layout == "u32[%d,%d,%d]{2,1,0:T(8,128)}" % shape
    assert copies == []


@aot
@pytest.mark.parametrize("n_shards", [4, 8])
def test_v5e_keeps_a_folded_topn_stack_whole_on_each_of_four_chips(
        v5e_or_skip, tmp_path, n_shards):
    """On a mesh the fold goes by the shards a DEVICE holds: 4 shards on
    four chips are one each (k = 8), 8 are two (k = 4). Split along its
    folded axis a device's block is its own shards' words, eight sublane
    rows of them, so the program is each chip's own: no collective, no
    copy of the block."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pilosa_tpu.parallel.mesh import stack_fold

    fn, _ = _topn_program(tmp_path, n_shards)
    k = stack_fold(n_shards, 4)
    mesh = Mesh(np.array(v5e_or_skip), ("shards",))
    layout, copies, collectives = _stack_layout(
        fn, NamedSharding(mesh, P(None, "shards", None)),
        (512, n_shards * k, WORDS_PER_ROW // k), n_shards,
        src_at=NamedSharding(mesh, P("shards", None)))
    assert layout == "u32[512,8,%d]{2,1,0:T(8,128)}" % (WORDS_PER_ROW // k)
    assert copies == [] and collectives == []


@aot
def test_v5e_lays_an_unfolded_one_shard_stack_on_one_sublane(
        v5e_or_skip, tmp_path):
    """The control: what the same program is given for the stack as it
    was stored before the fold. If a compiler ever tiles this densely by
    itself, the fold has nothing left to do at that shard count."""
    from jax.sharding import SingleDeviceSharding

    fn, _ = _topn_program(tmp_path, 1)
    layout, _, _ = _stack_layout(
        fn, SingleDeviceSharding(v5e_or_skip[0]), (512, 1, WORDS_PER_ROW), 1)
    assert layout == "u32[512,1,32768]{2,1,0:T(1,128)}"
