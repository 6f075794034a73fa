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


def _compile_for(devices, s_per_device, l, q=64, u=8):
    """Lower and compile the engine's kernel program for `devices` (one:
    the bare kernel; several: per device under shard_map with a psum, as
    parallel/engine.py builds it). Raises what the compiler raises."""
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
        (u, s_per_device * n, WORDS_PER_ROW), jnp.uint32,
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
