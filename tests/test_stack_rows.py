"""The row axis of a padded stack (parallel/engine.py `padded_rows`): the
next power of two up to STACK_PIECE members, the next multiple of it above,
so that TopN's 8,208 candidate rows at one shard are one stack of 8,704
rows and not 16,384. A stack of more than STACK_PIECE members is built of
pieces joined by one concatenate, and its pad rows stay leaf 0's current
plane through a scattered refresh, whose update count pads to at least
DELTA_MIN_UPDATES. And the bound of a TopN chunk those stacks are built for
(executor.py `_topn_chunk`): the byte budget alone.
"""

import numpy as np
import pytest

from pilosa_tpu.constants import SHARD_WIDTH, WORDS_PER_ROW
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import _topn_chunk
from pilosa_tpu.parallel.engine import (
    DELTA_MIN_UPDATES, STACK_PIECE, Leaf, ShardedQueryEngine, padded_rows)

ROWS = STACK_PIECE + 8      # two pieces, padded to 1,024 rows


@pytest.mark.parametrize("n,rows", [
    (0, 0), (1, 1), (48, 64), (300, 512), (512, 512),
    (513, 1024), (1116, 1536), (8208, 8704)])
def test_padded_rows(n, rows):
    assert padded_rows(n) == rows


@pytest.mark.parametrize("n,padded", [(1, 64), (16, 64), (64, 64),
                                      (65, 128), (300, 512)])
def test_a_scatter_pads_to_at_least_the_floor(n, padded):
    """Delta scatters of up to DELTA_MIN_UPDATES entries share one program
    a cached shape; above it the count rounds up to a power of two."""
    arrays = [np.arange(n, dtype=np.int32), np.full(n, 7, np.uint32)]
    out = ShardedQueryEngine._pad_updates(arrays)
    assert DELTA_MIN_UPDATES == 64 and [len(a) for a in out] == [padded] * 2
    np.testing.assert_array_equal(out[0][:n], arrays[0])
    assert (out[0][n:] == 0).all() and (out[1] == 7).all()


@pytest.mark.parametrize("shards,rows", [(1, 16384), (64, 256), (256, 64)])
def test_the_byte_budget_alone_bounds_a_chunk(monkeypatch, shards, rows):
    monkeypatch.delenv("PILOSA_TOPN_CHUNK_BYTES", raising=False)
    assert _topn_chunk(shards) == rows


@pytest.mark.parametrize("shards", [64, 256])
def test_the_adhoc_cells_topn_is_one_chunk_of_64_rows(monkeypatch, shards):
    """zipf-64.adhoc and zipf-4x64.adhoc rank 48 rows: one chunk, and a
    stack padded to 64 rows, as under the 512-row cap."""
    monkeypatch.delenv("PILOSA_TOPN_CHUNK_BYTES", raising=False)
    assert min(512, _topn_chunk(shards)) == _topn_chunk(shards) >= 48
    assert padded_rows(48) == 64


@pytest.fixture
def world(tmp_path):
    """One shard, ROWS rows of a few hundred bits each."""
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    fld = holder.create_index("i").create_field("f")
    rng = np.random.default_rng(41)
    rows = np.repeat(np.arange(ROWS, dtype=np.uint64), 200)
    cols = rng.integers(0, SHARD_WIDTH, len(rows)).astype(np.uint64)
    fld.import_bits(rows, cols)
    engine = ShardedQueryEngine(holder)
    yield holder, fld, engine
    engine.close()
    holder.close()


def planes(holder, rows):
    """The (len(rows), W) planes straight from storage."""
    frag = holder.fragment("i", "f", "standard", 0)
    return np.stack([frag.plane_np(r) for r in rows])


def stack_of(engine):
    leaves = [Leaf("f", "standard", r) for r in range(ROWS)]
    s = np.asarray(engine._stacked_leaf_tensor("i", leaves, (0,), pad=True))
    return s.reshape(s.shape[0], -1, WORDS_PER_ROW)[:, 0]   # shard 0


def test_a_stack_of_two_pieces_keeps_leaf_0_on_its_pad_rows(world):
    holder, fld, engine = world
    stack = stack_of(engine)
    assert stack.shape[0] == padded_rows(ROWS) == 2 * STACK_PIECE
    want = planes(holder, range(ROWS))
    np.testing.assert_array_equal(stack[:ROWS], want)
    np.testing.assert_array_equal(
        stack[ROWS:], np.broadcast_to(want[0], stack[ROWS:].shape))
    # Writes to leaf 0 and to the last row: the stack is refreshed by one
    # scatter, which carries leaf 0's words onto every pad row too.
    hits = engine.counters["stack_delta_hits"]
    for col in (5, 70000, SHARD_WIDTH - 1):
        fld.set_bit(0, col)
        fld.set_bit(ROWS - 1, col)
    stack = stack_of(engine)
    assert engine.counters["stack_delta_hits"] == hits + 1
    want = planes(holder, range(ROWS))
    np.testing.assert_array_equal(stack[:ROWS], want)
    np.testing.assert_array_equal(
        stack[ROWS:], np.broadcast_to(want[0], stack[ROWS:].shape))
