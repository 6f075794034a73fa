"""Pallas TPU kernel tier: the batched gather+expr+popcount hot loop.

This is the compiled-kernel tier of the framework — the TPU-native
replacement for the reference's specialized roaring container routines
(/root/reference/roaring/roaring.go:1836-3375). It deliberately contains
ONE kernel: for pure elementwise bitwise+popcount reductions XLA's own
fusion is the shipped implementation (ops/bitplane.py's jnp
formulations), and the one shape a hand-written pipeline can address is
the batched per-query GATHER, where the XLA formulation
(parallel/engine.py:_count_batch_setops) expresses each query's leaves
as gathered (Q, S, W) operands. batched_gather_expr_count instead DMAs
exactly each query's leaf blocks via scalar-prefetched block indices.

Speed against the XLA formulation on the chip: not measured. The
keep-or-delete rule is recorded in docs/query-compiler.md; what this
module guarantees is that the kernel BUILDS on the chip's compiler at
every deployment shape (tests/test_pallas.py compiles it ahead of time
against a v5e topology) and counts bit-exactly.

The caller says whether to interpret (parallel/engine.py passes "the
mesh's platform is not tpu"): interpret mode is a property of where the
arrays live, never a reaction to a backend that failed to come up.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One (sc, wc) leaf block: 8 shard rows x up to 16384 words. Large enough
# that a query's grid is a few thousand steps at 256 shards, small enough
# that 8 double-buffered leaves fit far inside the chip's VMEM.
_GATHER_BLOCK_BYTES = 512 << 10
# Ceiling on the double-buffered input blocks of one grid step (blocks
# shrink past _GATHER_BLOCK_BYTES / this many leaves). The kernel asks
# the compiler for exactly inputs + _VMEM_MARGIN via vmem_limit_bytes —
# the chip's default scoped limit (16 MiB on v5e) is never assumed.
_GATHER_INPUT_BYTES = 32 << 20
# Output block, accumulator scratch and the inner loop's vreg spills.
_VMEM_MARGIN = 4 << 20


def _gather_blocks(s: int, w: int, l: int):
    """(sc, wc, cw): shard rows and words per leaf block, and words per
    inner-loop chunk. sc is the uint32 sublane tile (8) — or all of S
    when S is smaller, which BlockSpec allows for a full dimension; a
    ragged last S block is masked in the kernel. wc halves from W (so it
    always divides W and stays a multiple of 128) until the block fits."""
    sc = min(s, 8)
    cap = min(_GATHER_BLOCK_BYTES, _GATHER_INPUT_BYTES // (2 * l))
    wc = w
    while sc * wc * 4 > cap and wc % 256 == 0:
        wc //= 2
    cw = 512 if wc % 512 == 0 else 128
    return sc, wc, cw


def batched_gather_expr_count(stacked, idxs, expr, interpret: bool):
    """Per-query fused gather+expr+popcount: (Q,) int32.

    `stacked` is the resident (U, S, W) uint32 leaf stack (or, where a
    device holds fewer than 8 shards, the same words stored folded as
    (U, S*k, W//k), parallel/mesh.py stack_fold: the kernel sums over
    shards and words alike, so that is S*k shards of W//k words to it and
    a full 8-row sublane tile a block), `idxs` is a tuple
    of L (Q,) int32 leaf-slot vectors (one per leaf position of the
    compiled expression), `expr` an elementwise jnp function over L planes
    (a canonical PQL set-op tree, docs/query-compiler.md). For query q the
    kernel computes
    popcount(expr(stacked[idxs[0][q]], ..., stacked[idxs[L-1][q]])) summed
    over shards and words.

    The slot vectors are scalar-prefetched so the BlockSpec index maps DMA
    exactly each query's leaf blocks from HBM — the gathered (Q, S, W)
    operands of the XLA formulation never exist here. The grid is
    (Q, S blocks, W blocks); both S and W are blocked (_gather_blocks) so
    the VMEM a step needs is independent of the shard count. Inside a step
    a fori_loop walks the block in (sc, cw) chunks read straight from the
    input refs, so `expr` (all L operand planes of a k-ary node reduced in
    one pass) and the popcount work on a few vregs and the block is never
    materialized a second time as an in-kernel temporary. Lane-folded
    (sc, 128) partials accumulate in a VMEM scratch across a query's
    blocks and the HBM-backed output block is written once per query.

    The kernel operates on ONE device's arrays: multi-device callers run
    it per device under shard_map on each local (U, S/d, W) shard-block
    and psum the per-query partials (parallel/engine.py
    _count_batch_setops).
    """
    _, s, w = stacked.shape
    l = len(idxs)
    q = idxs[0].shape[0]
    assert w % 128 == 0, w
    sc, wc, cw = _gather_blocks(s, w, l)
    n_sb = pl.cdiv(s, sc)
    n_wb = w // wc

    def kernel(*refs):
        leaf_refs = refs[l:-2]
        out_ref, acc_ref = refs[-2:]
        si = pl.program_id(1)
        bi = pl.program_id(2)

        def chunk(j, acc):
            off = pl.multiple_of(j * cw, cw)
            planes = tuple(r[0, :, pl.ds(off, cw)] for r in leaf_refs)
            pc = jax.lax.population_count(expr(planes)).astype(jnp.int32)
            for k in range(cw // 128):
                acc = acc + pc[:, k * 128:(k + 1) * 128]
            return acc

        partial = jax.lax.fori_loop(
            0, wc // cw, chunk, jnp.zeros((sc, 128), jnp.int32))
        if s % sc:
            # Ragged last S block: rows past S hold whatever the DMA left.
            row = si * sc + jax.lax.broadcasted_iota(jnp.int32, (sc, 128), 0)
            partial = jnp.where(row < s, partial, 0)
        first = jnp.logical_and(si == 0, bi == 0)

        @pl.when(first)
        def _():
            acc_ref[...] = partial

        @pl.when(jnp.logical_not(first))
        def _():
            acc_ref[...] += partial

        @pl.when(jnp.logical_and(si == n_sb - 1, bi == n_wb - 1))
        def _():
            out_ref[0] = acc_ref[...]

    def leaf_map(j):
        return lambda qi, si, bi, *idx_refs: (idx_refs[j][qi], si, bi)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=l,
        grid=(q, n_sb, n_wb),
        in_specs=[pl.BlockSpec((1, sc, wc), leaf_map(j)) for j in range(l)],
        out_specs=pl.BlockSpec(
            (1, sc, 128), lambda qi, si, bi, *idx_refs: (qi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((sc, 128), jnp.int32)],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((q, sc, 128), jnp.int32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * l * sc * wc * 4 + _VMEM_MARGIN,
        ),
        interpret=interpret,
        name="batched_gather_expr_count",
    )(*[ix.astype(jnp.int32) for ix in idxs], *([stacked] * l))
    return jnp.sum(out, axis=(1, 2))
