"""Dense bitplane ops — the TPU compute core.

A *bitplane* is one fragment row's 2^20 column bits packed into uint32 lanes:
shape (WORDS_PER_ROW,) = (32768,), i.e. 256 sublanes x 128 lanes — a clean VPU
tile. Batches of rows stack to (R, WORDS_PER_ROW). This dense layout replaces
the reference's per-container array/bitmap/run polymorphism
(/root/reference/roaring/roaring.go:988-1061), which is branch-and-pointer
heavy and wrong for a vector unit; roaring survives only as the host/disk
format (storage/bitmap.py).

Everything here is jit-compatible and branch-free: data-dependent choices are
jnp.where on scalar predicates so a whole PQL call tree can be fused into one
XLA program. Counts use lax.population_count on uint32 lanes.

BSI algorithms are the bit-sliced routines of /root/reference/fragment.go:
565-837 (sum/min/max/rangeEQ/NEQ/LT/GT/Between), re-derived for bitplanes.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from ..constants import BITS_PER_WORD, SHARD_WIDTH, WORDS_PER_ROW

# ------------------------------------------------------------- host packing


def pack_bits(cols: np.ndarray, width: int = SHARD_WIDTH) -> np.ndarray:
    """Pack sorted column ids (< width) into a uint32 bitplane (host).

    Uses the native C++ kernel when built (np.bitwise_or.at is an order of
    magnitude slower); numpy fallback otherwise.
    """
    n_words = width // BITS_PER_WORD
    if len(cols):
        from .. import native

        packed = native.pack_bits(np.asarray(cols, dtype=np.uint32), n_words)
        if packed is not None:
            return packed
    words = np.zeros(n_words, dtype=np.uint32)
    if len(cols):
        cols = np.asarray(cols, dtype=np.uint32)
        np.bitwise_or.at(words, cols >> 5, np.uint32(1) << (cols & np.uint32(31)))
    return words


def unpack_bits(plane: np.ndarray) -> np.ndarray:
    """Bitplane -> ascending uint64 column ids (numpy, host)."""
    plane = np.ascontiguousarray(np.asarray(plane, dtype=np.uint32))
    from .. import native

    if native.available():
        out = native.unpack_bits(plane)
        if out is not None:
            return out
    bits = np.unpackbits(plane.view(np.uint8), bitorder="little")
    return np.flatnonzero(bits).astype(np.uint64)


# ------------------------------------------------------------- basic algebra


def p_and(a, b):
    return jnp.bitwise_and(a, b)


def p_or(a, b):
    return jnp.bitwise_or(a, b)


def p_andnot(a, b):
    return jnp.bitwise_and(a, jnp.bitwise_not(b))


def p_xor(a, b):
    return jnp.bitwise_xor(a, b)


def popcount(plane) -> jnp.ndarray:
    """Total set bits. Sums over the trailing word axis; keeps leading axes.

    Per-shard counts fit int32 (<= 2^20 per row; a (R, W) batch sums per-row).
    """
    c = jax.lax.population_count(plane).astype(jnp.int32)
    return jnp.sum(c, axis=-1)


def intersection_count(a, b) -> jnp.ndarray:
    """popcount(a & b) without materializing the intersection."""
    return popcount(jnp.bitwise_and(a, b))


def row_counts(planes, filter_plane=None) -> jnp.ndarray:
    """Per-row counts of a (R, W) stack, optionally ANDed with a (W,) filter.

    This is the TopN inner loop (reference fragment.go:870-1058): all candidate
    rows are counted in one batched popcount instead of a per-row heap walk.
    """
    if filter_plane is not None:
        planes = jnp.bitwise_and(planes, filter_plane[None, :])
    return popcount(planes)


# ------------------------------------------------------------ folded stacks
#
# A resident (U, S, W) stack of fewer than 8 shards a device is stored as
# (U, S*k, W//k) (parallel/mesh.py stack_fold): the same words in the same
# order, a shard's W words over k sublane rows. A program learns k from the
# stack it is handed, and at k == 1 each of these returns its argument, so
# the program traced is the unfolded one.


def fold_of(stacked) -> int:
    """k of a resident stack, read from its word axis."""
    return WORDS_PER_ROW // stacked.shape[-1]


def fold_planes(planes, k: int):
    """(..., S, W) -> (..., S*k, W//k): the planes a program gets beside a
    folded stack (a filter's (S, W) plane), brought to the stack's form."""
    if k == 1:
        return planes
    *lead, s, w = planes.shape
    return planes.reshape(*lead, s * k, w // k)


def unfold_planes(planes, k: int):
    """(..., S*k, W//k) -> (..., S, W): result planes back in the form
    every caller of the engine knows."""
    if k == 1:
        return planes
    *lead, sk, wk = planes.shape
    return planes.reshape(*lead, sk // k, wk * k)


def shard_sums(partials, k: int):
    """(R, S*k) per-folded-row counts -> (R, S) per-shard counts."""
    if k == 1:
        return partials
    r, sk = partials.shape
    return jnp.sum(partials.reshape(r, sk // k, k), axis=2)


# ----------------------------------------------------------------- BSI ops


def bsi_plane_counts(planes, filter_plane=None) -> jnp.ndarray:
    """Per-plane popcounts for BSI sum (reference fragment.go:565-600).

    planes: (bit_depth + 1, W) — planes[i] is value-bit i, planes[bit_depth]
    is the not-null row. Returns (bit_depth + 1,) int32 counts; the weighted
    sum(2^i * counts[i]) is composed on host in Python ints to avoid overflow.
    """
    return row_counts(planes, filter_plane)


def bsi_min(planes, bit_depth: int, filter_plane=None):
    """Min over a BSI group (reference fragment.go:603-637).

    Returns (bits, count): bits is (bit_depth,) int32 0/1 — bit i of the min —
    and count is how many columns hold that min. Branch-free: each step keeps
    `consider` = columns still able to be minimal.
    """
    consider = planes[bit_depth]
    if filter_plane is not None:
        consider = jnp.bitwise_and(consider, filter_plane)
    bits = []
    for i in range(bit_depth - 1, -1, -1):
        x = p_andnot(consider, planes[i])
        nonzero = popcount(x) > 0
        bits.append(jnp.where(nonzero, 0, 1).astype(jnp.int32))
        consider = jnp.where(nonzero, x, consider)
    bits = jnp.stack(bits[::-1]) if bits else jnp.zeros((0,), jnp.int32)
    return bits, popcount(consider)


def bsi_max(planes, bit_depth: int, filter_plane=None):
    """Max over a BSI group (reference fragment.go:640-657)."""
    consider = planes[bit_depth]
    if filter_plane is not None:
        consider = jnp.bitwise_and(consider, filter_plane)
    bits = []
    for i in range(bit_depth - 1, -1, -1):
        x = jnp.bitwise_and(planes[i], consider)
        nonzero = popcount(x) > 0
        bits.append(jnp.where(nonzero, 1, 0).astype(jnp.int32))
        consider = jnp.where(nonzero, x, consider)
    bits = jnp.stack(bits[::-1]) if bits else jnp.zeros((0,), jnp.int32)
    return bits, popcount(consider)


def bsi_range_eq(planes, bit_depth: int, predicate: int):
    """Columns whose value == predicate (reference fragment.go:683-699)."""
    b = planes[bit_depth]
    for i in range(bit_depth - 1, -1, -1):
        if (predicate >> i) & 1:
            b = jnp.bitwise_and(b, planes[i])
        else:
            b = p_andnot(b, planes[i])
    return b


def bsi_range_neq(planes, bit_depth: int, predicate: int):
    """not-null minus EQ (reference fragment.go:701-714)."""
    return p_andnot(planes[bit_depth], bsi_range_eq(planes, bit_depth, predicate))


def bsi_range_lt(planes, bit_depth: int, predicate: int, allow_equality: bool):
    """Columns whose value < (or <=) predicate (reference fragment.go:716-762)."""
    zero = jnp.zeros_like(planes[bit_depth])
    keep = zero
    b = planes[bit_depth]
    leading_zeros = True
    for i in range(bit_depth - 1, -1, -1):
        row = planes[i]
        bit = (predicate >> i) & 1
        if leading_zeros:
            if bit == 0:
                b = p_andnot(b, row)
                continue
            leading_zeros = False
        if i == 0 and not allow_equality:
            if bit == 0:
                return keep
            return p_andnot(b, p_andnot(row, keep))
        if bit == 0:
            b = p_andnot(b, p_andnot(row, keep))
            continue
        if i > 0:
            keep = jnp.bitwise_or(keep, p_andnot(b, row))
    return b


def bsi_range_gt(planes, bit_depth: int, predicate: int, allow_equality: bool):
    """Columns whose value > (or >=) predicate (reference fragment.go:764-800)."""
    zero = jnp.zeros_like(planes[bit_depth])
    keep = zero
    b = planes[bit_depth]
    for i in range(bit_depth - 1, -1, -1):
        row = planes[i]
        bit = (predicate >> i) & 1
        if i == 0 and not allow_equality:
            if bit == 1:
                return keep
            return p_andnot(b, p_andnot(p_andnot(b, row), keep))
        if bit == 1:
            b = p_andnot(b, p_andnot(p_andnot(b, row), keep))
            continue
        if i > 0:
            keep = jnp.bitwise_or(keep, jnp.bitwise_and(b, row))
    return b


def bsi_range_between(planes, bit_depth: int, pmin: int, pmax: int):
    """Columns with pmin <= value <= pmax (reference fragment.go:812-851)."""
    zero = jnp.zeros_like(planes[bit_depth])
    b = planes[bit_depth]
    keep1 = zero  # GTE side
    keep2 = zero  # LTE side
    for i in range(bit_depth - 1, -1, -1):
        row = planes[i]
        bit1 = (pmin >> i) & 1
        bit2 = (pmax >> i) & 1
        if bit1 == 1:
            b = p_andnot(b, p_andnot(p_andnot(b, row), keep1))
        elif i > 0:
            keep1 = jnp.bitwise_or(keep1, jnp.bitwise_and(b, row))
        if bit2 == 0:
            b = p_andnot(b, p_andnot(row, keep2))
        elif i > 0:
            keep2 = jnp.bitwise_or(keep2, p_andnot(b, row))
    return b


# ----------------------------------------------------- jitted entry points

# Small stable jitted wrappers for direct (non-fused) use. The executor
# compiles whole query trees instead; these serve tests and simple paths.

and_count = jax.jit(intersection_count)
count = jax.jit(popcount)
topn_counts = jax.jit(row_counts)


def compose_bits(bits: np.ndarray) -> int:
    """(bit_depth,) 0/1 vector -> python int value (host, overflow-safe)."""
    return sum((1 << i) for i, b in enumerate(np.asarray(bits)) if b)
