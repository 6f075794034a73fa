"""Device mesh + shard placement for data-parallel query execution.

The reference's parallelism is data parallelism over 2^20-column shards
(SURVEY.md §2: executor.go:1464-1593 goroutine-per-shard + scatter-gather
RPC). The TPU-native equivalent: shards are laid out along a 1-D 'shards'
mesh axis; per-shard bitplane kernels run on every device in SPMD and
scalar reductions (Count/Sum/TopN candidate counts) ride ICI collectives
inserted by XLA (or explicit psum under shard_map).

Pipeline/tensor/sequence/expert parallelism have no analog in a bitmap
index (SURVEY.md §2 records their absence in the reference); the mesh is
deliberately 1-D.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..constants import WORDS_PER_ROW

SHARD_AXIS = "shards"


def default_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over this process's LOCAL devices.

    Local, not global: the per-node engine's programs are entered by this
    process alone (per-shard fan-out hands each node its own shards), and
    a program sharded over other processes' devices would block inside the
    runtime waiting for peers that never enter it. The multi-host global
    mesh belongs exclusively to the collective plane, where every process
    enters together (parallel/collective.py)."""
    devices = list(devices if devices is not None else jax.local_devices())
    return Mesh(np.array(devices), (SHARD_AXIS,))


def shard_sharding(mesh: Mesh, ndim: int, axis: int = 0) -> NamedSharding:
    """NamedSharding splitting dimension `axis` over the shard mesh axis."""
    spec = [None] * ndim
    spec[axis] = SHARD_AXIS
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_shards(n_shards: int, n_devices: int) -> int:
    """Number of shard slots after padding to a device multiple."""
    if n_shards % n_devices == 0:
        return n_shards
    return ((n_shards // n_devices) + 1) * n_devices


def stack_fold(n_shards: int, n_devices: int) -> int:
    """Sublane rows a shard's W words are folded onto in a resident stack.

    The chip tiles the last two axes of a uint32 array (8 sublanes, 128
    lanes). A stack whose shard axis a device holds fewer than 8 long is
    laid out `T(1,128)`, one sublane of eight in use a vector register,
    and its fused reduce reads at an eighth of the speed; the layout
    belongs to the array as it is STORED, so a reshape inside the reading
    program changes nothing (docs/query-compiler.md, "The layout of a
    stack"). Such a stack is therefore kept as (U, S*k, W//k) with k the
    least factor that makes a device's S*k rows a multiple of 8: the same
    bytes in the same order, word c of shard r at [r*k + c // (W//k),
    c % (W//k)]. From 8 shards a device up the compiler fills the
    sublanes by itself and k is 1. A function of the shard count a device
    holds and of nothing else; W//k stays a multiple of the 128 lanes."""
    s_local = pad_shards(n_shards, n_devices) // n_devices
    if s_local >= 8:
        return 1
    return min(8 // math.gcd(s_local, 8), max(1, WORDS_PER_ROW // 128))


def device_for_shard(shard_index: int, n_shards_padded: int, n_devices: int) -> int:
    """Block placement: contiguous runs of shards per device (matches the
    default NamedSharding block layout over the leading axis)."""
    per = n_shards_padded // n_devices
    return shard_index // per
