"""Multi-host collective execution: jax.distributed over ICI/DCN.

The reference scales across hosts with scatter-gather RPC over its private
protobuf plane (executor.go:1393-1440 mapReduce + NCCL/MPI in its training
stack). The TPU-native equivalent is a *global device mesh*: every host
process joins one `jax.distributed` job, the shard axis spans all hosts'
chips, each host feeds only the shard planes it owns
(`jax.make_array_from_process_local_data`), and a single jitted program
counts/reduces with XLA-inserted collectives that ride ICI within a host
and DCN between hosts — no Python in the reduce path.

SPMD discipline: every participating process must enter the same program
with the same shapes. The serving flow is therefore leader-driven: the
node that received the query broadcasts the (already compiled) query
descriptor over the cluster plane, every process calls `global_count`
together, and the all-reduced scalar materializes on every host — the
leader answers the client, the others discard it. `CollectiveWorker`
implements the non-leader side as a long-poll loop.

Single-process use (tests, one-host clusters) works unchanged: initialize()
is a no-op when num_processes == 1 and the global mesh degenerates to the
local one.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

SHARD_AXIS = "shards"


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> bool:
    """Join (or skip joining) a multi-host jax.distributed job.

    Args fall back to PILOSA_JAX_COORDINATOR / PILOSA_JAX_NUM_PROCESSES /
    PILOSA_JAX_PROCESS_ID so deployments can configure pods by env alone.
    Returns True when a multi-process runtime was initialized."""
    coordinator_address = coordinator_address or os.environ.get(
        "PILOSA_JAX_COORDINATOR"
    )
    if num_processes is None:
        num_processes = int(os.environ.get("PILOSA_JAX_NUM_PROCESSES", "1"))
    if process_id is None:
        process_id = int(os.environ.get("PILOSA_JAX_PROCESS_ID", "0"))
    if not coordinator_address or num_processes <= 1:
        return False
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def global_mesh(limit: Optional[int] = None):
    """1-D mesh over every device in the job — all hosts' chips after
    initialize(), just the local ones otherwise. XLA partitions programs
    over it and inserts ICI collectives within a host, DCN across hosts.

    `limit` restricts the mesh to the first N devices — single-process
    only (the collective plane's `mesh_devices` override); a
    multi-process subset would break the process-contiguous slot layout
    the collective plane verifies."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if limit:
        devs = devs[: int(limit)]
    return Mesh(np.array(devs), (SHARD_AXIS,))


def process_shard_slots(n_shards: int) -> tuple:
    """(global_padded, lo, hi): this process's contiguous slot range after
    padding the shard axis to a multiple of the global device count.
    Placement is block-contiguous, matching NamedSharding's default layout
    over the leading axis, so slot -> owning process is pure arithmetic —
    the same determinism jump-hash gives the HTTP cluster plane."""
    import jax

    n_dev = jax.device_count()
    per_proc = jax.local_device_count()
    padded = n_shards if n_shards % n_dev == 0 else ((n_shards // n_dev) + 1) * n_dev
    per_slot = padded // n_dev
    lo = jax.process_index() * per_proc * per_slot
    hi = lo + per_proc * per_slot
    return padded, lo, hi


def make_global_planes(local_planes: np.ndarray, n_shards_padded: int,
                       mesh=None):
    """Assemble a (S_global, W) device array sharded over the global mesh
    from this host's local block of shard planes. `local_planes` must be
    exactly this process's slot range (process_shard_slots)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh if mesh is not None else global_mesh()
    sharding = NamedSharding(mesh, P(SHARD_AXIS, None))
    global_shape = (n_shards_padded, local_planes.shape[-1])
    return jax.make_array_from_process_local_data(
        sharding, local_planes, global_shape
    )


def _split_sum(pc):
    """Overflow-safe scalar reduction without x64: per-shard partial sums
    (each ≤ 2^25 for a 2^20-column plane) are split into low/high 15-bit
    halves and all-reduced as two int32 scalars — exact up to 2^15 × S
    per half, i.e. ~64k shards / 2^41 bits, where a single int32 sum
    would wrap at 2^31 (jnp.int64 silently canonicalizes to int32 unless
    jax_enable_x64, which we don't force process-wide)."""
    import jax.numpy as jnp

    per = jnp.sum(pc.astype(jnp.int32), axis=tuple(range(1, pc.ndim)))
    lo = jnp.sum(per & 0x7FFF)
    hi = jnp.sum(per >> 15)
    return lo, hi


def global_count(planes) -> int:
    """Popcount-sum over a globally sharded (S, W) uint32 plane array.

    One jitted program per shape (cached by jax): per-device partial
    popcounts then an all-reduce that XLA lowers to ICI/DCN collectives.
    Every process gets the full scalar — fully-replicated output is the
    SPMD analog of the reference's coordinator-side merge loop."""
    import jax

    @jax.jit
    def fn(p):
        return _split_sum(jax.lax.population_count(p))

    lo, hi = fn(planes)
    return (int(hi) << 15) + int(lo)


def global_and_count(planes_a, planes_b) -> int:
    """Count(Intersect) across the global mesh: elementwise AND stays
    device-local (same sharding both sides — zero communication), only the
    scalar reduction crosses hosts."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(a, b):
        return _split_sum(jax.lax.population_count(jnp.bitwise_and(a, b)))

    lo, hi = fn(planes_a, planes_b)
    return (int(hi) << 15) + int(lo)


# NOTE: the round-3 CollectiveWorker lived here. It assumed block-contiguous
# slot->process placement, which contradicts the cluster's jump-hash
# placement and silently counted unowned slots as zeros. The production
# collective plane is parallel/collective.py (placement follows jump-hash,
# workers verify ownership, entry is barrier-guarded and seq-ordered). The
# low-level helpers above remain for hand-assembled plane blocks (tests).
