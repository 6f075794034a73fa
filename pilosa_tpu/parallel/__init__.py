"""Sharded device query engine: mesh placement, compiled-program and
device-tensor caches, delta refresh, multi-host collectives."""

from __future__ import annotations

from dataclasses import dataclass


# The [engine] config section IS this dataclass (same pattern as
# [scheduler]/SchedulerConfig and [storage]/StorageConfig). It lives in the
# package __init__ — NOT engine.py — so config.py can import it without
# pulling jax into every CLI startup. Env vars (PILOSA_TPU_ENGINE_*, same
# spellings config.py maps for this section) override per-process.
@dataclass
class EngineConfig:
    """Device-cache refresh knobs for ShardedQueryEngine.

    delta_max_fraction: a stale resident plane/stack is refreshed by a
        small scattered update (indices+values host->HBM) only while the
        changed 32-bit words stay under this fraction of the tensor;
        past it the full regather path wins. 0 disables the delta path.
    delta_journal_ops: per-fragment dirty-word journal bound
        (core/fragment.py); overflow falls back to full regather.
    gather_workers: threads for the cold-path per-shard host container
        walks (0 = auto-size to the CPU count, 1 = serial).
    """

    delta_max_fraction: float = 0.25
    delta_journal_ops: int = 4096
    gather_workers: int = 0
    # Engine mesh width: 0 = all local devices (default). A positive N
    # restricts the per-node engine's mesh to the first N local devices.
    # The operational reason is the multi-device CPU backend: concurrent
    # sharded programs whose scalar reductions lower to cross-device
    # all-reduces can interleave their rendezvous and deadlock (a
    # jax-level hazard the micro-batcher only narrows), so CPU
    # deployments that want the COLLECTIVE plane on the full device set
    # pin the engine to mesh-devices=1 — per-node programs then carry no
    # collectives at all and only the (runner-serialized) collective
    # plane uses the full mesh. On four real chips the hazard did not
    # show, in a smoke's waves (PR 21) or under the benchmark's load for
    # minutes (PR 30), so the launches carry no lock (docs/multichip.md,
    # "One process, every local chip").
    mesh_devices: int = 0
    # Cache budgets (0 = auto). Auto means: the legacy env override
    # (PILOSA_LEAF_CACHE_BYTES / PILOSA_STACK_CACHE_BYTES /
    # PILOSA_MEMO_ENTRIES / PILOSA_AUX_MEMO_ENTRIES) if set, else the
    # [tier] hbm-bytes split (byte budgets only), else the platform
    # default. A nonzero config value loses only to the legacy env var —
    # env stays the per-process override, as before these were
    # configurable at all. Effective values surface in /debug/vars
    # (engine_budgets).
    leaf_cache_bytes: int = 0
    stack_cache_bytes: int = 0
    memo_entries: int = 0
    aux_memo_entries: int = 0
    # Device-fault handling (docs/fault-tolerance.md, device-plane
    # section). dispatch_watchdog: seconds a device dispatch may block
    # before the watchdog frees the serving thread and the failure is
    # classified `timeout` into the device breakers (0 disables; the
    # wedged dispatch itself cannot be killed — it parks a worker of the
    # engine's dedicated 4-slot dispatch pool until the runtime answers,
    # and once every slot is parked further dispatches run inline
    # unwatchdogged). cold_host_count: 1 answers a one-off Count whose
    # leaves are ALL demoted to the host tier directly from the
    # compressed bytes in one numpy pass — no decode + device_put for a
    # plane nobody re-reads (ROADMAP compressed-domain execution); the
    # SECOND touch of the same leaf set promotes normally so hot planes
    # still climb back into HBM. 0 disables.
    dispatch_watchdog: float = 0.0
    cold_host_count: int = 1
    # plan_cache: 1 caches each Call tree's canonical plan (signature +
    # leaf slots + lowered expression, plan/signature.py) on the Call
    # object, keyed by the index's write epoch — one lowering per query
    # instead of one per dispatch site / shard batch / TopN chunk. 0
    # recompiles every time (escape hatch).
    plan_cache: int = 1


# The [collective] config section (docs/multichip.md) — jax-free here for
# the same reason as EngineConfig: config.py/cli.py import it at startup.
@dataclass
class CollectiveConfig:
    """Multi-host collective serving plane knobs
    (parallel/collective.py).

    enabled: 0 turns the collective rung off entirely (every full-index
        query takes the HTTP fan-out) — the escape hatch.
    single_process: 1 lets a single-process job with a single-node
        cluster serve through the collective plane over its LOCAL device
        mesh (a one-pod deployment whose chips hold the whole index; the
        barrier degenerates to a no-op). Default 0: multi-node clusters
        must span a real jax.distributed job.
    timeout_ms: barrier timeout — how long a process waits for its peers
        before aborting a collective entry (PILOSA_COLLECTIVE_TIMEOUT_MS
        env keeps working as the per-process override).
    leaf_budget_bytes: resident sharded-stack budget per process; LRU
        past it, evicted planes demote through the tier manager
        (PILOSA_COLLECTIVE_LEAF_BYTES env override).
    delta_max_fraction: same contract as [engine] delta-max-fraction,
        for the collective plane's resident stacks: a stale resident
        global array refreshes by a per-device scattered update while
        the changed words stay under this fraction. 0 disables deltas
        (every staleness is a full re-assembly).
    """

    enabled: int = 1
    single_process: int = 0
    timeout_ms: int = 10000
    leaf_budget_bytes: int = 1 << 28
    delta_max_fraction: float = 0.25
