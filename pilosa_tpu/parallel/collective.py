"""Generalized multi-host collective query execution — the PRIMARY read
path for whole-index fast-path queries (docs/multichip.md).

The reference fans every call type out over HTTP and reduces in Python
(/root/reference/executor.go:1393-1440, 1464-1555). The TPU-native fast
path replaces that reduce loop with ONE SPMD program over a global device
mesh spanning every host's chips: each process feeds the shard planes it
owns, XLA inserts ICI/DCN collectives for the reductions, and the
all-reduced result materializes on every host.

Design (round-4 redesign of the round-3 CollectiveWorker, promoted to the
default serving path in PR 12):

- **Placement follows the cluster.** The leader derives each process's
  shard list from the REAL jump-hash placement (cluster/hash.py, reference
  cluster.go:776-857) and ships it in the descriptor; global array slots
  are ordered by process so every process contributes exactly the
  fragments it owns. Workers verify ownership of every assigned shard
  against their own cluster view and refuse loudly on mismatch — the
  round-3 block-contiguous layout silently counted unowned slots as zero.
- **Any fast-path call tree.** The descriptor carries the PQL string of
  the (already key-translated) call; every process compiles it with the
  shared engine compiler (parallel/engine.py _Compiler), so any
  Row/Intersect/Union/Difference/Xor/Range tree, TopN candidate counting,
  and BSI Sum/Min/Max run collectively — not just Count(Intersect).
  Descriptor signatures are the CANONICAL plan signature
  (plan/signature.py), so commutative/associative respellings of one
  query shape share one descriptor signature and one compiled program.
- **Resident sharded stacks.** Each process keeps its slice of the
  global (S, W) leaf planes and (U, S, W) stacks device-resident,
  invalidated by per-fragment (incarnation, generation) fingerprints.
  A stale resident array refreshes by a per-device scattered update of
  just the dirty words (core/fragment.py journals) while the change
  stays under ``delta-max-fraction``; the cold path consults the tier
  manager's compressed host image before walking live containers, and
  LRU-evicted planes DEMOTE through the same tier (docs/
  tiered-storage.md) — per-query host→device plane assembly is a cache
  miss, not the steady state.
- **Batched launches.** ``count_batch`` evaluates N same-canonical-
  signature queries in ONE descriptor: one KV sequence slot, one
  barrier, one SPMD program entry (the collective path's fixed costs).
  The sched micro-batcher feeds it (sched/batcher.py collective_count).
- **Failure semantics.** Every process passes a named barrier (the
  jax.distributed runtime's wait_at_barrier, with a timeout) BEFORE
  entering the device program. A dead or lagging peer times the barrier
  out everywhere; the leader falls back to the HTTP fan-out path and the
  peers simply skip — nobody blocks forever inside an all-reduce.
  Barrier timeouts and broadcast losses feed per-mesh-slice breakers
  (device_health.CollectivePlaneHealth): once open, queries skip the
  collective rung INSTANTLY instead of paying a barrier timeout each,
  and a half-open probe query re-closes the plane. Topology refusals
  (stale epoch, ownership, schema divergence) fall back WITHOUT
  advancing the breakers — membership churn must refresh descriptors,
  not disable the plane wholesale.
- **Epoch-aware membership.** Descriptors carry the leader's routing
  epoch; a peer whose epoch diverges refuses before computing (the
  leader re-routes through the fan-out, which has its own epoch gates),
  ownership is re-verified at entry time against the receiver's CURRENT
  view, and every process re-checks the epoch after plane assembly so a
  cutover committing mid-gather can never ride a GC'd fragment into a
  silently-empty contribution.
- **Total order.** Collective entry is serialized per process by a single
  runner thread consuming descriptors in cluster-wide sequence order
  (sequence numbers from the jax.distributed KV store's atomic increment),
  so concurrent leaders cannot interleave SPMD programs differently on
  different processes (deadlock/cross-wired results).
"""

from __future__ import annotations

import heapq
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import failpoints
from ..constants import VIEW_BSI_GROUP_PREFIX, WORDS_PER_ROW
from ..errors import PilosaError
from ..obs import current as obs_current, current_span as obs_current_span
from . import CollectiveConfig
from .device_health import BARRIER_TIMEOUT, BROADCAST, CollectivePlaneHealth
from .distributed import SHARD_AXIS, global_mesh

DEFAULT_TIMEOUT_MS = int(os.environ.get("PILOSA_COLLECTIVE_TIMEOUT_MS", "10000"))
_SPLIT = 0x7FFF  # 15-bit split keeps per-row sums exact without x64 (distributed._split_sum)


class CollectiveUnavailable(PilosaError):
    """The collective plane cannot (or must not) serve this request;
    callers fall back to the HTTP fan-out path. `reason` is the
    fallback-counter key (/debug/vars `collective.fallbacks`): breaker
    evidence only for reasons that indicate a FAULT (barrier-timeout,
    error) — topology churn (epoch, ownership, schema, placement,
    inactive) falls back without opening anything."""

    def __init__(self, message: str = "", reason: str = "error"):
        super().__init__(message)
        self.reason = reason


class CollectiveBarrierTimeout(CollectiveUnavailable):
    """A barrier wait expired: some participant never entered. The one
    failure kind that MUST advance the plane breaker — paying a full
    barrier timeout per query on a known-sick plane is the tax the
    breaker exists to remove."""

    def __init__(self, message: str = ""):
        super().__init__(message, reason="barrier-timeout")


def _dist_client():
    """The jax.distributed runtime client (barrier + KV store), or None
    outside a multi-process job."""
    try:
        from jax._src import distributed as jdist

        return jdist.global_state.client
    except (ImportError, AttributeError):  # pragma: no cover
        # jax._src.distributed is private API: absent (ImportError) or
        # reorganized (AttributeError) both read as "no runtime client".
        return None


def placement(cluster, index: str, n_shards: int, n_processes: int) -> List[List[int]]:
    """Per-process shard lists from the REAL cluster placement.

    Each shard goes to the process of its first available owner per
    jump-hash (cluster.go:776-857) — including per-shard routing
    overrides for committed live-rebalance cutovers (cluster/node.py
    shard_nodes follows Cluster.migrated), so a descriptor built
    mid-rebalance reflects the refreshed placement, not the pre-job one.
    Raises CollectiveUnavailable when any owning node's jax process
    index is unknown (node not in the job, or membership status hasn't
    propagated yet)."""
    slots: List[List[int]] = [[] for _ in range(n_processes)]
    for s in range(n_shards):
        owners = cluster.shard_nodes(index, s)
        owner = next(
            (n for n in owners if n.id not in cluster.unavailable), None
        ) or (owners[0] if owners else None)
        if owner is None:
            raise CollectiveUnavailable(
                f"no owner for shard {s}", reason="placement")
        p = owner.process_idx
        if p is None or not (0 <= p < n_processes):
            raise CollectiveUnavailable(
                f"node {owner.id} has no known jax process index",
                reason="placement",
            )
        slots[p].append(s)
    return slots


class CollectiveBackend:
    """Leader + peer sides of collective execution for one server process."""

    def __init__(self, server, config: Optional[CollectiveConfig] = None):
        self.server = server
        self.holder = server.holder
        self.logger = server.logger
        cfg = config or getattr(server, "collective_config", None)
        if cfg is None:
            # No resolved config (library/test use): honor the historical
            # env spellings directly. When a Config DID resolve the
            # [collective] section, flags > env > TOML already happened.
            cfg = CollectiveConfig(
                single_process=int(os.environ.get(
                    "PILOSA_COLLECTIVE_SINGLE_PROCESS", "0")),
                timeout_ms=DEFAULT_TIMEOUT_MS,
                leaf_budget_bytes=int(
                    os.environ.get("PILOSA_COLLECTIVE_LEAF_BYTES", 1 << 28)),
                delta_max_fraction=float(os.environ.get(
                    "PILOSA_COLLECTIVE_DELTA_MAX_FRACTION", "0.25")),
            )
        self.config = cfg
        self.enabled = bool(int(cfg.enabled))
        self.single_process = bool(int(cfg.single_process))
        self.timeout_ms = int(cfg.timeout_ms)
        # Per-device-count override for the MULTICHIP scaling curve:
        # restrict the global mesh to the first N devices (single-process
        # only — a multi-process mesh subset would break the
        # process-contiguity the slot layout assumes).
        self.mesh_devices: Optional[int] = None
        # Collective-plane breakers: barrier timeouts / broadcast losses
        # open per-slice and plane-wide breakers so a sick plane costs an
        # instant fallback, never a barrier timeout per query. Shares the
        # [resilience] section with the peer/device breakers.
        rcfg = getattr(
            getattr(getattr(server, "cluster", None), "health", None),
            "config", None)
        self.health = CollectivePlaneHealth(rcfg)
        # Compiled-program cache, entry-bounded LRU: keys embed baked Range
        # predicates, so varied predicates would otherwise pin one XLA
        # executable each forever (same bound as engine.py's fn caches).
        self._fn_cache: Dict[Tuple, object] = {}
        self._fn_budget = int(os.environ.get("PILOSA_FN_CACHE_ENTRIES", 256))
        # Resident sharded stacks: this process's slices of the global
        # leaf planes and (U, S, W) stacks, fingerprint-invalidated,
        # delta-refreshed, tier-demotable. One byte budget each.
        self._leaf_cache: Dict[Tuple, Tuple[Tuple, object]] = {}
        self._leaf_bytes = 0
        self._leaf_budget = int(cfg.leaf_budget_bytes)
        self._stack_cache: Dict[Tuple, Tuple[Tuple, object]] = {}
        self._stack_bytes = 0
        self._stack_budget = int(cfg.leaf_budget_bytes)
        self._delta_max_fraction = float(cfg.delta_max_fraction)
        self._lock = threading.Lock()
        self._local_seq = 0
        self.counters: Dict[str, int] = {
            "entries": 0,
            "served_count": 0, "served_topn": 0, "served_bsi": 0,
            "batched_entries": 0, "batched_launches": 0,
            "barrier_timeouts": 0, "breaker_short_circuits": 0,
            "resident_hits": 0, "delta_hits": 0, "delta_bytes": 0,
            "full_refreshes": 0, "full_refresh_bytes": 0,
            "tier_promotes": 0, "evictions": 0, "demotions": 0,
            "stale_epoch_refusals": 0, "epoch_rechecks": 0,
        }
        # Why the fast path refused, by CollectiveUnavailable.reason —
        # a climbing CollectiveFallback stat is undiagnosable without it.
        self.fallbacks: Dict[str, int] = {}
        self._runner = _Runner(self)
        # Descriptor broadcasts ride a shared pool: a thread per peer per
        # query would churn on the hot path (every full-index query).
        self._senders = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="collective-send"
        )

    # ------------------------------------------------------------ lifecycle

    def close(self) -> None:
        self._runner.close()
        self._senders.shutdown(wait=False)

    def active(self) -> bool:
        """True when the collective plane may serve whole-index queries:
        a multi-process jax job spanning the whole cluster, or (opt-in,
        `[collective] single-process`) a single-process job whose one
        node holds the whole index."""
        if not self.enabled:
            return False
        import jax

        n_proc = jax.process_count()
        cluster = self.server.cluster
        if n_proc <= 1:
            # One-pod mode: every fragment is local, the barrier is a
            # no-op, and the mesh is the local device mesh. Only safe
            # when the cluster IS this one node — a multi-node cluster
            # without a spanning jax job would count remote shards as
            # silently empty.
            return self.single_process and len(cluster.nodes) <= 1
        if cluster.unavailable:
            # A down node can't reach the barrier; entering would stall
            # every query the full barrier timeout before falling back.
            # The failure detector already knows — fall back instantly.
            return False
        nodes = cluster.nodes
        if len(nodes) != n_proc:
            return False
        return all(n.process_idx is not None for n in nodes)

    def _count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def note_fallback(self, reason: str) -> None:
        """Record WHY the fast path refused (the executor calls this on
        every CollectiveUnavailable it catches)."""
        with self._lock:
            self.fallbacks[reason] = self.fallbacks.get(reason, 0) + 1

    def snapshot(self) -> dict:
        """Wholesale counter export — the `collective` group in
        /debug/vars plus diagnostics aggregates (pilint R4)."""
        with self._lock:
            out = dict(self.counters)
            out["fallbacks"] = dict(self.fallbacks)
            out["leaf_cache_entries"] = len(self._leaf_cache)
            out["leaf_cache_bytes"] = self._leaf_bytes
            out["stack_cache_entries"] = len(self._stack_cache)
            out["stack_cache_bytes"] = self._stack_bytes
        out["health"] = self.health.snapshot()
        return out

    def _tier(self):
        """The engine's TierManager, when one exists: the collective
        plane's resident stacks demote into (and promote from) the SAME
        compressed host tier as the per-node engine caches — tier keys
        share the (index, leaf, shards) shape. Peeks the lazy engine
        slot only: cache maintenance must never be what first opens the
        device backend."""
        ex = getattr(self.server, "executor", None)
        eng = getattr(ex, "_engine", None)
        return getattr(eng, "tier", None)

    # ---------------------------------------------------------- leader side

    def count(self, index: str, call) -> int:
        out = self.count_batch(index, [call])
        return int(out[0])

    def count_batch(self, index: str, calls: Sequence) -> List[int]:
        """N same-canonical-signature Counts in ONE collective entry:
        one KV seq slot, one barrier, one SPMD program — the batched
        launch the sched micro-batcher feeds (docs/multichip.md). The
        calls need not be distinct; duplicates compute once and fan
        back out. Returns per-call counts in input order."""
        calls = list(calls)
        sig = self._call_sig(index, calls[0])
        desc = self._descriptor(
            "count", index, queries=[str(c) for c in calls], sig=sig,
        )
        lo, hi = self._lead(desc)
        lo = np.asarray(lo)
        hi = np.asarray(hi).astype(np.int64)
        with self._lock:
            self.counters["served_count"] += len(calls)
            if len(calls) > 1:
                self.counters["batched_entries"] += len(calls)
                self.counters["batched_launches"] += 1
        return [int(h << 15) + int(l) for l, h in zip(lo, hi)]

    def topn_counts(self, index: str, field: str, row_ids: Sequence[int],
                    src_call=None) -> np.ndarray:
        """Global per-row counts (optionally ∩ src bitmap) — the distributed
        TopN phase-2 inner loop, one SPMD program for the whole cluster."""
        desc = self._descriptor(
            "topn", index, field=field, rows=[int(r) for r in row_ids],
            query=str(src_call) if src_call is not None else None,
            sig=self._call_sig(index, src_call),
        )
        lo, hi = self._lead(desc)
        self._count("served_topn")
        return (np.asarray(hi).astype(np.int64) << 15) + np.asarray(lo)

    def bsi_val_count(self, index: str, field: str, kind: str, depth: int,
                      filter_call=None):
        """Collective BSI Sum/Min/Max (fragment.go:565-837 bit-slice scans
        over the global plane set). kind='sum' -> (depth+1,) per-plane
        global counts; 'min'/'max' -> (bits, count)."""
        desc = self._descriptor(
            "bsi", index, field=field, bsi_kind=kind, depth=depth,
            query=str(filter_call) if filter_call is not None else None,
            sig=self._call_sig(index, filter_call),
        )
        out = self._lead(desc)
        self._count("served_bsi")
        if kind == "sum":
            lo, hi = out
            return (np.asarray(hi).astype(np.int64) << 15) + np.asarray(lo)
        bits, count = out
        return np.asarray(bits), int(count)

    def _call_sig(self, index: str, call) -> Optional[str]:
        """CANONICAL structure signature of a compiled call (the plan
        compiler's sig_tuple, docs/query-compiler.md) — commutative/
        associative respellings of one shape produce the SAME descriptor
        signature, so they share one collective program and one batcher
        group. Shipped in the descriptor so peers can detect schema
        divergence (a lagging bsig depth/offset bakes DIFFERENT
        predicates into each side of the SPMD program — silently wrong
        sums) and refuse instead of computing."""
        if call is None:
            return None
        comp, _ = self._compile(index, call)
        return repr(self._sig_tuple(comp))

    @staticmethod
    def _sig_tuple(comp) -> Tuple:
        return (comp.plan.sig_tuple if comp.plan is not None
                else tuple(comp.signature))

    def _descriptor(self, kind: str, index: str, query: Optional[str] = None,
                    queries: Optional[List[str]] = None,
                    field: Optional[str] = None, rows: Optional[List[int]] = None,
                    bsi_kind: Optional[str] = None, depth: Optional[int] = None,
                    sig: Optional[str] = None) -> dict:
        import jax

        idx = self.holder.index(index)
        if idx is None:
            from ..errors import IndexNotFoundError

            raise IndexNotFoundError(index)
        n_shards = idx.max_shard() + 1
        n_proc = jax.process_count()
        mesh_devices = None
        if n_proc > 1:
            if not self.active():
                raise CollectiveUnavailable(
                    "jax.distributed job does not span the cluster "
                    f"({len(self.server.cluster.nodes)} nodes, {n_proc} processes)",
                    reason="inactive",
                )
            slots = placement(self.server.cluster, index, n_shards, n_proc)
            d_local = jax.local_device_count()
        else:
            slots = [list(range(n_shards))]
            mesh_devices = self.mesh_devices
            d_local = mesh_devices or jax.local_device_count()
        k = max(max(len(s) for s in slots), 1)
        k = ((k + d_local - 1) // d_local) * d_local
        return {
            "type": "collective-exec", "kind": kind,
            "index": index, "query": query, "queries": queries,
            "field": field, "rows": rows,
            "bsiKind": bsi_kind, "depth": depth, "nShards": n_shards,
            "slots": slots, "k": k, "timeoutMs": self.timeout_ms,
            "sig": sig, "meshDevices": mesh_devices,
            # The leader's routing view: peers whose epoch diverges
            # refuse (clean fan-out fallback) rather than contributing
            # planes placed under a different topology.
            "epoch": int(getattr(self.server.cluster, "routing_epoch", 0)),
        }

    def _next_seq(self) -> int:
        client = _dist_client()
        if client is not None:
            try:
                return int(client.key_value_increment("pilosa-collective-seq", 1))
            except Exception as e:
                raise CollectiveUnavailable(f"seq allocation failed: {e}")
        with self._lock:
            self._local_seq += 1
            return self._local_seq

    def _lead(self, desc: dict):
        """Gate on the plane breakers, allocate the sequence slot,
        broadcast the descriptor, enter locally, return the result.

        The broadcast must not wait for peer responses (a peer blocks
        inside the collective until every process enters), and any failure
        surfaces as CollectiveUnavailable so the executor falls back to
        the HTTP fan-out path. Fault outcomes (barrier timeout, runtime
        error) feed the breakers; topology refusals do not."""
        import jax

        n_proc = jax.process_count()
        slices = list(range(n_proc))
        if not self.health.allow(slices):
            # Breaker open: instant fallback — the whole point is never
            # paying a barrier timeout per query on a known-sick plane.
            self._count("breaker_short_circuits")
            raise CollectiveUnavailable(
                "collective plane breaker open", reason="breaker-open")
        # Seq allocated AFTER the gate: a refused query must not burn a
        # cluster-wide sequence slot (and a batch burns exactly one).
        desc["seq"] = self._next_seq()
        if n_proc > 1:
            for node in self.server.cluster.nodes:
                if node.id == self.server.cluster.node.id:
                    continue
                self._senders.submit(self._send, node, desc)
        local = dict(desc)
        # The runner thread has no obs context: the trace and the span
        # that is open here travel with the descriptor.
        local["_trace"] = obs_current()
        local["_span"] = obs_current_span()
        fut = self._runner.submit(local)
        try:
            result = fut.result(timeout=desc["timeoutMs"] / 1000.0 + 30.0)
        except CollectiveBarrierTimeout:
            self._count("barrier_timeouts")
            self.health.record_failure(BARRIER_TIMEOUT, slices)
            raise
        except CollectiveUnavailable as e:
            if e.reason == "error":
                # A real fault (runtime error, lost client), not
                # topology churn — evidence for the plane breaker.
                self.health.record_failure("runtime")
            raise
        except Exception as e:
            self.health.record_failure("runtime")
            raise CollectiveUnavailable(f"collective execution failed: {e}")
        self.health.record_success(slices)
        return result

    def _send(self, node, desc: dict) -> None:
        try:
            self.server.client.send_message(node, desc)
        except PilosaError as e:
            # The peer misses the descriptor; the barrier times out and
            # every process aborts cleanly instead of hanging. The
            # breaker evidence points at the unreachable slice.
            if node.process_idx is not None:
                self.health.record_failure(BROADCAST, [node.process_idx])
            self.logger.error("collective broadcast to %s failed: %s", node.id, e)

    # ------------------------------------------------------------ peer side

    def receive(self, desc: dict) -> None:
        """Peer side of the broadcast: enqueue and return immediately (the
        HTTP handler thread must not block inside the collective). Peers
        do NOT consult the breakers — a probing leader's barrier must
        find every healthy peer waiting, or the plane could never
        re-close under a single-leader workload."""
        self._runner.submit(desc)

    # ----------------------------------------------------------- execution

    def _enter(self, desc: dict):
        """Execute one descriptor. Called only from the runner thread, in
        cluster-wide seq order."""
        import jax

        trace = desc.get("_trace")
        t_entry = time.monotonic()
        index = desc["index"]
        n_proc = jax.process_count()
        pid = jax.process_index()
        slots = desc["slots"]
        k = int(desc["k"])
        self._count("entries")
        cluster = self.server.cluster
        epoch0 = int(getattr(cluster, "routing_epoch", 0))
        want_epoch = desc.get("epoch")
        if want_epoch is not None and int(want_epoch) != epoch0:
            # The leader routed under a different topology than ours
            # (mid-rebalance cutover window). Refuse before computing:
            # the leader falls back to the fan-out, whose per-hop epoch
            # gates serve the query correctly either way.
            self._count("stale_epoch_refusals")
            raise CollectiveUnavailable(
                f"routing epoch divergence (descriptor {want_epoch}, "
                f"local {epoch0})", reason="epoch")
        if len(slots) != n_proc:
            raise CollectiveUnavailable(
                f"descriptor spans {len(slots)} processes, job has {n_proc}",
                reason="placement",
            )
        my_shards = [int(s) for s in slots[pid]]
        if len(my_shards) > k:
            raise CollectiveUnavailable("slot range overflow",
                                        reason="placement")
        if n_proc > 1:
            self._verify_ownership(index, my_shards)
        mesh = global_mesh(desc.get("meshDevices") if n_proc == 1 else None)
        self._verify_mesh_layout(mesh, pid)
        s_padded = n_proc * k

        kind = desc["kind"]
        queries = desc.get("queries")
        if queries is None:
            queries = [desc["query"]] if desc.get("query") else []
        calls = []
        if queries:
            from ..pql.parser import parse

            calls = [parse(q).calls[0] for q in queries]

        if kind == "count":
            out = self._run_count(desc, index, calls, my_shards, k,
                                  s_padded, mesh, trace)
        elif kind == "topn":
            out = self._run_topn(desc, index, calls[0] if calls else None,
                                 my_shards, k, s_padded, mesh, trace)
        elif kind == "bsi":
            out = self._run_bsi(desc, index, calls[0] if calls else None,
                                my_shards, k, s_padded, mesh, trace)
        else:
            raise CollectiveUnavailable(f"unknown collective kind: {kind}")
        if int(getattr(cluster, "routing_epoch", 0)) != epoch0:
            # A live-rebalance cutover committed while planes were being
            # assembled/computed: post-commit GC may have read a moved
            # shard's fragment as silently empty. Discard — the leader
            # re-runs through the fan-out on refreshed placement.
            self._count("epoch_rechecks")
            raise CollectiveUnavailable(
                f"routing epoch advanced during collective execution "
                f"({epoch0} -> {cluster.routing_epoch})", reason="epoch")
        if trace is not None:
            trace.record("collective.entry",
                         (time.monotonic() - t_entry) * 1000.0,
                         parent=desc.get("_span"),
                         kind=kind, seq=desc.get("seq"))
        return out

    def _verify_ownership(self, index: str, my_shards: List[int]) -> None:
        """Refuse loudly when the leader's placement disagrees with this
        node's cluster view — silently contributing zero planes for
        unowned shards is a wrong count (ADVICE r3 high)."""
        cluster = self.server.cluster
        me = cluster.node.id
        for s in my_shards:
            if not cluster.owns_shard(me, index, s):
                raise CollectiveUnavailable(
                    f"placement mismatch: process assigned shard {s} of "
                    f"{index!r} but node {me} does not own it",
                    reason="ownership",
                )

    @staticmethod
    def _verify_mesh_layout(mesh, pid: int) -> None:
        """make_array_from_process_local_data assumes this process's devices
        hold the contiguous slot block [pid*k, (pid+1)*k); that holds only
        when mesh device order is process-contiguous. Check, don't assume."""
        devs = list(mesh.devices.flat)
        mine = [i for i, d in enumerate(devs) if d.process_index == pid]
        if not mine:
            raise CollectiveUnavailable(
                "this process owns no devices in the global mesh",
                reason="placement",
            )
        if mine != list(range(pid * len(mine), (pid + 1) * len(mine))):
            raise CollectiveUnavailable(
                "global device order is not process-contiguous; "
                "collective slot layout would misplace shards",
                reason="placement",
            )

    def _barrier(self, desc: dict, trace=None) -> None:
        import jax

        t0 = time.monotonic()
        try:
            # Deterministic chaos hook (docs/durability.md R6 table):
            # fires even in single-process mode, where the real barrier
            # is a no-op, so the MULTICHIP chaos leg exercises the
            # timeout -> breaker -> fallback ladder on one pod.
            failpoints.fire("collective-barrier")
            if jax.process_count() > 1:
                client = _dist_client()
                if client is None:
                    raise CollectiveUnavailable(
                        "no distributed runtime client")
                client.wait_at_barrier(
                    f"pilosa-collective-{desc['seq']}", int(desc["timeoutMs"])
                )
        except CollectiveUnavailable:
            raise
        except Exception as e:
            raise CollectiveBarrierTimeout(
                f"collective barrier timed out (seq {desc['seq']}): {e}"
            )
        finally:
            if trace is not None:
                trace.record("collective.barrier",
                             (time.monotonic() - t0) * 1000.0,
                             parent=desc.get("_span"),
                             seq=desc.get("seq"))

    # ------------------------------------------------- resident plane stacks

    def _local_block(self, index: str, leaf, my_shards: List[int], k: int,
                     frags: Optional[List] = None) -> np.ndarray:
        buf = np.zeros((k, WORDS_PER_ROW), dtype=np.uint32)
        if frags is None:
            frags = [self.holder.fragment(index, leaf.field, leaf.view, s)
                     for s in my_shards]
        for i, frag in enumerate(frags):
            if frag is not None:
                buf[i] = frag.plane_np(leaf.row)
        return buf

    def _leaf_fingerprint(self, index: str, leaf, my_shards: List[int],
                          frags: Optional[List] = None) -> Tuple:
        # (incarnation, generation) pairs, as in engine._fingerprint: a
        # deleted-and-recreated index resets generation counters while this
        # name-keyed cache survives, and a bare counter climbing back to a
        # cached value would alias the old index's stale plane.
        if frags is None:
            frags = (
                self.holder.fragment(index, leaf.field, leaf.view, s)
                for s in my_shards
            )
        return tuple(
            -1 if f is None else (f.incarnation, f.generation)
            for f in frags
        )

    def _collect_updates(self, members, size: int):
        """Dirty-word deltas for stale cache members, or None when only a
        full re-assembly is safe — same contract as the engine's
        _collect_updates (missing fragment, recreated incarnation,
        journal overflow, or budget exceeded all poison to None).

        `members`: iterable of (coords, frag, row, old_fp, new_fp);
        coords are LOCAL block coordinates ((slot,) for a leaf,
        (u, slot) for a stack). Returns a list of (coords, col32
        indices, uint32 values) — possibly empty (generation churn from
        rows outside this cache, zero bytes to move)."""
        from .engine import ShardedQueryEngine

        out = []
        n32 = 0
        for coords, frag, row, old_fp, new_fp in members:
            if frag is None or old_fp == -1 or new_fp == -1:
                return None
            if old_fp[0] != new_fp[0] or frag.incarnation != new_fp[0]:
                return None
            w = frag.dirty_words_since(row, old_fp[1])
            if w is None:
                return None
            if not len(w):
                continue
            n32 += 2 * len(w)
            if n32 > self._delta_max_fraction * size:
                return None
            cols, vals = ShardedQueryEngine._updates32(
                w, frag.row_words64(row, w))
            out.append((coords, cols, vals))
        return out

    def _delta_scatter(self, arr, updates, pid: int, k: int, stacked: bool):
        """Apply (coords, cols, vals) updates to this process's
        addressable pieces of a global array and reassemble — the
        multi-process-safe delta path. Each piece is a SINGLE-DEVICE
        array, so the scatter is a local program (no collectives, no
        peer coordination); pieces without dirty words are reused
        as-is, so a 1-bit write moves a handful of scattered words to
        exactly one device instead of re-uploading the plane."""
        import jax

        from .engine import ShardedQueryEngine

        slot_axis = 1 if stacked else 0
        pieces = []
        for sh in arr.addressable_shards:
            sl = sh.index[slot_axis]
            lo = sl.start or 0
            hi = sl.stop if sl.stop is not None else arr.shape[slot_axis]
            sel = [(co, pid * k + co[-1] - lo, cols, vals)
                   for co, cols, vals in updates
                   if lo <= pid * k + co[-1] < hi]
            if not sel:
                pieces.append(sh.data)
                continue
            rows = np.concatenate(
                [np.full(len(c), r, np.int32) for _, r, c, _ in sel])
            cols = np.concatenate([c for _, _, c, _ in sel])
            vals = np.concatenate([v for _, _, _, v in sel])
            if stacked:
                us = np.concatenate(
                    [np.full(len(c), co[0], np.int32) for co, _, c, _ in sel])
                us, rows, cols, vals = ShardedQueryEngine._pad_updates(
                    [us, rows, cols, vals])
                fn = self._fn(
                    ("scatter3", sh.data.shape, len(rows)),
                    lambda: jax.jit(
                        lambda a, u, r, c, v: a.at[u, r, c].set(v)))
                pieces.append(fn(sh.data, us, rows, cols, vals))
            else:
                rows, cols, vals = ShardedQueryEngine._pad_updates(
                    [rows, cols, vals])
                fn = self._fn(
                    ("scatter2", sh.data.shape, len(rows)),
                    lambda: jax.jit(lambda a, r, c, v: a.at[r, c].set(v)))
                pieces.append(fn(sh.data, rows, cols, vals))
        return jax.make_array_from_single_device_arrays(
            arr.shape, arr.sharding, pieces)

    def _byte_put(self, cache: Dict, key, entry: Tuple, budget: int,
                  used: int, evicted: Optional[List] = None) -> int:
        """Insert at MRU, evict LRU past the byte budget; returns updated
        used-bytes. Caller holds self._lock. Evicted keys collect into
        `evicted` for off-lock tier demotion — eviction is demotion, not
        loss (docs/tiered-storage.md)."""
        prev = cache.pop(key, None)
        if prev is not None:
            used -= prev[1].nbytes
        used += entry[1].nbytes
        cache[key] = entry
        while used > budget and len(cache) > 1:
            old_key = next(iter(cache))
            if old_key == key:
                break
            used -= cache.pop(old_key)[1].nbytes
            self.counters["evictions"] += 1
            if evicted is not None:
                evicted.append(old_key)
        return used

    def _demote_keys(self, keys) -> None:
        """Hand evicted resident planes to the tier manager (off-lock):
        the compressed host image makes the next cold assembly a decode,
        not a container walk. Keys are cache keys; the tier key is their
        (index, leaf, shards) prefix — the same key space the engine
        uses, so the two planes share one inclusive host tier."""
        if not keys:
            return
        tier = self._tier()
        if tier is None:
            return
        from ..plan import Leaf

        for key in keys:
            index, leaves, shards = key[0], key[1], key[2]
            # Leaf IS a NamedTuple: a leaf-cache key holds one Leaf, a
            # stack-cache key holds a tuple of them — a bare tuple check
            # would iterate a single Leaf's fields.
            if isinstance(leaves, Leaf):
                leaves = (leaves,)
            for leaf in leaves:
                if tier.demote((index, leaf, shards)):
                    self._count("demotions")

    def _global_leaf(self, index: str, leaf, my_shards: List[int], k: int,
                     s_padded: int, mesh):
        """(S_padded, W) global array for one leaf — RESIDENT: cached per
        process, invalidated by this process's OWN fragment generations
        (each process's buffers are local, so staleness is a local
        property), delta-refreshed from the dirty-word journals, and
        assembled from the compressed tier image when cold."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        pid = jax.process_index()
        # Mesh identity in the key: the same (shards, k, s_padded) over a
        # DIFFERENT mesh width (mesh_devices scaling) is a different
        # device layout — a cross-mesh resident hit would silently serve
        # the old layout.
        key = (index, leaf, tuple(my_shards), k, s_padded,
               int(mesh.devices.size))
        frags = [self.holder.fragment(index, leaf.field, leaf.view, s)
                 for s in my_shards]
        fp = self._leaf_fingerprint(index, leaf, my_shards, frags)
        with self._lock:
            cached = self._leaf_cache.get(key)
            if cached is not None and cached[0] == fp:
                self._leaf_cache[key] = self._leaf_cache.pop(key)  # LRU touch
                self.counters["resident_hits"] += 1
                return cached[1]
            stale = cached
        evicted: List = []
        if stale is not None and self._delta_max_fraction > 0 \
                and len(stale[0]) == len(fp):
            updates = self._collect_updates(
                (((i,), frags[i], leaf.row, stale[0][i], fp[i])
                 for i in range(len(frags)) if stale[0][i] != fp[i]),
                stale[1].size,
            )
            if updates is not None:
                arr = (stale[1] if not updates else self._delta_scatter(
                    stale[1], updates, pid, k, stacked=False))
                moved = sum(c.nbytes + v.nbytes for _, c, v in updates)
                with self._lock:
                    self.counters["delta_hits"] += 1
                    self.counters["delta_bytes"] += moved
                    self._leaf_bytes = self._byte_put(
                        self._leaf_cache, key, (fp, arr),
                        self._leaf_budget, self._leaf_bytes, evicted)
                self._demote_keys(evicted)
                return arr
        # Cold (or delta-ineligible): compressed tier image first, live
        # container walk second.
        block = None
        tier = self._tier()
        if tier is not None:
            block = tier.promote((index, leaf, tuple(my_shards)), frags, fp, k)
        tier_hit = block is not None
        if block is None:
            block = self._local_block(index, leaf, my_shards, k, frags)
        sharding = NamedSharding(mesh, P(SHARD_AXIS, None))
        arr = jax.make_array_from_process_local_data(
            sharding, block, (s_padded, WORDS_PER_ROW)
        )
        with self._lock:
            if tier_hit:
                self.counters["tier_promotes"] += 1
            self.counters["full_refreshes"] += 1
            self.counters["full_refresh_bytes"] += int(block.nbytes)
            self._leaf_bytes = self._byte_put(
                self._leaf_cache, key, (fp, arr),
                self._leaf_budget, self._leaf_bytes, evicted)
        self._demote_keys(evicted)
        return arr

    def _global_stack(self, index: str, leaves, my_shards: List[int], k: int,
                      s_padded: int, mesh):
        """(L, S_padded, W) global array for a leaf stack (TopN rows, BSI
        planes) — RESIDENT like the leaves: fingerprint-invalidated,
        delta-refreshed per device piece, LRU-bounded. BSI plane sets
        are stable per field (big win); TopN candidate stacks cache per
        rows-tuple so repeated hot TopNs stop re-walking containers."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        pid = jax.process_index()
        leaves = list(leaves)
        key = (index, tuple(leaves), tuple(my_shards), k, s_padded,
               int(mesh.devices.size))
        frags = [
            [self.holder.fragment(index, leaf.field, leaf.view, s)
             for s in my_shards]
            for leaf in leaves
        ]
        fp = tuple(
            self._leaf_fingerprint(index, leaf, my_shards, frags[u])
            for u, leaf in enumerate(leaves)
        )
        with self._lock:
            cached = self._stack_cache.get(key)
            if cached is not None and cached[0] == fp:
                self._stack_cache[key] = self._stack_cache.pop(key)
                self.counters["resident_hits"] += 1
                return cached[1]
            stale = cached
        evicted: List = []
        if stale is not None and self._delta_max_fraction > 0 \
                and len(stale[0]) == len(fp) \
                and all(len(o) == len(n) for o, n in zip(stale[0], fp)):

            def members():
                for u, leaf in enumerate(leaves):
                    if stale[0][u] == fp[u]:
                        continue
                    for i in range(len(my_shards)):
                        if stale[0][u][i] == fp[u][i]:
                            continue
                        yield ((u, i), frags[u][i], leaf.row,
                               stale[0][u][i], fp[u][i])

            updates = self._collect_updates(members(), stale[1].size)
            if updates is not None:
                arr = (stale[1] if not updates else self._delta_scatter(
                    stale[1], updates, pid, k, stacked=True))
                moved = sum(c.nbytes + v.nbytes for _, c, v in updates)
                with self._lock:
                    self.counters["delta_hits"] += 1
                    self.counters["delta_bytes"] += moved
                    self._stack_bytes = self._byte_put(
                        self._stack_cache, key, (fp, arr),
                        self._stack_budget, self._stack_bytes, evicted)
                self._demote_keys(evicted)
                return arr
        tier = self._tier()
        blocks = []
        for u, leaf in enumerate(leaves):
            block = None
            if tier is not None:
                block = tier.promote(
                    (index, leaf, tuple(my_shards)), frags[u], fp[u], k)
            if block is not None:
                self._count("tier_promotes")
            else:
                block = self._local_block(index, leaf, my_shards, k, frags[u])
            blocks.append(block)
        block = np.stack(blocks)
        sharding = NamedSharding(mesh, P(None, SHARD_AXIS, None))
        arr = jax.make_array_from_process_local_data(
            sharding, block, (len(leaves), s_padded, WORDS_PER_ROW)
        )
        with self._lock:
            self.counters["full_refreshes"] += 1
            self.counters["full_refresh_bytes"] += int(block.nbytes)
            self._stack_bytes = self._byte_put(
                self._stack_cache, key, (fp, arr),
                self._stack_budget, self._stack_bytes, evicted)
        self._demote_keys(evicted)
        return arr

    def _compile(self, index: str, call):
        from .engine import _Compiler

        comp = _Compiler(self.holder, index)
        expr = comp.compile(call)
        return comp, expr

    def _fn(self, key: Tuple, build):
        with self._lock:
            fn = self._fn_cache.get(key)
            if fn is not None:
                self._fn_cache[key] = self._fn_cache.pop(key)  # LRU touch
        if fn is None:
            fn = build()
            with self._lock:
                self._fn_cache[key] = fn
                while len(self._fn_cache) > self._fn_budget:
                    self._fn_cache.pop(next(iter(self._fn_cache)))
        return fn

    # -------------------------------------------------------- program kinds

    def _check_sig(self, desc, comp) -> None:
        """Refuse when this process compiled a different program structure
        than the leader (schema divergence: a lagging bsig depth/offset
        bakes different predicates into each side of the SPMD program)."""
        want = desc.get("sig")
        if want is not None and repr(self._sig_tuple(comp)) != want:
            raise CollectiveUnavailable(
                "schema divergence: local call signature "
                f"{self._sig_tuple(comp)!r} != leader's {want}",
                reason="schema",
            )

    def _run_count(self, desc, index, calls, my_shards, k, s_padded, mesh,
                   trace=None):
        import jax
        import jax.numpy as jnp

        # Duplicates (N clients asking the SAME hot query) compute once;
        # padding to a pow2 batch size keeps the compiled-program count
        # logarithmic in batch_max instead of linear.
        queries = [str(c) for c in calls]
        uniq: Dict[str, int] = {}
        ucalls = []
        for q, c in zip(queries, calls):
            if q not in uniq:
                uniq[q] = len(ucalls)
                ucalls.append(c)
        comps = [self._compile(index, c) for c in ucalls]
        for comp, _ in comps:
            self._check_sig(desc, comp)
        all_leaves = [
            tuple(self._global_leaf(index, leaf, my_shards, k, s_padded, mesh)
                  for leaf in comp.leaves)
            for comp, _ in comps
        ]
        n = len(all_leaves)
        n_pad = 1 << (n - 1).bit_length() if n else 1
        all_leaves = tuple(all_leaves + [all_leaves[0]] * (n_pad - n))
        expr = comps[0][1]
        sig = ("count", self._sig_tuple(comps[0][0]), n_pad, s_padded,
               int(mesh.devices.size))

        def build():
            @jax.jit
            def fn(lvs):
                los, his = [], []
                for lv in lvs:
                    pc = jax.lax.population_count(expr(lv)).astype(jnp.int32)
                    per = jnp.sum(pc, axis=1)  # (S,) partials, each <= 2^20
                    los.append(jnp.sum(per & _SPLIT))
                    his.append(jnp.sum(per >> 15))
                return jnp.stack(los), jnp.stack(his)

            return fn

        fn = self._fn(sig, build)
        self._barrier(desc, trace)
        lo, hi = fn(all_leaves)
        lo = np.asarray(lo)
        hi = np.asarray(hi)
        order = [uniq[q] for q in queries]
        return lo[order], hi[order]

    def _run_topn(self, desc, index, call, my_shards, k, s_padded, mesh,
                  trace=None):
        import jax
        import jax.numpy as jnp

        from .engine import Leaf
        from ..constants import VIEW_STANDARD

        field = desc["field"]
        rows = [int(r) for r in desc["rows"]]
        leaves = [Leaf(field, VIEW_STANDARD, r) for r in rows]
        stacked = self._global_stack(index, leaves, my_shards, k, s_padded, mesh)
        src_leaves = None
        fsig = ()
        expr = None
        if call is not None:
            comp, expr = self._compile(index, call)
            self._check_sig(desc, comp)
            src_leaves = tuple(
                self._global_leaf(index, leaf, my_shards, k, s_padded, mesh)
                for leaf in comp.leaves
            )
            fsig = self._sig_tuple(comp)
        sig = ("topn", fsig, len(rows), s_padded, int(mesh.devices.size))

        def build():
            @jax.jit
            def fn(stacked, src_lv):
                x = stacked
                if expr is not None:
                    x = jnp.bitwise_and(x, expr(src_lv)[None])
                pc = jax.lax.population_count(x).astype(jnp.int32)
                per = jnp.sum(pc, axis=2)  # (R, S)
                return jnp.sum(per & _SPLIT, axis=1), jnp.sum(per >> 15, axis=1)

            return fn

        fn = self._fn(sig, build)
        self._barrier(desc, trace)
        lo, hi = fn(stacked, src_leaves)
        return np.asarray(lo), np.asarray(hi)

    def _run_bsi(self, desc, index, call, my_shards, k, s_padded, mesh,
                 trace=None):
        import jax
        import jax.numpy as jnp

        from .engine import Leaf

        field = desc["field"]
        depth = int(desc["depth"])
        kind = desc["bsiKind"]
        # The plane layout itself depends on the bsig depth: a peer whose
        # depth disagrees would read its bit-i planes as different
        # magnitudes than the leader. Verify, don't assume.
        fld = self.holder.field(index, field)
        bsig = fld.bsi_group(field) if fld is not None else None
        if bsig is None or bsig.bit_depth() != depth:
            local = "missing" if bsig is None else bsig.bit_depth()
            raise CollectiveUnavailable(
                f"schema divergence: bsig depth for {field!r} is {local}, "
                f"leader says {depth}", reason="schema",
            )
        view = VIEW_BSI_GROUP_PREFIX + field
        leaves = [Leaf(field, view, i) for i in range(depth + 1)]
        planes = self._global_stack(index, leaves, my_shards, k, s_padded, mesh)
        filter_leaves = None
        fsig = ()
        expr = None
        if call is not None:
            comp, expr = self._compile(index, call)
            self._check_sig(desc, comp)
            filter_leaves = tuple(
                self._global_leaf(index, leaf, my_shards, k, s_padded, mesh)
                for leaf in comp.leaves
            )
            fsig = self._sig_tuple(comp)
        sig = ("bsi", kind, depth, fsig, s_padded, int(mesh.devices.size))

        def build():
            def total(x):
                pc = jax.lax.population_count(x).astype(jnp.int32)
                per = jnp.sum(pc, axis=-1)
                return jnp.sum(per)

            if kind == "sum":
                @jax.jit
                def fn(planes, flt):
                    x = planes
                    if expr is not None:
                        x = jnp.bitwise_and(x, expr(flt)[None])
                    pc = jax.lax.population_count(x).astype(jnp.int32)
                    per = jnp.sum(pc, axis=2)  # (D+1, S)
                    return (
                        jnp.sum(per & _SPLIT, axis=1),
                        jnp.sum(per >> 15, axis=1),
                    )
            else:
                maximize = kind == "max"

                @jax.jit
                def fn(planes, flt):
                    consider = planes[depth]
                    if expr is not None:
                        consider = jnp.bitwise_and(consider, expr(flt))
                    bits = []
                    for i in range(depth - 1, -1, -1):
                        if maximize:
                            x = jnp.bitwise_and(planes[i], consider)
                        else:
                            x = jnp.bitwise_and(consider, jnp.bitwise_not(planes[i]))
                        nonzero = total(x) > 0
                        bit = (
                            jnp.where(nonzero, 1, 0)
                            if maximize
                            else jnp.where(nonzero, 0, 1)
                        )
                        bits.append(bit.astype(jnp.int32))
                        consider = jnp.where(nonzero, x, consider)
                    bits = (
                        jnp.stack(bits[::-1])
                        if bits
                        else jnp.zeros((0,), jnp.int32)
                    )
                    return bits, total(consider)

            return fn

        fn = self._fn(sig, build)
        self._barrier(desc, trace)
        out = fn(planes, filter_leaves)
        if kind == "sum":
            lo, hi = out
            return np.asarray(lo), np.asarray(hi)
        bits, count = out
        return np.asarray(bits), int(count)


class _Runner:
    """Single consumer thread executing descriptors in cluster-wide seq
    order. Seqs are dense except when a leader dies between allocating a
    seq and broadcasting it; a bounded gap wait keeps a dead leader from
    stalling the queue (its own peers' barrier times out regardless)."""

    GAP_TIMEOUT = 2.0

    def __init__(self, backend: CollectiveBackend):
        self.backend = backend
        self._heap: List[Tuple[int, int, dict, Future]] = []
        self._tiebreak = 0
        self._cond = threading.Condition()
        self._last_seq = 0
        self._closed = False
        self._thread: Optional[threading.Thread] = None

    def submit(self, desc: dict) -> Future:
        fut: Future = Future()
        with self._cond:
            if self._closed:
                fut.set_exception(CollectiveUnavailable(
                    "collective runner closed", reason="closed"))
                return fut
            self._tiebreak += 1
            heapq.heappush(
                self._heap, (int(desc["seq"]), self._tiebreak, desc, fut)
            )
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, name="collective-runner", daemon=True
                )
                self._thread.start()
            self._cond.notify_all()
        return fut

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._heap and not self._closed:
                    self._cond.wait()
                if self._closed:
                    for _, _, _, fut in self._heap:
                        if not fut.done():
                            fut.set_exception(CollectiveUnavailable(
                                "collective runner closed", reason="closed"))
                    self._heap.clear()
                    return
                # In-order delivery: wait (bounded) for a missing seq so all
                # processes execute collectives in the same order.
                deadline = time.monotonic() + self.GAP_TIMEOUT
                while (
                    self._heap
                    and self._heap[0][0] > self._last_seq + 1
                    and not self._closed
                ):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(timeout=remaining)
                if not self._heap:
                    continue
                seq, _, desc, fut = heapq.heappop(self._heap)
                if seq <= self._last_seq:
                    # A gap-skipped descriptor arrived late: its other
                    # participants already timed out at its barrier, and
                    # entering it now would both stall this runner for the
                    # full barrier timeout and break the same-order
                    # invariant. Reject, never execute.
                    fut.set_exception(CollectiveUnavailable(
                        f"stale collective seq {seq} (already past "
                        f"{self._last_seq})", reason="stale-seq",
                    ))
                    continue
                self._last_seq = seq
            try:
                result = self.backend._enter(desc)
            except BaseException as e:
                if not fut.done():
                    fut.set_exception(e)
                continue
            if not fut.done():
                fut.set_result(result)
