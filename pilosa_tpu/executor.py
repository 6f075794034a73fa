"""PQL executor: recursive evaluator + distributed map/reduce.

Port of /root/reference/executor.go. Per-shard bitmap math runs on device
bitplanes (ops/bitplane.py via core/fragment.py); this module owns call
dispatch, the shard map/reduce (executor.go:1464-1593), two-phase TopN
(executor.go:524-560), writes, and string-key translation
(executor.go:1595-1699).
"""

from __future__ import annotations

import threading
import time as _time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .constants import MAX_WRITES_PER_REQUEST, SHARD_WIDTH, VIEW_BSI_GROUP_PREFIX, VIEW_STANDARD
from .core.cache import Pair, add_pairs, sort_pairs, thread_rank_rebuilds
from .core.fragment import Fragment, TopOptions
from .core.holder import Holder
from .core.row import Row
from .errors import (
    FieldNotFoundError,
    BSIGroupNotFoundError,
    IndexNotFoundError,
    PilosaError,
    QueryError,
    TooManyWritesError,
)
from .obs import (
    NOP_SPAN, current as obs_current, current_span as obs_current_span,
    span as obs_span,
)
from .parallel.device_health import DeviceDispatchError
from .pql import parser as pql_parser
from .pql.ast import BETWEEN, Call, Condition, GT, GTE, LT, LTE, NEQ
from .timeq import parse_timestamp, views_by_time_range

DEFAULT_FIELD = "general"
DEFAULT_MIN_THRESHOLD = 1


def _topn_chunk(n_shards: int) -> int:
    """Candidate rows per TopN device program, bounded by BYTES alone: each
    row costs n_shards * 128 KiB in the stacked tensor, so the byte budget
    (PILOSA_TOPN_CHUNK_BYTES, default 2 GiB) gives 16,384 rows at one
    shard, 256 at 64 and 64 at 256. It trades launches per TopN against
    the stack's working set: every launch pays the host's whole per-launch
    cost (probes, fingerprints, a wait for the device), so a fixed row cap
    under the budget only multiplied launches (17 for 8,208 rows at one
    shard under a cap of 512, PERF.md PR 41). Row counts pad in the engine
    (parallel/engine.py padded_rows) so varied chunk sizes reuse compiled
    programs. The floor is ONE row: at extreme shard counts even 16 rows
    overrun the budget (16 rows x 4096 shards x 128 KiB = 8 GiB), and a
    single row per program is the smallest dispatch that still makes
    progress."""
    import os

    from .constants import WORDS_PER_ROW

    budget = int(os.environ.get("PILOSA_TOPN_CHUNK_BYTES", 2 << 30))
    return max(1, budget // max(1, n_shards * WORDS_PER_ROW * 4))


def _rank_matrix(rankings):
    """Per-shard rankings (Fragment.top_arrays: ids and cache counts in
    rank order) laid side by side as two (shards, ranks) int64 arrays. A
    shard that ranks fewer rows is padded with count 0, which no
    candidate rule lets through."""
    width = max((len(ids) for ids, _ in rankings), default=0)
    shape = (len(rankings), width)
    if not width:
        return np.zeros(shape, np.int64), np.zeros(shape, np.int64)
    pad = np.zeros(width, np.int64)
    ids_parts, cnt_parts = [], []
    for ids, cnt in rankings:
        ids_parts.append(ids)
        cnt_parts.append(cnt)
        if len(ids) < width:
            ids_parts.append(pad[len(ids):])
            cnt_parts.append(pad[len(ids):])
    return (np.concatenate(ids_parts).reshape(shape),
            np.concatenate(cnt_parts).reshape(shape))


def _tanimoto_passes(count, cnt, src, tanimoto):
    """The reference's coefficient test (fragment.go:1008-1027) in float64,
    cell by cell as Fragment.top computes it: ceil(count * 100.0 /
    (cnt + src - count)) > tanimoto. Cells with nothing to divide by
    (count, cnt and src all 0) fail it, as they fail count > 0."""
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.ceil(count * 100.0 / (cnt + src - count)) > tanimoto


def _replay_topn(cnt, count, cand, src, n, min_threshold, tanimoto):
    """Fragment.top's heap selection (fragment.go:899-990) for every shard
    at once. `cnt` (cache counts), `count` (intersections with src) and
    `cand` (which cells are candidates) are (shards, ranks) in rank order,
    `src` is (shards,). Returns the mask of accepted cells; what `count`
    reads where `cand` is false is never looked at.

    The heap never evicts, and once it holds `n` every later push is at
    least its minimum, so the threshold a shard applies after the fill is
    ONE number: the smallest of the first `n` accepted counts. Cache
    counts fall along the rank axis, so "stop at the first cnt <
    threshold" is `cnt >= threshold` cell by cell."""
    if tanimoto:
        src = src[:, None]
        # Bounds pruning (Fragment._filter_candidates), now that src is
        # counted: cnt outside them cannot reach the coefficient.
        cand = cand & ~((src > 0) & ((cnt <= src * tanimoto / 100.0)
                                     | (cnt >= src * 100.0 / tanimoto)))
        fill = cand & (count > 0) & _tanimoto_passes(count, cnt, src, tanimoto)
    else:
        fill = cand & (count >= min_threshold)
    if n == 0:
        return fill
    filling = np.cumsum(fill, axis=1) - fill < n  # accepted so far < n
    fill &= filling
    top = np.iinfo(np.int64).max
    threshold = np.where(fill, count, top).min(axis=1, initial=top)[:, None]
    after = cand & ~filling & (cnt >= threshold) & (count >= threshold)
    if tanimoto:  # else every accepted count is min_threshold or more
        after &= threshold >= min_threshold
    return fill | after


# Shard lists whose owners Executor._shard_owners keeps at once (an entry
# is a copy of the list and a pointer a shard; ids are shared). Emptied
# when full: queries name few distinct lists, one per index as a rule.
_OWNERS_KEPT = 64

_WRITE_CALLS = {"Set", "Clear", "SetValue", "SetRowAttrs", "SetColumnAttrs"}


def _is_node_failure(e) -> bool:
    """True when a ClientError indicates the NODE failed (connect/transport
    error carries status 0, server fault is 5xx) rather than the REQUEST
    (4xx application errors are deterministic: the peer is healthy and
    every replica would answer the same). A deadline-expiry 503 is the
    REQUEST's budget running out on a healthy peer — one client's tight
    deadline must not mark nodes unavailable and poison routing."""
    status = getattr(e, "status", 0)
    if status == 503 and ("deadline exceeded" in str(e)
                          or "write consistency" in str(e)):
        # Deadline expiry is the REQUEST's budget dying on a healthy peer;
        # a write-consistency 503 is the PEER's own replica set being
        # degraded — both are deterministic answers from a live node, not
        # evidence the node itself failed.
        return False
    return status == 0 or status >= 500


@dataclass
class ExecOptions:
    remote: bool = False
    exclude_row_attrs: bool = False
    exclude_columns: bool = False
    column_attrs: bool = False
    # Per-request time budget (sched/deadline.py), installed at admission.
    # Checked before every device dispatch and every remote fan-out hop so
    # an expired query stops consuming device time instead of pinning
    # threads; the REMAINING budget rides forwarded requests' headers.
    deadline: Optional[Any] = None
    # Sender's routing epoch on forwarded requests (live rebalance,
    # cluster/rebalance.py): when this node has advanced past it AND no
    # longer serves a requested shard, the request 409s so the sender
    # re-routes once — never an empty answer from a migrated/GC'd shard.
    epoch: Optional[int] = None
    # LOCAL routing epoch captured by execute() before the stale-epoch
    # gate (remote requests only): the post-gather re-check in _fan_out
    # compares against this anchor, so a cutover committing anywhere in
    # the window from gate to gather end — translation, or an earlier
    # call of a multi-call query — is still detected. Anchoring inside
    # _fan_out would capture a post-cutover epoch and miss the GC.
    entry_epoch: Optional[int] = None
    # Point-in-time read (cdc/): execute against fragments materialized
    # at this CDC position (base image + op replay, cdc/pit.py) instead
    # of live storage. Read-only, node-local, requires cdc.enabled.
    at_position: Optional[int] = None
    # Bounded-staleness read (geo/, X-Pilosa-Max-Staleness header): on a
    # geo follower, serve locally only when replication lag <= this many
    # seconds, else raise StaleReadError (409) carrying the current lag.
    # No-op on a leader or non-geo node: local state is the source of
    # truth there, never stale (docs/geo-replication.md).
    max_staleness: Optional[float] = None
    # QoS budget identity (X-Pilosa-Tenant header, default: the index
    # name). Tags the query's trace so the per-tenant ledger
    # (sched/qos.py) can attribute the measured device cost, and rides
    # forwarded requests' headers so data-node spans carry it too.
    tenant: Optional[str] = None


class _NoDeviceHealth:
    """Ladder stub for the shadow executor: never route to the device."""

    @staticmethod
    def plan(sig):
        return "shard"


class _NoDeviceEngine:
    """Engine stub installed on the point-in-time shadow executor
    (_execute_at_position): refuses every fast-path gate, forcing the
    host per-shard map/reduce walk. Historical fragments are pathless
    one-shot materializations — pushing them through the device engine
    would enroll frozen snapshots in resident-stack/generation tracking
    keyed by (index, field, view, shard), colliding with the LIVE
    fragments of the same coordinates."""

    device_health = _NoDeviceHealth()

    @staticmethod
    def supports(call, index=None):
        return False

    @staticmethod
    def host_supports(call):
        return False


_NO_DEVICE_ENGINE = _NoDeviceEngine()


@dataclass
class ValCount:
    """Sum/Min/Max result (reference executor.go:1762-1808)."""

    val: int = 0
    count: int = 0

    def add(self, other: "ValCount") -> "ValCount":
        return ValCount(self.val + other.val, self.count + other.count)

    def smaller(self, other: "ValCount") -> "ValCount":
        if self.count == 0 or (other.val < self.val and other.count > 0):
            return other
        return ValCount(self.val, self.count)

    def larger(self, other: "ValCount") -> "ValCount":
        if self.count == 0 or (other.val > self.val and other.count > 0):
            return other
        return ValCount(self.val, self.count)

    def to_dict(self):
        return {"value": self.val, "count": self.count}


class Executor:
    def __init__(
        self,
        holder: Holder,
        cluster=None,
        client=None,
        translate_store=None,
        max_writes_per_request: int = MAX_WRITES_PER_REQUEST,
        workers: int = 8,
        engine_config=None,
        tier_config=None,
    ):
        from .cluster.node import Cluster

        self.holder = holder
        # Device-engine knobs (parallel.EngineConfig); held here because
        # the engine is constructed on first use (the server forces that
        # at open(), library users on their first query).
        self.engine_config = engine_config
        # [tier] residency budgets (tier.TierConfig) + the scheduler's
        # per-index traffic signal for the tier prefetcher; the server
        # wires traffic_fn before any query can build the engine.
        self.tier_config = tier_config
        self.tier_traffic_fn = None
        self.cluster = cluster or Cluster()
        self.client = client
        self.translate_store = translate_store
        self.max_writes_per_request = max_writes_per_request
        self._pool = ThreadPoolExecutor(max_workers=workers) if workers > 1 else None
        self._engine = None  # lazy ShardedQueryEngine
        # Cross-query micro-batcher (sched/batcher.py), wired by the
        # server's scheduler. When present, compatible local count
        # dispatches coalesce into one fused engine launch; None keeps the
        # direct single-query engine path (library/embedded use).
        self.batcher = None
        # Multi-host collective backend (parallel/collective.py), wired by
        # the server. When a jax.distributed job spans the cluster, full-
        # index fast-path queries run as ONE SPMD program over the global
        # mesh instead of the HTTP fan-out; failures fall back to fan-out.
        self.collective = None
        # Queries touching a quarantined fragment (corrupt file moved
        # aside at open, not yet repaired by anti-entropy) are served with
        # that fragment reading as EMPTY rather than erroring — this
        # counter surfaces how often results were degraded (/debug/vars).
        self.quarantined_reads = 0
        # Owners of whole shard lists, kept across queries (_shard_owners),
        # and how often they served: the `executor` group of /debug/vars.
        self._owners_kept: Dict[tuple, tuple] = {}
        self._owners_mu = threading.Lock()
        self.assign_hits = 0
        self.assign_walks = 0
        # Batched TopN runner calls answered on (rows, shards) arrays, and
        # shards a batched runner handed one by one to the per-shard rung
        # (_execute_topn_shards): plain bumps, no lock, the same group.
        self.topn_array_walks = 0
        self.topn_shard_replays = 0
        # Of the candidate phase (the phase-1 runner) alone, bumped together
        # once a call has its answer: calls that handed candidates to the
        # device, the programs they launched (one a chunk of _topn_chunk
        # rows) and the rows in them. The refetch of the winners is one
        # program more and is in none of the three.
        self.topn_queries = 0
        self.topn_chunks = 0
        self.topn_candidate_rows = 0
        # How long a write caught in a live-rebalance cutover window
        # (ShardMovedError locally, 409 from a frozen remote owner) keeps
        # re-routing while the commit broadcast lands, before surfacing a
        # clean retryable error. The server installs
        # [rebalance] cutover-pause-max here.
        self.cutover_wait = 2.0
        # Hinted handoff (cluster/hints.py), wired by the server: when a
        # replica forward is skipped (breaker open) or fails at the
        # transport, the write's captured op batch lands in the peer's
        # durable hint log instead of waiting for the next anti-entropy
        # sweep. None (library use) keeps the skip-and-sweep behavior.
        self.hints = None
        # [replication] section (write-consistency ack gating); None =
        # the reference's ack-on-first-apply behavior.
        self.replication_config = None
        # Geo replication (geo/manager.py), wired by the server when
        # [geo] role != "none": the read-path staleness gate and the
        # follower write fence. None (library/single-cluster use) makes
        # X-Pilosa-Max-Staleness a documented no-op.
        self.geo = None
        from .logger import NopLogger

        self.logger = NopLogger()  # server wires its logger in open()

    @property
    def engine(self):
        if self._engine is None:
            from .parallel.engine import ShardedQueryEngine

            self._engine = ShardedQueryEngine(
                self.holder, config=self.engine_config,
                tier_config=self.tier_config,
                traffic_fn=self.tier_traffic_fn,
                # The device-plane breakers share the [resilience] section
                # with the peer breakers they are modeled on; the cluster's
                # health registry already holds the resolved config, so the
                # lazily-built engine needs no extra plumbing.
                resilience_config=self.cluster.health.config)
            info = self._engine.device_info()
            self.logger.info(
                "device engine: platform=%s device_kind=%s n_devices=%d "
                "mesh=%s", info["platform"], info["device_kind"],
                info["n_devices"], info["mesh_shape"])
        return self._engine

    def close(self) -> None:
        """Release serving resources (thread pools, client sockets)."""
        if self._pool is not None:
            self._pool.shutdown(wait=False)
        if self._engine is not None:
            self._engine.close()
        # The internal client's per-thread keep-alive pools are registered
        # for exactly this moment: embedded/library users own client
        # lifetime through the executor (close() is idempotent, so the
        # server closing the same shared client again is harmless).
        if self.client is not None and hasattr(self.client, "close"):
            self.client.close()

    @property
    def health(self):
        """Per-peer breaker/budget/latency state (cluster/health.py)."""
        return self.cluster.health


    @property
    def node(self):
        return self.cluster.node

    # ------------------------------------------------------------- execute

    def execute(
        self,
        index: str,
        query,
        shards: Optional[Sequence[int]] = None,
        opt: Optional[ExecOptions] = None,
    ) -> List[Any]:
        if not index:
            raise PilosaError("index required")
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        if isinstance(query, str):
            with obs_span("parse"):
                query = pql_parser.parse(query)
        if self.max_writes_per_request > 0 and len(query.write_calls()) > self.max_writes_per_request:
            raise TooManyWritesError(
                f"too many writes: {len(query.write_calls())} > {self.max_writes_per_request}"
            )
        opt = opt or ExecOptions()
        if opt.remote and opt.entry_epoch is None:
            opt.entry_epoch = self.cluster.routing_epoch
        if opt.max_staleness is not None and self.geo is not None:
            # Bounded-staleness contract (docs/geo-replication.md):
            # refuse BEFORE translation/dispatch — a 409 with the current
            # lag, never a silently-stale answer. Leaders and non-geo
            # nodes pass unconditionally inside the gate.
            self.geo.check_staleness(opt.max_staleness)
        if self.geo is not None and not opt.remote and query.write_calls():
            # Geo write fence: a follower never accepts an external
            # write (409 pointing at the leader); a leader tallies the
            # accepting epoch. Only the external entry is gated —
            # remote=True forwards were fenced at their coordinator.
            self.geo.check_write()

        for call in query.calls:
            self._translate_call(index, idx, call)

        needs_shards = any(c.name not in _WRITE_CALLS for c in query.calls)
        if not shards and needs_shards:
            shards = list(range(idx.max_shard() + 1))
        shards = list(shards or [])

        if opt.remote and (opt.epoch or 0) < self.cluster.routing_epoch:
            # The sender routed under an older placement than ours. Serving
            # a shard we no longer own would read a migrated (possibly
            # GC'd) fragment as empty — a silent hole. 409 instead; the
            # sender re-routes once on refreshed placement. An UNSTAMPED
            # request counts as epoch 0: a sender that never saw the
            # rebalance (lost the begin broadcast, or predates it) is the
            # stalest possible router, not an exempt one.
            for shard in shards:
                if not self._serves_shard(index, shard):
                    from .errors import StaleRoutingEpochError

                    raise StaleRoutingEpochError(
                        f"shard {shard} of {index} no longer served here "
                        f"(request epoch {opt.epoch} < local "
                        f"{self.cluster.routing_epoch})"
                    )

        if opt.at_position is not None:
            return self._execute_at_position(index, idx, query, shards, opt)

        results = []
        for call in query.calls:
            results.append(self._execute_call(index, call, shards, opt))

        return [
            self._translate_result(index, idx, call, r)
            for call, r in zip(query.calls, results)
        ]

    def _execute_at_position(self, index: str, idx, query, shards, opt):
        """Point-in-time execution: the whole call tree runs against a
        SHADOW executor whose holder materializes every fragment at the
        requested CDC position (cdc/pit.py HistoricalHolder). The shadow
        is a shallow copy with the device/cluster fast paths stubbed out
        — materialized fragments live outside the engine's resident
        stacks and generation tracking, so counts must take the host
        map/reduce walk, and coalescing a frozen-past query with live
        ones would poison the batcher's epoch-keyed groups. Per-shard
        dispatch still uses the shared thread pool: every closure binds
        the shadow, so pool threads see the historical holder too."""
        import copy as _copy

        from .cdc.pit import HistoricalHolder

        cdc = getattr(self.holder, "cdc", None)
        if cdc is None:
            raise QueryError(
                "at-position reads require change capture (cdc.enabled)")
        if query.write_calls():
            raise QueryError("at-position queries must be read-only")
        if opt.remote or len(self.cluster.nodes) > 1:
            # Positions are per-index but assigned per-node: another
            # node's fragments carry DIFFERENT position stamps, so a
            # fanned-out at-position read would mix timelines.
            raise QueryError("at-position reads are node-local")
        # Fast 410 gate before any materialization work.
        cdc.check_position(index, opt.at_position)
        shadow = _copy.copy(self)
        shadow.holder = HistoricalHolder(
            self.holder, cdc, index, opt.at_position)
        shadow.collective = None
        shadow.batcher = None
        shadow.hints = None
        shadow._engine = _NO_DEVICE_ENGINE
        results = []
        for call in query.calls:
            results.append(shadow._execute_call(index, call, shards, opt))
        return [
            self._translate_result(index, idx, call, r)
            for call, r in zip(query.calls, results)
        ]

    def _execute_call(self, index: str, c: Call, shards: List[int], opt: ExecOptions):
        if c.name == "Sum":
            return self._execute_val_count(index, c, shards, opt, "sum")
        if c.name == "Min":
            return self._execute_val_count(index, c, shards, opt, "min")
        if c.name == "Max":
            return self._execute_val_count(index, c, shards, opt, "max")
        if c.name == "Count":
            return self._execute_count(index, c, shards, opt)
        if c.name == "Set":
            return self._execute_set_bit(index, c, opt)
        if c.name == "Clear":
            return self._execute_clear_bit(index, c, opt)
        if c.name == "SetValue":
            self._execute_set_value(index, c, opt)
            return None
        if c.name == "SetRowAttrs":
            self._execute_set_row_attrs(index, c, opt)
            return None
        if c.name == "SetColumnAttrs":
            self._execute_set_column_attrs(index, c, opt)
            return None
        if c.name == "TopN":
            return self._execute_topn(index, c, shards, opt)
        return self._execute_bitmap_call(index, c, shards, opt)

    # ---------------------------------------------------------- collective

    def _collective_ok(self, index: str, shards: List[int], opt: ExecOptions) -> bool:
        """True when the multi-host collective plane should serve this
        query: a jax.distributed job spans the cluster and the query covers
        the full shard range (the collective program always covers all
        shards; subsets go through the fan-out)."""
        c = self.collective
        if c is None or opt.remote or not shards:
            return False
        try:
            if not c.active():
                return False
        except Exception as e:
            # A probe failure routes the query to the HTTP fan-out; record
            # it like every other refusal so a climbing fallback counter
            # stays diagnosable.
            self._collective_fallback(e)
            return False
        idx = self.holder.index(index)
        if idx is None:
            return False
        return set(shards) == set(range(idx.max_shard() + 1))

    def _collective_fallback(self, e) -> None:
        """Record WHY the fast path refused, where the decision was made —
        a climbing CollectiveFallback counter is undiagnosable without it.
        The per-reason breakdown lands in the backend's `collective`
        counter group (/debug/vars) next to its serve counters."""
        self._count_stat("CollectiveFallback")
        if self.collective is not None:
            self.collective.note_fallback(getattr(e, "reason", "error"))
        self.logger.error("collective fallback: %s", e)

    # ----------------------------------------------------------- mapReduce

    def _shard_owners(self, index: str, shards: List[int]):
        """(owner ids of each shard in placement order, whether this node
        is among the owners of every one): Cluster.shard_list_owners, kept
        across queries.

        Who owns a shard is a pure function of the index, the shard and
        what Cluster.placement_witness() names, so the answer for a shard
        list is kept WITH the witness it was computed from and served while
        the live witness equals it. Nothing is invalidated and nothing can
        change placement behind it: `nodes` assigned or changed in place, a
        rewritten Node.id and a rebalance (no witness, nothing kept or
        served) all make the comparison fail. A hit takes no lock: one
        dict read and two comparisons, from every serving thread (PERF.md,
        PR 29: a lock here costs more than the walk). So assign_hits may
        lose a bump between two threads; assign_walks is exact."""
        key = (index, len(shards), shards[0], shards[-1])
        kept = self._owners_kept.get(key)
        if (kept is not None and kept[0] == self.cluster.placement_witness()
                and kept[1] == shards):
            self.assign_hits += 1
            return kept[2], kept[3]
        witness, owners = self.cluster.shard_list_owners(index, shards)
        me = self.node.id
        all_mine = all(me in ids for ids in owners)
        with self._owners_mu:
            self.assign_walks += 1
            if witness is not None:
                if len(self._owners_kept) >= _OWNERS_KEPT:
                    self._owners_kept.clear()
                self._owners_kept[key] = (witness, list(shards), owners,
                                          all_mine)
        return owners, all_mine

    def _assign_shards(self, index: str, shards: List[int], exclude=()):
        """Shards -> (local list, {node_id: shards}) using health info.

        Prefers self when a replica (maximizes local device work,
        executor.go:1444-1458); skips nodes in `exclude` and peers whose
        circuit breaker refuses traffic. The breaker gate is consulted
        lazily in placement order and memoized per assignment round, so a
        down peer whose backoff elapsed is admitted for its WHOLE shard
        batch — that one batched request is the half-open probe, and its
        outcome (recorded by the fan-out) decides re-close vs re-open.
        Only the placement is kept across calls (_shard_owners); the
        breaker and `exclude` are asked every time."""
        if not shards:
            return [], {}
        me = self.node.id
        owners, all_mine = self._shard_owners(index, shards)
        if all_mine and me not in exclude:
            return list(shards), {}
        health = self.cluster.health
        admitted: Dict[str, bool] = {}

        def ok(node_id: str) -> bool:
            if node_id not in admitted:
                admitted[node_id] = health.allow_request(node_id)
            return admitted[node_id]

        local: List[int] = []
        remote: Dict[str, List[int]] = {}
        for shard, ids in zip(shards, owners):
            if me in ids and me not in exclude:
                local.append(shard)
                continue
            for node_id in ids:  # placement order, like the reference
                if node_id not in exclude and ok(node_id):
                    remote.setdefault(node_id, []).append(shard)
                    break
            else:
                raise PilosaError(f"no available node owns shard {shard}")
        return local, remote

    def _map_reduce(self, index: str, shards: List[int], c: Call, opt: ExecOptions, map_fn, reduce_fn):
        """Group shards by owning node; local shards run concurrently on the
        device, remote nodes get one batched query. Failed nodes are marked
        and their shards re-mapped onto replicas (executor.go:1464-1555)."""

        deadline = opt.deadline

        def checked_map(shard):
            # Per-shard deadline gate: mid-map-reduce expiry aborts before
            # the NEXT shard's work rather than draining the whole list.
            if deadline is not None:
                deadline.check("shard map")
            return map_fn(shard)

        def local_runner(local_shards):
            if self._pool is not None and len(local_shards) > 1:
                values = list(self._pool.map(checked_map, local_shards))
            else:
                values = [checked_map(s) for s in local_shards]
            result = None
            for v in values:
                result = v if result is None else reduce_fn(result, v)
            return result

        return self._fan_out(index, shards, c, opt, local_runner, reduce_fn)

    def _count_stat(self, name: str) -> None:
        """stats.count guarded for library use (Holder(None) has no stats
        client); the ladder counters must not be the thing that breaks a
        degraded query."""
        if self.holder.stats is not None:
            self.holder.stats.count(name, 1)

    def _serves_shard(self, index: str, shard: int) -> bool:
        """True when this node serves (index, shard) under the CURRENT
        routing view — the one predicate behind every stale-placement
        gate (entry 409, receiver/local post-gather re-checks), kept in
        one place so the epoch gates cannot drift apart."""
        return any(n.id == self.node.id
                   for n in self.cluster.shard_nodes(index, shard))

    def _fan_out(self, index, shards, c, opt, local_runner, reduce_fn):
        # A remote (forwarded) execution runs EXACTLY the shards it was
        # handed — no ownership re-check (executor.go:1476-1480). The
        # coordinator chose them; re-deriving placement here would silently
        # drop shards whenever membership views differ mid-transition.
        if opt.remote:
            if not shards:
                return None
            # Same mid-gather hazard the local batch below guards: the
            # entry gate passed, but a cutover committing AFTER it can GC
            # a moved shard's fragment mid-read so it reads as silently
            # empty. Compare against the epoch execute() anchored BEFORE
            # the gate (a snapshot taken here could already be
            # post-cutover — translation and earlier calls of a
            # multi-call query sit inside the window); a moved shard
            # means the result may hold a hole, so 409 back to the
            # sender for its free re-route.
            epoch_at_entry = opt.entry_epoch
            if epoch_at_entry is None:
                epoch_at_entry = self.cluster.routing_epoch
            v = local_runner(list(shards))
            if self.cluster.routing_epoch != epoch_at_entry:
                moved = [s for s in shards
                         if not self._serves_shard(index, s)]
                if moved:
                    if self.holder.stats is not None:
                        self.holder.stats.count("RemoteEpochReread", 1)
                    from .errors import StaleRoutingEpochError

                    raise StaleRoutingEpochError(
                        f"shards {sorted(moved)} of {index} moved during "
                        f"forwarded execution (epoch {epoch_at_entry} -> "
                        f"{self.cluster.routing_epoch})"
                    )
            return v

        trace = obs_current()
        if trace is None:
            return self._fan_out_rounds(index, shards, c, opt, local_runner,
                                        reduce_fn)
        # One "reduce" span per fan-out (accumulated merge cost), not
        # one span per reduce_fn call — merges interleave with
        # gathers and per-merge spans would be noise.
        reduce_acc = [0.0]
        inner_reduce = reduce_fn

        def reduce_fn(a, b, _f=inner_reduce):
            t0 = _time.monotonic()
            r = _f(a, b)
            reduce_acc[0] += _time.monotonic() - t0
            return r

        # An open span, so that every dispatch of the fan-out is its
        # child and its self time is the fan-out's own bookkeeping.
        with obs_span("executor.fanout", shards=len(shards)):
            result = self._fan_out_rounds(index, shards, c, opt,
                                          local_runner, reduce_fn)
            trace.record("reduce", reduce_acc[0] * 1000.0)
        return result

    def _fan_out_rounds(self, index, shards, c, opt, local_runner, reduce_fn):
        """The fan-out proper: assign shards to owners, run the local
        batch, forward the rest, re-route what failed or moved, until
        nothing is pending. Returns the reduced result."""
        from .server.client import ClientError

        result = None
        failed: set = set()
        app_error = None
        pending = list(shards)
        while pending:
            # Epoch BEFORE the placement read: the dispatch stamp and the
            # local re-check below must reflect the routing decision, not
            # the epoch at send time. Stamping the CURRENT epoch would let
            # a cutover that lands between assign and dispatch defeat the
            # receiver's stale-epoch gate (sender epoch caught up, stale
            # placement rides along) — the receiver would serve a shard
            # whose fragment it already GC'd as silently empty. An epoch
            # that advances right after this read only causes a spurious
            # 409 + free re-route, the safe direction.
            epoch_at_assign = self.cluster.routing_epoch
            try:
                local, remote = self._assign_shards(index, pending, exclude=failed)
            except PilosaError:
                if app_error is not None:
                    # Owners exhausted chasing a deterministic 4xx (e.g.
                    # schema lag on every replica): the application error is
                    # the real story, not "no available node".
                    raise app_error
                raise
            pending = []
            if local:
                if opt.deadline is not None:
                    opt.deadline.check("local dispatch")
                v = local_runner(local)
                moved = [] if self.cluster.routing_epoch == epoch_at_assign else [
                    s for s in local if not self._serves_shard(index, s)
                ]
                if moved:
                    # A live-rebalance cutover committed since this batch
                    # was assigned: post-commit GC may have removed a
                    # moved shard's fragment mid-read, so it read as
                    # EMPTY — a silent hole, not an error. Discard this
                    # batch and re-run it on refreshed placement (the
                    # moved shards dispatch to their new owner next
                    # round).
                    if self.holder.stats is not None:
                        self.holder.stats.count("LocalEpochReread", 1)
                    pending.extend(local)
                elif v is not None:
                    result = v if result is None else reduce_fn(result, v)
            for node_id, node_shards in remote.items():
                if opt.remote:
                    continue  # remote calls are restricted to local shards
                node = self.cluster.node_by_id(node_id)
                kw = {}
                if epoch_at_assign:
                    # Stamp the epoch the placement decision was made
                    # under (only once a rebalance has ever advanced it —
                    # duck-typed test clients without the parameter keep
                    # working untouched). See the capture above: the
                    # current epoch could have caught up with the
                    # receiver's after a mid-flight cutover, masking the
                    # stale placement from its 409 gate.
                    kw["epoch"] = epoch_at_assign
                if opt.deadline is not None:
                    # Abort before the hop, and forward only the REMAINING
                    # budget so the peer never works past our cutoff. The
                    # kwarg rides only when a deadline exists, so duck-typed
                    # test clients without the parameter keep working.
                    opt.deadline.check("remote fan-out")
                    kw["deadline"] = opt.deadline.remaining()
                if opt.tenant is not None:
                    # Tenant identity rides the hop (trace attribution on
                    # the peer); kwarg only when set so duck-typed test
                    # clients without the parameter keep working.
                    kw["tenant"] = opt.tenant
                try:
                    v = self._remote_dispatch(node, index, c, node_shards, kw)
                except ClientError as e:
                    if opt.deadline is not None and opt.deadline.expired():
                        # The peer failed while OUR budget ran out (its
                        # forwarded budget is a slice of ours, so a peer
                        # expiry implies ours): abort cleanly as a deadline
                        # miss instead of spending the corpse of the budget
                        # chasing replicas or re-marking healthy nodes.
                        opt.deadline.check("remote fan-out")
                    if not _is_node_failure(e):
                        # 4xx: the peer executed and rejected the query.
                        # The node is TRANSPORT-healthy, so this counts as
                        # breaker success (a half-open probe answered with
                        # an app error must re-close, not wedge HALF_OPEN
                        # until probe_ttl) — but the error may be transient
                        # schema lag, so try the shards on a replica first
                        # and only surface it once owners are exhausted.
                        self.health.record_success(node_id)
                        app_error = app_error or e
                        failed.add(node_id)
                        if getattr(e, "status", 0) == 409:
                            # Routing conflict (live-rebalance cutover):
                            # ONE free re-route on refreshed placement —
                            # this is a placement change, not survivor
                            # load amplification, so it must not drain
                            # the retry budget into a retry storm.
                            if self.holder.stats is not None:
                                self.holder.stats.count(
                                    "StaleEpochReroute", 1)
                            pending.extend(node_shards)
                            continue
                        if not self.health.try_spend_retry():
                            # Budget drained: surface the rejection now
                            # instead of adding replica load.
                            raise app_error
                        pending.extend(node_shards)
                        continue
                    # The breaker already advanced inside _remote_dispatch
                    # (opens after breaker_failures consecutive transport
                    # failures; default 1 matches executor.go:1498-1508
                    # mark-dead-on-first-failure). Re-map the shards onto
                    # replicas — but only within the retry budget, so a
                    # brown-out cannot amplify load onto the survivors.
                    failed.add(node_id)
                    if not self.health.try_spend_retry():
                        raise PilosaError(
                            f"retry budget exhausted re-mapping shards of "
                            f"{node_id}: {e}"
                        )
                    pending.extend(node_shards)
                    continue
                result = v if result is None else reduce_fn(result, v)
        return result

    def _remote_dispatch(self, node, index: str, c: Call, node_shards, kw):
        """One batched query to a peer, with per-peer latency accounting
        and (when a worker pool exists) a hedged backup request: if the
        primary hasn't answered within the peer's hedge delay (rolling
        p99 or the configured fixed delay), the same shard batch is fired
        at a replica that also owns every shard in it, and the first good
        response wins. Hedge volume is capped by the health registry."""
        health = self.health
        # Captured HERE (the request thread): hedge legs run on pool
        # threads where the obs contextvar is not set, so the trace
        # object travels by closure and each leg records its own
        # remote:<peer> span (two legs = two spans, honestly), under the
        # span that is open here.
        trace = obs_current()
        above = obs_current_span()

        def call(target):
            """One request with health accounting — success AND transport
            failure are recorded HERE, whatever thread runs it, so a
            losing hedge leg (or an abandoned primary) still drives its
            peer's breaker even when its exception is never re-raised."""
            t0 = _time.monotonic()
            sp = (trace.span(f"remote:{target.id}", parent=above,
                             shards=len(node_shards))
                  if trace is not None else NOP_SPAN)
            call_kw = kw if trace is None else {**kw, "trace": sp}
            with sp:
                try:
                    res = self.client.query_node(
                        target, index, str(c), shards=node_shards,
                        remote=True, **call_kw,
                    )[0]
                except ClientError as e:
                    if _is_node_failure(e):
                        health.record_failure(target.id)
                    raise
            health.record_success(target.id, _time.monotonic() - t0)
            return res

        from .server.client import ClientError

        if self._pool is None or not health.hedge_enabled():
            return call(node)
        hedge_node = self._hedge_replica(index, node, node_shards)
        if hedge_node is None:
            return call(node)
        from concurrent.futures import (
            FIRST_COMPLETED, TimeoutError as FuturesTimeout, wait,
        )

        primary = self._pool.submit(call, node)
        try:
            # A fast primary failure raises here and takes the normal
            # replica-retry classification path.
            return primary.result(timeout=health.hedge_delay(node.id))
        except FuturesTimeout:
            pass
        if not health.allow_hedge():
            return primary.result()
        hedge = self._pool.submit(call, hedge_node)
        futures = {primary, hedge}
        errors = {}
        while futures:
            done, futures = wait(futures, return_when=FIRST_COMPLETED)
            for fut in done:
                err = fut.exception()
                if err is None:
                    if fut is hedge:
                        health.note_hedge_won()
                    return fut.result()
                errors[fut] = err
        # Both legs failed: surface the PRIMARY's error so the caller's
        # retry classification re-maps the shards it actually assigned
        # (the hedge leg's failure was already recorded by call()).
        raise errors.get(primary) or errors[hedge]

    def _hedge_replica(self, index: str, primary, node_shards):
        """A routable peer (breaker closed, not self, not the primary)
        that owns EVERY shard in the batch, or None. Shard batches group
        by owner, so replicas usually align; when they don't, hedging is
        skipped rather than splitting the batch."""
        health = self.health
        common = None
        for shard in node_shards:
            ids = {n.id for n in self.cluster.shard_nodes(index, shard)}
            common = ids if common is None else common & ids
            if not common or common == {primary.id}:
                return None
        for nid in sorted(common):
            if nid in (primary.id, self.node.id) or health.is_down(nid):
                continue
            n = self.cluster.node_by_id(nid)
            if n is not None:
                return n
        return None

    # ------------------------------------------------------------- bitmaps

    def _execute_bitmap_call(self, index: str, c: Call, shards: List[int], opt: ExecOptions) -> Row:
        def map_fn(shard):
            return self._execute_bitmap_call_shard(index, c, shard)

        def reduce_fn(prev, v):
            prev.merge(v)
            return prev

        row = self._batched_or_map_reduce(
            index, c, shards, opt, "bitmap", map_fn, reduce_fn
        )
        if row is None:
            row = Row()

        if c.name == "Row" and not opt.exclude_row_attrs:
            idx = self.holder.index(index)
            if idx is not None:
                field_name = c.field_arg()
                fld = idx.field(field_name)
                if fld is not None:
                    row_id, ok = c.uint_arg(field_name)
                    if ok:
                        row.attrs = fld.row_attr_store.attrs(row_id)
        if opt.exclude_columns:
            row.segments = {}
        return row

    def _execute_bitmap_call_shard(self, index: str, c: Call, shard: int) -> Row:
        if c.name == "Row":
            return self._execute_row_shard(index, c, shard)
        if c.name == "Difference":
            return self._execute_nary_shard(index, c, shard, "difference")
        if c.name == "Intersect":
            return self._execute_nary_shard(index, c, shard, "intersect")
        if c.name == "Union":
            return self._execute_nary_shard(index, c, shard, "union")
        if c.name == "Xor":
            return self._execute_nary_shard(index, c, shard, "xor")
        if c.name == "Range":
            return self._execute_range_shard(index, c, shard)
        raise QueryError(f"unknown call: {c.name}")

    def _fragment(self, index: str, field: str, view: str, shard: int):
        """Read-path fragment lookup. A quarantined fragment (corrupt file
        moved aside at open, repair pending) is returned as-is — its
        storage is empty, so reads degrade to empty instead of erroring —
        but the touch is counted so operators can see degraded results."""
        frag = self.holder.fragment(index, field, view, shard)
        if frag is not None and frag.quarantined:
            self.quarantined_reads += 1
        return frag

    def _execute_row_shard(self, index: str, c: Call, shard: int) -> Row:
        field_name = c.field_arg()
        fld = self.holder.field(index, field_name)
        if fld is None:
            raise FieldNotFoundError(field_name)
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise QueryError("Row() must specify row")
        frag = self._fragment(index, field_name, VIEW_STANDARD, shard)
        if frag is None:
            return Row()
        return frag.row(row_id)

    def _execute_nary_shard(self, index: str, c: Call, shard: int, op: str) -> Row:
        if not c.children and op in ("difference", "intersect"):
            raise QueryError(f"empty {c.name} query is currently not supported")
        rows = [self._execute_bitmap_call_shard(index, ch, shard) for ch in c.children]
        if not rows:
            return Row()
        out = rows[0]
        for r in rows[1:]:
            out = getattr(out, op)(r)
        return out

    def _execute_range_shard(self, index: str, c: Call, shard: int) -> Row:
        if c.has_condition_arg():
            return self._execute_bsi_range_shard(index, c, shard)

        field_name = c.field_arg()
        fld = self.holder.field(index, field_name)
        if fld is None:
            raise FieldNotFoundError(field_name)
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise QueryError("Range() must specify row")
        start = c.args.get("_start")
        end = c.args.get("_end")
        if not isinstance(start, str) or not isinstance(end, str):
            raise QueryError("Range() start/end time required")
        start_t, end_t = parse_timestamp(start), parse_timestamp(end)
        q = fld.time_quantum()
        if not q:
            return Row()
        row = Row()
        for view_name in views_by_time_range(VIEW_STANDARD, start_t, end_t, q):
            frag = self._fragment(index, field_name, view_name, shard)
            if frag is not None:
                row.merge(frag.row(row_id))
        return row

    def _execute_bsi_range_shard(self, index: str, c: Call, shard: int) -> Row:
        if len(c.args) == 0:
            raise QueryError("Range(): condition required")
        if len(c.args) > 1:
            raise QueryError("Range(): too many arguments")
        (field_name, cond), = c.args.items()
        if not isinstance(cond, Condition):
            raise QueryError(f"Range(): expected condition argument, got {cond!r}")
        fld = self.holder.field(index, field_name)
        if fld is None:
            raise FieldNotFoundError(field_name)
        bsig = fld.bsi_group(field_name)
        if bsig is None:
            raise BSIGroupNotFoundError(field_name)
        frag = self._fragment(index, field_name, VIEW_BSI_GROUP_PREFIX + field_name, shard)

        if cond.op == NEQ and cond.value is None:  # != null
            return frag.not_null(bsig.bit_depth()) if frag else Row()

        if cond.op == BETWEEN:
            predicates = cond.int_slice_value()
            if len(predicates) != 2:
                raise QueryError("Range(): BETWEEN condition requires exactly two integer values")
            lo, hi, out_of_range = bsig.base_value_between(*predicates)
            if out_of_range or frag is None:
                return Row()
            if predicates[0] <= bsig.min and predicates[1] >= bsig.max:
                return frag.not_null(bsig.bit_depth())
            return frag.range_between(bsig.bit_depth(), lo, hi)

        if not isinstance(cond.value, int) or isinstance(cond.value, bool):
            raise QueryError("Range(): conditions only support integer values")
        value = cond.value
        base, out_of_range = bsig.base_value(cond.op, value)
        if out_of_range and cond.op != NEQ:
            return Row()
        if frag is None:
            return Row()
        # Full-range LT/GT collapse to not-null (executor.go:938-948).
        if (
            (cond.op == LT and value > bsig.max)
            or (cond.op == LTE and value >= bsig.max)
            or (cond.op == GT and value < bsig.min)
            or (cond.op == GTE and value <= bsig.min)
        ):
            return frag.not_null(bsig.bit_depth())
        if out_of_range and cond.op == NEQ:
            return frag.not_null(bsig.bit_depth())
        return frag.range_op(cond.op, bsig.bit_depth(), base)

    # --------------------------------------------------------------- count

    def _execute_count(self, index: str, c: Call, shards: List[int], opt: ExecOptions) -> int:
        if len(c.children) == 0:
            raise QueryError("Count() requires an input bitmap")
        if len(c.children) > 1:
            raise QueryError("Count() only accepts a single bitmap input")
        child = c.children[0]

        if self._collective_ok(index, shards, opt):
            supported = self.engine.supports(child, index)
            if supported:
                from .parallel.collective import CollectiveUnavailable

                try:
                    if self.batcher is not None and supported is not True:
                        # Batched collective launch: concurrent queries of
                        # one canonical signature coalesce into ONE
                        # barrier + ONE seq slot + ONE SPMD entry
                        # (sched/batcher.py collective_count). The group
                        # key is the SAME canonical sig the descriptor
                        # carries — one helper, so they cannot drift.
                        comp, _ = supported
                        sig = self.collective._sig_tuple(comp)
                        result = self.batcher.collective_count(
                            self.collective, index, child, sig,
                            deadline=opt.deadline)
                    else:
                        result = int(self.collective.count(index, child))
                    self._count_stat("CollectiveCount")
                    return result
                except CollectiveUnavailable as e:
                    self._collective_fallback(e)

        def map_fn(shard):
            return self._execute_bitmap_call_shard(index, child, shard).count()

        result = self._batched_or_map_reduce(
            index, c, shards, opt, "count", map_fn, lambda a, b: a + b, child=child
        )
        return int(result or 0)

    def _batched_or_map_reduce(self, index, c, shards, opt, kind, map_fn, reduce_fn, child=None):
        """Run locally-owned shards as ONE sharded device program when the
        call tree compiles onto the fast path; remote/unsupported shards use
        the reference-style per-shard map/reduce.

        The device-fault ladder (docs/fault-tolerance.md) sits here: the
        engine's breaker state routes a quarantined SIGNATURE to the
        per-shard XLA walk and an open PLANE to host execution before any
        device work is attempted, and a dispatch that fails mid-request
        falls one rung down for exactly that batch instead of surfacing a
        500 — the breakers make the routing sticky for the next query."""
        target = child if child is not None else c
        supported = self.engine.supports(target, index) if shards else False
        if not supported:
            return self._map_reduce(index, shards, c, opt, map_fn, reduce_fn)
        # supports(call, index) returns the compiled (comp, expr) pair,
        # so the gate and the execution share one AST walk on the
        # hottest serving path (True means a patched/syntactic gate:
        # let the engine compile internally).
        compiled = None if supported is True else supported
        health_sig = compiled[0].plan.sig_tuple if compiled else None
        route = self.engine.device_health.plan(health_sig)
        if route == "shard":
            # Per-signature quarantine: THIS structure keeps failing on
            # the fused path; everything else stays on the device. The
            # half-open probe re-admits it via plan() after backoff.
            self._count_stat("DeviceSigQuarantined")
            inner_map = map_fn

            def map_fn(shard):
                # The trace must show WHICH rung served a degraded query.
                with obs_span("device.dispatch", rung="shard", shard=shard):
                    return inner_map(shard)

            return self._map_reduce(index, shards, c, opt, map_fn, reduce_fn)
        if route == "host":
            # Plane breaker open: the device is sick — no dispatches at
            # all. Counts answer compressed-domain from the host ladder;
            # trees the host evaluator can't express (BSI) take the
            # per-shard walk.
            self._count_stat("DeviceHostRouted")
            if kind == "count" and self.engine.host_supports(target):

                def host_runner(local_shards):
                    if opt.deadline is not None:
                        opt.deadline.check("host execution")
                    with obs_span("device.dispatch", rung="host",
                                  shards=len(local_shards)):
                        return self.engine.host_count(
                            index, target, local_shards, comp_expr=compiled)

                return self._fan_out(
                    index, shards, c, opt, host_runner, reduce_fn)
            return self._map_reduce(index, shards, c, opt, map_fn, reduce_fn)

        def fallback(local_shards):
            # One rung down for THIS batch: the breaker state decides
            # where the NEXT query routes; this query still answers.
            if kind == "count" and self.engine.host_supports(target):
                with obs_span("device.dispatch", rung="host",
                              shards=len(local_shards)):
                    return self.engine.host_count(
                        index, target, local_shards, comp_expr=compiled)
            result = None
            with obs_span("device.dispatch", rung="shard",
                          shards=len(local_shards)):
                for s in local_shards:
                    v = map_fn(s)
                    result = v if result is None else reduce_fn(result, v)
            return result

        def local_runner(local_shards):
            if opt.deadline is not None:
                # "Aborts before the next device dispatch": the gate
                # sits exactly at the engine-launch boundary.
                opt.deadline.check("device dispatch")
            try:
                with obs_span("device.dispatch", rung="device",
                              shards=len(local_shards)) as sp:
                    if sp is not NOP_SPAN and health_sig is not None:
                        sp.tag(sig=str(health_sig))
                    if kind == "count":
                        if self.batcher is not None:
                            return self.batcher.count(
                                index, target, local_shards,
                                comp_expr=compiled, deadline=opt.deadline)
                        return self.engine.count(
                            index, target, local_shards, comp_expr=compiled)
                    if self.batcher is not None:
                        # Generalized micro-batching: bitmap dispatches
                        # coalesce with same-canonical-signature peers
                        # into one fused bitmap_batch launch, exactly
                        # like Counts (docs/query-compiler.md).
                        return self.batcher.bitmap(
                            index, target, local_shards,
                            comp_expr=compiled, deadline=opt.deadline)
                    return self.engine.bitmap(
                        index, target, local_shards, comp_expr=compiled)
            except DeviceDispatchError as e:
                self._count_stat("DeviceLadderFallback")
                self.logger.error(
                    "device dispatch failed (%s), serving %s from the "
                    "fallback rung: %s", e.kind, kind, e)
                return fallback(local_shards)

        return self._fan_out(index, shards, c, opt, local_runner, reduce_fn)

    # --------------------------------------------------------- sum/min/max

    def _execute_val_count(self, index: str, c: Call, shards: List[int], opt: ExecOptions, kind: str) -> ValCount:
        if not c.args.get("field"):
            raise QueryError(f"{c.name}(): field required")
        if len(c.children) > 1:
            raise QueryError(f"{c.name}() only accepts a single bitmap input")

        def map_fn(shard):
            return self._execute_val_count_shard(index, c, shard, kind)

        def reduce_fn(prev, v):
            if kind == "sum":
                return prev.add(v)
            if kind == "min":
                return prev.smaller(v)
            return prev.larger(v)

        field_name = c.args.get("field")
        fld = self.holder.field(index, field_name)
        bsig = fld.bsi_group(field_name) if fld else None
        filter_call = c.children[0] if c.children else None

        if (
            bsig is not None
            and (filter_call is None or self.engine.supports(filter_call, index))
            and self._collective_ok(index, shards, opt)
        ):
            from .parallel.collective import CollectiveUnavailable

            try:
                result = self._collective_val_count(
                    index, field_name, bsig, kind, filter_call
                )
                self._count_stat("CollectiveValCount")
                return result
            except CollectiveUnavailable as e:
                self._collective_fallback(e)

        local_runner = None
        if bsig is not None and (
            filter_call is None or self.engine.supports(filter_call, index)
        ) and self.engine.device_health.plan(None) == "device":
            # Batched path: one device program per node covering all its
            # shards (replaces the per-shard ValCount merge loop). An
            # open plane breaker short-circuits to the per-shard walk
            # BEFORE any dispatch — BSI's bit-sliced kernels have no host
            # twin, so rung 1 is its whole degraded ladder, and paying a
            # failing dispatch (or a watchdog stall) per query on a known-
            # sick device would defeat the breaker.
            depth = bsig.bit_depth()

            def local_runner(local_shards):
                try:
                    with obs_span("device.dispatch", rung="device",
                                  shards=len(local_shards)):
                        out = self.engine.bsi_val_count(
                            index, field_name, kind, depth, local_shards,
                            filter_call
                        )
                except DeviceDispatchError as e:
                    # Ladder rung for BSI: the bit-sliced scan is device
                    # code with no host twin, so the fallback is the
                    # reference per-shard merge for this batch (the
                    # breaker reroutes subsequent queries).
                    self._count_stat("DeviceLadderFallback")
                    self.logger.error(
                        "device BSI dispatch failed (%s), per-shard "
                        "fallback: %s", e.kind, e)
                    result = None
                    with obs_span("device.dispatch", rung="shard",
                                  shards=len(local_shards)):
                        for s in local_shards:
                            v = map_fn(s)
                            result = (v if result is None
                                      else reduce_fn(result, v))
                    return result
                return self._compose_bsi_result(bsig, kind, out)

        if local_runner is not None:
            result = self._fan_out(index, shards, c, opt, local_runner, reduce_fn) or ValCount()
        else:
            result = self._map_reduce(index, shards, c, opt, map_fn, reduce_fn) or ValCount()
        if result.count == 0:
            return ValCount()
        return result

    def _collective_val_count(self, index: str, field_name: str, bsig, kind: str,
                              filter_call) -> ValCount:
        """BSI Sum/Min/Max as ONE SPMD program over the global mesh — the
        cluster-wide replacement for the per-node ValCount merge loop."""
        out = self.collective.bsi_val_count(
            index, field_name, kind, bsig.bit_depth(), filter_call
        )
        return self._compose_bsi_result(bsig, kind, out)

    @staticmethod
    def _compose_bsi_result(bsig, kind: str, out) -> ValCount:
        """ValCount from a bsi_val_count result — ONE implementation of the
        offset/weight math shared by the local-engine and collective
        providers so the two paths cannot silently diverge."""
        depth = bsig.bit_depth()
        if kind == "sum":
            counts = out
            vcount = int(counts[depth])
            if vcount == 0:
                return ValCount()
            vsum = sum((1 << i) * int(counts[i]) for i in range(depth))
            return ValCount(vsum + vcount * bsig.min, vcount)
        bits, count = out
        if count == 0:
            return ValCount()
        from .ops.bitplane import compose_bits

        return ValCount(compose_bits(bits) + bsig.min, count)

    def _execute_val_count_shard(self, index: str, c: Call, shard: int, kind: str) -> ValCount:
        filter_row = None
        if len(c.children) == 1:
            filter_row = self._execute_bitmap_call_shard(index, c.children[0], shard)
        field_name = c.args.get("field")
        fld = self.holder.field(index, field_name)
        if fld is None:
            return ValCount()
        bsig = fld.bsi_group(field_name)
        if bsig is None:
            return ValCount()
        frag = self._fragment(index, field_name, VIEW_BSI_GROUP_PREFIX + field_name, shard)
        if frag is None:
            return ValCount()
        if kind == "sum":
            vsum, vcount = frag.sum(filter_row, bsig.bit_depth())
            return ValCount(val=vsum + vcount * bsig.min, count=vcount)
        if kind == "min":
            v, cnt = frag.min(filter_row, bsig.bit_depth())
        else:
            v, cnt = frag.max(filter_row, bsig.bit_depth())
        return ValCount(val=v + bsig.min if cnt else 0, count=cnt)

    # ----------------------------------------------------------------- TopN

    def _check_chunk_deadline(self, deadline, where: str) -> None:
        """Deadline re-check BETWEEN device-dispatch chunks and after
        gathers: the scheduler gates the budget before a dispatch, but a
        multi-chunk TopN would otherwise finish dead work after the
        budget expires mid-flight. The counter separates 'expired between
        chunks' (work was abandoned early, the good case) from the
        admission-time expiries the scheduler already counts."""
        if deadline is None:
            return
        if deadline.expired():
            self._count_stat("DeadlineMidQuery")
        deadline.check(where)

    def _topn_counts_laddered(self, index, field, ids, local_shards,
                              src_call, need_rc):
        """engine.topn_shard_counts under the device-fault ladder: an
        open plane breaker (or a dispatch failure mid-request) answers
        the same contract from host planes + numpy popcounts instead of
        erroring (docs/fault-tolerance.md). When the src tree has no
        host twin (BSI Range), a DeviceDispatchError propagates — the
        batched local_runners catch it and take the per-shard rung."""
        eng = self.engine
        host_ok = src_call is None or eng.host_supports(src_call)
        if eng.device_health.plan(None) == "device":
            try:
                with obs_span("device.dispatch", rung="device",
                              shards=len(local_shards)):
                    return eng.topn_shard_counts(
                        index, field, ids, local_shards, src_call,
                        need_row_counts=need_rc)
            except DeviceDispatchError as e:
                if not host_ok:
                    raise
                self._count_stat("DeviceLadderFallback")
                self.logger.error(
                    "device TopN dispatch failed (%s), host fallback: %s",
                    e.kind, e)
        elif not host_ok:
            raise DeviceDispatchError(
                "runtime", None,
                "device plane degraded and TopN src is not host-executable")
        else:
            self._count_stat("DeviceHostRouted")
        with obs_span("device.dispatch", rung="host",
                      shards=len(local_shards)):
            return eng.host_topn_shard_counts(
                index, field, ids, local_shards, src_call,
                need_row_counts=need_rc)

    def _execute_topn(self, index: str, c: Call, shards: List[int], opt: ExecOptions) -> List[Pair]:
        ids_arg = self._uint_slice_arg(c, "ids")
        n, _ = c.uint_arg("n")

        pairs = self._execute_topn_shards(index, c, shards, opt)
        if not pairs or ids_arg or opt.remote:
            return pairs

        # Phase 2: refetch full counts for the merged candidate ids
        # (executor.go:524-560). Re-check the budget first: phase 1's
        # gathers may have consumed it, and phase 2 is a full second
        # fan-out of dead work if so.
        self._check_chunk_deadline(opt.deadline, "between TopN phases")
        other = Call(c.name, dict(c.args), list(c.children))
        other.args["ids"] = sorted({p.id for p in pairs})
        trimmed = self._execute_topn_shards(index, other, shards, opt)
        if n and len(trimmed) > n:
            trimmed = trimmed[:n]
        return trimmed

    def _execute_topn_shards(self, index: str, c: Call, shards: List[int], opt: ExecOptions) -> List[Pair]:
        def map_fn(shard):
            return self._execute_topn_shard(index, c, shard)

        local_runner = None
        ids = self._uint_slice_arg(c, "ids")
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if tanimoto > 100:
            raise QueryError("Tanimoto Threshold is from 1 to 100 only")
        src_call = c.children[0] if c.children else None
        field_name = c.args.get("_field") or DEFAULT_FIELD
        thr = max(c.uint_arg("threshold")[0], DEFAULT_MIN_THRESHOLD)
        attr_name = c.args.get("attrName", "")
        attr_values = set(c.args.get("attrValues") or [])

        def attr_rows(rows: List[int]) -> List[int]:
            """The rows the attr filter lets through: asked once per row,
            not once per row per shard (a field has one row attr store)."""
            if not (attr_name and attr_values):
                return rows
            fld = self.holder.field(index, field_name)
            store = fld.row_attr_store if fld else None
            return [r for r in rows if Fragment.row_attrs_match(
                store, r, attr_name, attr_values)]

        if (
            ids
            and not attr_name
            and not tanimoto
            and thr <= 1
            and (src_call is None or self.engine.supports(src_call, index))
            and self._collective_ok(index, shards, opt)
        ):
            # Collective phase-2: global candidate counts in one SPMD
            # program per chunk instead of an HTTP fan-out per node.
            # Restricted to threshold<=1 (per-shard MinThreshold semantics
            # need per-shard counts, fragment.go:899-990).
            from .parallel.collective import CollectiveUnavailable

            try:
                pairs: List[Pair] = []
                CHUNK = _topn_chunk(len(shards))  # bounds the (R, S, W) global stack
                for i in range(0, len(ids), CHUNK):
                    if i:
                        self._check_chunk_deadline(
                            opt.deadline, "between collective TopN chunks")
                    chunk = ids[i : i + CHUNK]
                    counts = self.collective.topn_counts(
                        index, field_name, chunk, src_call
                    )
                    pairs.extend(
                        Pair(id=r, count=int(cnt))
                        for r, cnt in zip(chunk, counts)
                        if cnt > 0
                    )
                self._count_stat("CollectiveTopN")
                return sort_pairs(pairs)
            except CollectiveUnavailable as e:
                self._collective_fallback(e)
        if (
            ids
            and src_call is not None  # without src the host rank cache has
            and self.engine.supports(src_call, index)  # exact counts; device adds RTT
        ):
            # Batched phase-2: all candidate counts across all local shards
            # in one device program, preserving per-shard MinThreshold,
            # tanimoto (fragment.go:899-990, 1008-1027 — the coefficient is
            # a pure function of the (row, inter, src) counts the program
            # already produces), and attr-filter semantics (a host-side
            # per-row check against the field's row attr store,
            # fragment.go:922-934 — filtered rows never join the program).

            def local_runner(local_shards):
                run_ids = attr_rows(ids)
                if not run_ids:
                    return []
                # Row (cache) counts only gate tanimoto and thresholds > 1:
                # at thr<=1 the count>0 check below subsumes them, so the
                # common phase-2 skips the candidate-plane popcount pass
                # entirely (engine.topn_shard_counts need_row_counts).
                need_rc = bool(tanimoto) or thr > 1
                row_counts, inter, src_counts = self._topn_counts_laddered(
                    index, field_name, run_ids, local_shards, src_call,
                    need_rc,
                )
                # inter is never None here: this branch requires a
                # supported src_call. One (rows, shards) mask, one sum.
                with obs_span("topn.replay", rows=len(run_ids),
                              shards=len(local_shards)):
                    count = np.asarray(inter, np.int64)
                    cnt = np.asarray(row_counts, np.int64) if need_rc else count
                    keep = (cnt > 0) & (count > 0)
                    if tanimoto:
                        keep &= _tanimoto_passes(
                            count, cnt,
                            np.asarray(src_counts, np.int64)[None, :], tanimoto)
                    else:
                        keep &= (cnt >= thr) & (count >= thr)
                    totals = np.where(keep, count, 0).sum(axis=1).tolist()
                    # add_pairs: an id named twice counts twice, as it does
                    # on the per-shard rung.
                    return add_pairs([], [
                        Pair(id=r, count=t)
                        for r, t in zip(run_ids, totals) if t
                    ])

        elif (
            src_call is not None
            and not ids
            and self.engine.supports(src_call, index)
        ):
            # Batched phase-1: each shard's candidate list comes from its
            # host rank cache (cheap), but the src intersections for the
            # UNION of candidates across all local shards run as ONE device
            # program — the per-fragment fallback pays a device round trip
            # per plane chunk per shard.
            # Heap semantics stay exact: _replay_topn runs Fragment.top's
            # selection (fragment.go:899-990) over the shard axis. Tanimoto
            # (the ChEMBL workload, docs/examples.md:321-328) and attr
            # filters ride this path too: the coefficient needs only the
            # per-shard src popcount the same program produces, and attr
            # filtering is a host-side candidate check, once per row.
            # The host half visits no (row, shard) cell in a statement of
            # its own: 74 ms a TopN at 256 shards when it did (PERF.md,
            # PR 34). Its stages are spans of their own (topn.rank, one
            # topn.chunk a device program, topn.replay), so that the
            # fan-out's self time holds none of it.
            n_arg, _ = c.uint_arg("n")

            def local_runner(local_shards):
                with obs_span("topn.rank", shards=len(local_shards)) as sp:
                    rebuilt = thread_rank_rebuilds()
                    shard_list, rankings = [], []
                    for s in local_shards:
                        frag = self._fragment(
                            index, field_name, VIEW_STANDARD, s)
                        if frag is not None:
                            shard_list.append(s)
                            rankings.append(frag.top_arrays())
                    rank_ids, rank_cnt = _rank_matrix(rankings)
                    # Candidate rules of Fragment._filter_candidates as
                    # masks (tanimoto's bounds wait for src's counts:
                    # _replay_topn).
                    cand = rank_cnt > 0 if tanimoto else rank_cnt >= thr
                    union = np.unique(rank_ids[cand])
                    if attr_name and attr_values:
                        union = np.asarray(attr_rows(union.tolist()), np.int64)
                        cand &= np.isin(rank_ids, union)
                    sp.tag(rows=len(union),
                           rebuilt=thread_rank_rebuilds() - rebuilt)
                if not len(union):
                    return []
                chunks = []
                CHUNK = _topn_chunk(len(shard_list))  # bounds the gather working set
                for i in range(0, len(union), CHUNK):
                    if i:
                        # Between chunks AND after the previous chunk's
                        # gather: a budget that died mid-TopN stops here
                        # (503) instead of finishing dead device work.
                        self._check_chunk_deadline(
                            opt.deadline, "between TopN chunks")
                    rows = union[i : i + CHUNK].tolist()
                    # Ranking uses the cache counts already attached to the
                    # candidates; the device program only computes the src
                    # intersections (need_row_counts=False).
                    with obs_span("topn.chunk", rows=len(rows),
                                  shards=len(shard_list)):
                        _, inter, src_counts = self._topn_counts_laddered(
                            index, field_name, rows, shard_list, src_call,
                            False,
                        )
                    chunks.append(inter)
                with obs_span("topn.replay", rows=len(union),
                              shards=len(shard_list)):
                    inter = np.concatenate(chunks)
                    # (union, shards) -> each shard's rank order. A cell
                    # that is no candidate reads some row's count, which
                    # nothing uses.
                    row_of = np.minimum(
                        np.searchsorted(union, rank_ids), len(union) - 1)
                    count = np.asarray(inter, np.int64)[
                        row_of, np.arange(len(shard_list))[:, None]]
                    accepted = _replay_topn(
                        rank_cnt, count, cand,
                        np.asarray(src_counts, np.int64), n_arg, thr, tanimoto)
                    # Every accepted count is over 0, so a row some shard
                    # accepted has a total over 0: what add_pairs over the
                    # shards' pair lists gave.
                    totals = np.zeros(len(union), np.int64)
                    np.add.at(totals, row_of[accepted], count[accepted])
                    pairs = [
                        Pair(id=r, count=t)
                        for r, t in zip(union.tolist(), totals.tolist()) if t
                    ]
                self.topn_queries += 1
                self.topn_chunks += len(chunks)
                self.topn_candidate_rows += len(union)
                return pairs

        if local_runner is not None:
            # Last rung for a batch neither the device nor the host
            # evaluator could serve (e.g. degraded plane + BSI src): the
            # reference per-shard TopN walk, same one _map_reduce runs.
            batched_runner = local_runner

            def guarded_runner(local_shards):
                try:
                    out = batched_runner(local_shards)
                    self.topn_array_walks += 1
                    return out
                except DeviceDispatchError as e:
                    self._count_stat("DeviceLadderFallback")
                    self.logger.error(
                        "batched TopN unavailable (%s), per-shard rung: %s",
                        e.kind, e)
                    self.topn_shard_replays += len(local_shards)
                    out = []
                    for s in local_shards:
                        out = add_pairs(out, map_fn(s))
                    return out

            result = self._fan_out(
                index, shards, c, opt, guarded_runner, add_pairs) or []
        else:
            result = self._map_reduce(index, shards, c, opt, map_fn, add_pairs) or []
        return sort_pairs(result)

    def _execute_topn_shard(self, index: str, c: Call, shard: int) -> List[Pair]:
        field_name = c.args.get("_field") or DEFAULT_FIELD
        n, _ = c.uint_arg("n")
        attr_name = c.args.get("attrName", "")
        row_ids = self._uint_slice_arg(c, "ids")
        min_threshold, _ = c.uint_arg("threshold")
        attr_values = c.args.get("attrValues") or []
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if tanimoto > 100:
            raise QueryError("Tanimoto Threshold is from 1 to 100 only")

        src = None
        if len(c.children) == 1:
            src = self._execute_bitmap_call_shard(index, c.children[0], shard)
        elif len(c.children) > 1:
            raise QueryError("TopN() can only have one input bitmap")

        frag = self._fragment(index, field_name, VIEW_STANDARD, shard)
        if frag is None:
            return []
        return frag.top(
            TopOptions(
                n=n,
                src=src,
                row_ids=row_ids,
                min_threshold=min_threshold or DEFAULT_MIN_THRESHOLD,
                filter_name=attr_name,
                filter_values=attr_values,
                tanimoto_threshold=tanimoto,
            )
        )

    @staticmethod
    def _uint_slice_arg(c: Call, key: str) -> List[int]:
        v = c.args.get(key)
        if v is None:
            return []
        if not isinstance(v, list):
            raise QueryError(f"invalid call.Args[{key}]: {v!r}")
        return [int(x) for x in v]

    # --------------------------------------------------------------- writes

    def _forward_tolerant(self, node, send, errors, note_app_error,
                          what: str = "", hint=None):
        """THE per-target write-tolerance step (one implementation for
        the single-shard and the group fan-outs): breaker short-circuit
        (don't pay a connect timeout per write; an elapsed backoff makes
        this forward the half-open probe), transport-vs-4xx
        classification — a 4xx means the replica is alive and rejected
        the write, which is transport-level SUCCESS for the breaker (a
        half-open probe must re-close, not wedge) but is handed to
        `note_app_error` so the caller surfaces the divergence only
        after every other owner got its forward — and health recording.
        Returns the forward's result on success, None otherwise (errors
        are appended, never raised).

        `hint` (hinted handoff, cluster/hints.py) is a callable(node) ->
        bool that appends this write's captured op batch to the peer's
        durable hint log; it runs when the forward is skipped at the
        breaker or fails at the transport, so a dead replica costs an
        O(batch) disk append — never a connect timeout — and the missed
        write replays when the peer returns. While a peer has UNDELIVERED
        hints, later writes append behind them even though the breaker
        would admit a send: per-peer FIFO keeps replay order identical to
        coordinator apply order, so a drain can never resurrect a bit
        that a post-recovery write already cleared. A hinted forward
        still counts as NOT applied for write-consistency accounting."""
        from .server.client import ClientError

        if hint is not None and self.hints is not None \
                and self.hints.pending(node.id):
            if hint(node):
                self._count_stat("WriteForwardHinted")
                errors.append(
                    f"{node.id}{what}: hinted (queued behind pending "
                    "handoff)")
                return None
            # Hint append refused (byte budget / disk fault): fall through
            # to the direct forward — applying out of order beats dropping
            # the write, and anti-entropy owns the reconciliation either
            # way (the refused append flagged the shard for priority sync).
        if not self.health.allow_request(node.id):
            self._count_stat("WriteForwardSkipped")
            if hint is not None and hint(node):
                self._count_stat("WriteForwardHinted")
                errors.append(
                    f"{node.id}{what}: unavailable (breaker open; hinted)")
            else:
                errors.append(f"{node.id}{what}: unavailable (breaker open)")
            return None
        try:
            res = send(node)
        except ClientError as e:
            if not _is_node_failure(e):
                self.health.record_success(node.id)
                note_app_error(e)
                errors.append(f"{node.id}: {e}")
                return None
            self.health.record_failure(node.id)
            self._count_stat("WriteForwardFailed")
            if hint is not None and hint(node):
                self._count_stat("WriteForwardHinted")
            errors.append(f"{node.id}: {e}")
            return None
        self.health.record_success(node.id)
        return res if res is not None else True

    def _write_required(self, n_owners: int) -> int:
        """Owners that must APPLY before a write acks ([replication]
        write-consistency): 1 without config (the reference behavior)."""
        cfg = self.replication_config
        return 1 if cfg is None else cfg.required_owners(n_owners)

    def _write_level(self) -> str:
        cfg = self.replication_config
        return "one" if cfg is None else cfg.write_consistency

    def tolerant_owner_fanout(self, index: str, shard: int, remote: bool,
                              local_fn, forward_fn, on_forward_ok=None,
                              hint=None):
        """THE write-tolerance policy, shared by PQL writes and bulk
        imports (executor.go:1109): apply locally FIRST (arming the
        caller's hint capture with this write's op bytes), forward to
        every other owner, hint-or-skip dead owners (hinted handoff
        replays the miss when the peer returns; anti-entropy remains the
        backstop), finish the whole loop before surfacing a deterministic
        4xx rejection (so one lagging replica cannot cause extra
        divergence on the others), then gate the ack on the configured
        write-consistency level: a write that applied on fewer owners
        than `one|quorum|all` requires surfaces as a typed retryable 503
        (errors.WriteConsistencyError) AFTER hints were enqueued for the
        missed owners — the applied copies stand, there is no rollback
        (docs/durability.md "Write-path consistency").

        Live-rebalance cutovers surface here as ShardMovedError (the
        local fragment froze) or a 409 from a frozen remote owner: the
        write re-routes on refreshed placement — re-applying to an owner
        that already took it is an idempotent set/clear — and keeps
        retrying up to `cutover_wait` while the commit broadcast lands,
        so a write racing the cutover follows the shard to its new owner
        instead of failing. Past the cap it surfaces clean (retryable)."""
        from .errors import ShardMovedError, WriteConsistencyError

        deadline = _time.monotonic() + (0.0 if remote else
                                        max(self.cutover_wait, 0.0))
        while True:
            try:
                applied, total, errors = self._owner_fanout_once(
                    index, shard, remote, local_fn, forward_fn,
                    on_forward_ok, hint)
            except PilosaError as e:
                mid_cutover = isinstance(e, ShardMovedError) or (
                    getattr(e, "status", 0) == 409)
                if not mid_cutover or _time.monotonic() >= deadline:
                    raise
                if self.holder.stats is not None:
                    self.holder.stats.count("CutoverWriteWait", 1)
                _time.sleep(0.02)
                continue
            if remote:
                # Forwarded leg: the COORDINATOR owns level accounting
                # (our `applied` counts the forwarder's owners as
                # fictitious applies).
                return
            required = self._write_required(total)
            if applied < required:
                self._count_stat("WriteConsistencyUnmet")
                raise WriteConsistencyError(
                    f"applied on {applied}/{total} owners of {index}/"
                    f"shard {shard}, level {self._write_level()!r} "
                    f"requires {required}: " + "; ".join(errors),
                    level=self._write_level(), required=required,
                    applied=applied,
                )
            return

    def _owner_fanout_once(self, index, shard, remote, local_fn, forward_fn,
                           on_forward_ok, hint=None):
        """One fan-out pass; returns (applied, n_owners, errors)."""
        applied = 0
        errors = []
        app_error = [None]

        def note(e):
            app_error[0] = app_error[0] or e

        owners = self.cluster.shard_nodes(index, shard)
        if remote and not any(n.id == self.node.id for n in owners):
            # A forwarded write for a shard this node no longer serves
            # (the sender routed under a pre-cutover placement). The old
            # behavior — count every non-self owner as applied-by-
            # forwarder and ack — SILENTLY DROPPED the write: zero
            # fragments were touched. Raise instead (HTTP 409) so the
            # sender re-routes to the shard's current owner.
            from .errors import ShardMovedError

            raise ShardMovedError(
                f"{index}/shard {shard} is not served by this node")
        # Local apply first (stable otherwise): the caller's hint capture
        # is filled by the local apply, and a forward can miss — and need
        # those bytes — at ANY position in the owner walk. Replicas have
        # no ordering contract among themselves, so the reorder is free.
        for node in sorted(owners, key=lambda n: n.id != self.node.id):
            if node.id == self.node.id:
                local_fn()
                applied += 1
                continue
            if remote:
                applied += 1  # forwarding node already counted the write
                continue
            res = self._forward_tolerant(node, forward_fn, errors, note,
                                         hint=hint)
            if res is None:
                continue
            applied += 1
            if on_forward_ok is not None:
                on_forward_ok(res if res is not True else None)
        if app_error[0] is not None:
            raise app_error[0]
        return applied, len(owners), errors

    def tolerant_group_fanout(self, index: str, shards, remote: bool,
                              apply_local, send_remote,
                              workers: int = 1) -> None:
        """Bulk-import fan-out for MANY shard batches at once: the same
        write-tolerance policy as tolerant_owner_fanout (dead replicas
        skipped + marked, deterministic rejections surfaced only after
        every batch got its chance, failure only when a shard reached NO
        owner), but parallel — local applies run across the worker pool
        and remote forwards are batched PER PEER: one task per node
        streams that node's shard batches sequentially over its
        keep-alive connection while different nodes (and local applies)
        proceed concurrently. `workers` caps how much of the shared pool
        one import may occupy, so a huge load can't starve query fan-out
        of threads. apply_local(shard) / send_remote(node, shard).

        Hinted handoff + consistency: local applies run under hint
        capture (core/fragment.py), and the local wave completes BEFORE
        any remote forward is attempted — a forward that then misses
        enqueues the shard's captured op batch for the dead peer (a shard
        with no local replica degrades to a sync-priority marker). After
        the loop, the same [replication] write-consistency gate as the
        single-shard fan-out applies PER SHARD: any shard under its level
        raises a typed retryable 503 (hints already enqueued, no
        rollback)."""
        import threading

        from .core.fragment import capture_hint_ops

        # Placement resolved up front: one routing decision per import.
        plan = {int(s): self.cluster.shard_nodes(index, int(s)) for s in shards}
        if remote:
            from .errors import ShardMovedError

            for shard, owners in plan.items():
                if not any(n.id == self.node.id for n in owners):
                    # Same silent-drop hazard as the single-shard fanout:
                    # a forwarded batch for a migrated-away shard must
                    # 409 so the sender re-routes, not ack into the void.
                    raise ShardMovedError(
                        f"{index}/shard {shard} is not served by this node")
        applied = {s: 0 for s in plan}
        errors: List[str] = []
        app_error: List[Optional[Exception]] = [None]
        captured: Dict[int, list] = {}  # shard -> [(frag, op_bytes)]
        mu = threading.Lock()

        local_shards: List[int] = []
        node_work: Dict[str, tuple] = {}  # node.id -> (node, [shards])
        for shard, owners in plan.items():
            for node in owners:
                if node.id == self.node.id:
                    local_shards.append(shard)
                elif remote:
                    applied[shard] += 1  # forwarding node counted the write
                else:
                    node_work.setdefault(node.id, (node, []))[1].append(shard)

        def run_local(shard):
            rec: list = []
            try:
                with capture_hint_ops(rec):
                    apply_local(shard)
            except Exception as e:
                # Local failures are deterministic (validation, storage
                # fault): surface after the loop like a replica's 4xx, so
                # one bad batch can't abort the others mid-flight.
                with mu:
                    app_error[0] = app_error[0] or e
                    errors.append(f"local/shard {shard}: {e}")
                return
            with mu:
                captured[shard] = rec
                applied[shard] += 1

        def note_app_error(e):
            with mu:
                app_error[0] = app_error[0] or e

        def hint_for(shard):
            def hint(node):
                if self.hints is None:
                    return False
                with mu:
                    rec = captured.get(shard)
                return self.hints.add(node.id, index, shard, rec)
            return hint

        def run_node(node, shard_list):
            # The per-target tolerance step is _forward_tolerant — the
            # SAME implementation tolerant_owner_fanout uses, so the two
            # fan-outs cannot drift apart on breaker/4xx/hint semantics.
            for shard in shard_list:
                local_errs: List[str] = []
                res = self._forward_tolerant(
                    node, lambda n, s=shard: send_remote(n, s),
                    local_errs, note_app_error, what=f"/shard {shard}",
                    hint=hint_for(shard))
                with mu:
                    errors.extend(local_errs)
                    if res is not None:
                        applied[shard] += 1

        # Two waves — all local applies, THEN remote forwards: a forward
        # can only hint op bytes its shard's local apply has already
        # captured. Locals still parallelize among themselves and per-peer
        # streams still overlap each other; only the local->remote overlap
        # is given up, and that was already bounded by `workers` waves.
        for tasks in ([(run_local, (s,)) for s in local_shards],
                      [(run_node, nw) for nw in node_work.values()]):
            if self._pool is None or workers <= 1 or len(tasks) <= 1:
                for fn, args in tasks:
                    fn(*args)
            else:
                # Bounded waves rather than one submit-all: `workers` caps
                # this import's occupancy of the shared pool.
                cap = max(1, workers)
                for i in range(0, len(tasks), cap):
                    futs = [self._pool.submit(fn, *args)
                            for fn, args in tasks[i:i + cap]]
                    for f in futs:
                        f.result()  # worker exceptions captured inside

        if app_error[0] is not None:
            raise app_error[0]
        if remote:
            # Forwarded leg: the coordinator owns level accounting.
            return
        from .errors import WriteConsistencyError

        under = sorted(
            s for s, n in applied.items()
            if n < self._write_required(len(plan[s])))
        if under:
            self._count_stat("WriteConsistencyUnmet")
            raise WriteConsistencyError(
                f"import applied under level {self._write_level()!r} on "
                f"{index}/shards {under}: " + "; ".join(errors),
                level=self._write_level(),
            )

    def _for_shard_owners(self, index: str, c: Call, shard: int, opt: ExecOptions, local_fn):
        """Apply a PQL write locally and forward to other owners — the
        shared tolerant fan-out with query_node as the transport. The
        local apply runs under a hint capture (core/fragment.py), so a
        missed forward hands the peer's hint log the exact WAL op bytes
        this write produced — every view the write touched (standard plus
        time-quantum views) rides along with no re-derivation."""
        from .core.fragment import capture_hint_ops

        out = {"ret": False}
        captured: list = []

        def local():
            captured.clear()  # cutover retries must not double the batch
            with capture_hint_ops(captured):
                if local_fn():
                    out["ret"] = True

        def forward(node):
            return self.client.query_node(node, index, str(c), remote=True)

        def note(res):
            if res and isinstance(res[0], bool):
                out["ret"] = out["ret"] or res[0]

        def hint(node):
            if self.hints is None:
                return False
            return self.hints.add(node.id, index, shard, captured)

        self.tolerant_owner_fanout(
            index, shard, opt.remote, local, forward, on_forward_ok=note,
            hint=hint,
        )
        return out["ret"]

    def _execute_set_bit(self, index: str, c: Call, opt: ExecOptions) -> bool:
        field_name = c.field_arg()
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        fld = idx.field(field_name)
        if fld is None:
            raise FieldNotFoundError(field_name)
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise QueryError("Set() row argument required")
        col_id, ok = c.uint_arg("_col")
        if not ok:
            raise QueryError("Set() column argument required")
        timestamp = None
        ts = c.args.get("_timestamp")
        if isinstance(ts, str):
            timestamp = parse_timestamp(ts)
        shard = col_id // SHARD_WIDTH
        return self._for_shard_owners(
            index, c, shard, opt, lambda: fld.set_bit(row_id, col_id, timestamp)
        )

    def _execute_clear_bit(self, index: str, c: Call, opt: ExecOptions) -> bool:
        field_name = c.field_arg()
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        fld = idx.field(field_name)
        if fld is None:
            raise FieldNotFoundError(field_name)
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise QueryError("Clear() row argument required")
        col_id, ok = c.uint_arg("_col")
        if not ok:
            raise QueryError("Clear() column argument required")
        shard = col_id // SHARD_WIDTH
        return self._for_shard_owners(
            index, c, shard, opt, lambda: fld.clear_bit(row_id, col_id)
        )

    def _execute_set_value(self, index: str, c: Call, opt: ExecOptions) -> None:
        col_id, ok = c.uint_arg("col")
        if not ok:
            # Message parity: executor_test.go:451-458.
            raise QueryError("SetValue() column field 'col' required")
        args = {k: v for k, v in c.args.items() if k != "col"}
        for name, value in args.items():
            fld = self.holder.field(index, name)
            if fld is None:
                raise FieldNotFoundError(name)
            if not isinstance(value, int) or isinstance(value, bool):
                # pilosa.go:42 ErrInvalidBSIGroupValueType.
                raise QueryError("invalid bsigroup value type")
            fld.set_value(col_id, value)
        self._forward_to_all(index, c, opt)

    def _execute_set_row_attrs(self, index: str, c: Call, opt: ExecOptions) -> None:
        field_name = c.args.get("_field")
        fld = self.holder.field(index, field_name)
        if fld is None:
            raise FieldNotFoundError(field_name)
        row_id, ok = c.uint_arg("_row")
        if not ok:
            raise QueryError("SetRowAttrs() row argument required")
        attrs = {k: v for k, v in c.args.items() if k not in ("_field", "_row")}
        fld.row_attr_store.set_attrs(row_id, attrs)
        self._forward_to_all(index, c, opt)

    def _execute_set_column_attrs(self, index: str, c: Call, opt: ExecOptions) -> None:
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        col, ok = c.uint_arg("_col")
        if not ok:
            raise QueryError("SetColumnAttrs() col argument required")
        attrs = {k: v for k, v in c.args.items() if k not in ("_col", "field")}
        idx.column_attr_store.set_attrs(col, attrs)
        self._forward_to_all(index, c, opt)

    def _forward_to_all(self, index: str, c: Call, opt: ExecOptions) -> None:
        """Fan a write out to every node. The local apply already succeeded,
        so dead peers are marked unavailable and skipped rather than failing
        the request (anti-entropy converges them later); previously one dead
        peer made every attr/value write block on a client timeout and raise."""
        from .server.client import ClientError

        if opt.remote:
            return
        app_error = None
        for node in self.cluster.nodes:
            if node.id == self.node.id:
                continue
            if not self.health.allow_request(node.id):
                self._count_stat("WriteForwardSkipped")
                continue
            try:
                self.client.query_node(node, index, str(c), remote=True)
            except ClientError as e:
                if not _is_node_failure(e):
                    # Deterministic rejection by a live peer: transport
                    # success for the breaker; finish the fan-out (don't
                    # widen divergence), then surface it.
                    self.health.record_success(node.id)
                    app_error = app_error or e
                    continue
                self.health.record_failure(node.id)
                self._count_stat("WriteForwardFailed")
            else:
                self.health.record_success(node.id)
        if app_error is not None:
            raise app_error

    # ---------------------------------------------------------- translation

    def _translate_call(self, index: str, idx, c: Call) -> None:
        """Translate string keys to ids in-place (executor.go:1595-1659).

        Mirrors the reference's key selection exactly: Set/Clear/Row use the
        positional column arg and the field-named row arg; every other call
        uses literal 'col'/'row' args with the field taken from a 'field'
        arg — so e.g. SetValue(col=10, f="x") is NOT key-translated and
        falls through to the BSI type check (executor_test.go:461-466)."""
        store = self.translate_store
        if store is not None:
            if c.name in ("Set", "Clear", "Row"):
                col_key = "_col"
                # Reference ignores FieldArg errors here (fieldName, _ =
                # c.FieldArg()); a missing field is rejected at execution
                # time, not during translation.
                try:
                    field_name = c.field_arg()
                except QueryError:
                    field_name = None
                row_key = field_name
            else:
                col_key = "col"
                # callArgString semantics: a non-string `field` arg reads as
                # "" in the reference, so row translation is skipped and the
                # call is rejected later — not a FieldNotFoundError here.
                fv = c.args.get("field")
                field_name = fv if isinstance(fv, str) else None
                row_key = "row"

            col = c.args.get(col_key)
            if idx.keys():
                if col is not None and not isinstance(col, str):
                    raise QueryError(
                        "column value must be a string when index 'keys' option enabled"
                    )
                if isinstance(col, str) and col != "":
                    # Empty keys are not translated (callArgString != ""
                    # guard); the later uint-arg check rejects the call.
                    c.args[col_key] = store.translate_columns_to_uint64(index, [col])[0]
            elif isinstance(col, str):
                raise QueryError(
                    "string 'col' value not allowed unless index 'keys' option enabled"
                )

            if field_name:
                fld = idx.field(field_name)
                if fld is None:
                    raise FieldNotFoundError(field_name)
                row = c.args.get(row_key)
                if fld.keys():
                    if row is not None and not isinstance(row, str):
                        raise QueryError(
                            "row value must be a string when field 'keys' option enabled"
                        )
                    if isinstance(row, str) and row != "":
                        c.args[row_key] = store.translate_rows_to_uint64(
                            index, field_name, [row]
                        )[0]
                elif isinstance(row, str):
                    raise QueryError(
                        "string 'row' value not allowed unless field 'keys' option enabled"
                    )
        for child in c.children:
            self._translate_call(index, idx, child)

    def _translate_result(self, index: str, idx, c: Call, result):
        store = self.translate_store
        if store is None:
            return result
        if isinstance(result, Row) and idx.keys():
            result.keys = store.translate_columns_to_string(
                index, [int(x) for x in result.columns()]
            )
        if isinstance(result, list) and result and isinstance(result[0], Pair):
            field_name = c.args.get("_field")
            fld = idx.field(field_name) if field_name else None
            if fld is not None and fld.keys():
                result = [
                    Pair(id=p.id, count=p.count,
                         key=store.translate_row_to_string(index, field_name, p.id))
                    for p in result
                ]
        return result
