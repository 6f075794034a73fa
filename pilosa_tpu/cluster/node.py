"""Node identity and cluster membership/placement.

Port of the data-placement core of /root/reference/cluster.go: Node, cluster
states, partition/shardNodes placement with replication. The full resize
state machine lives in cluster/resize.py; this module is dependency-light so
the executor can use placement without pulling in networking.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

from ..constants import DEFAULT_PARTITION_N
from .hash import MEMO_ENTRIES, JmpHasher, shard_hash
from .health import DownView, HealthRegistry

# Cluster states (reference cluster.go:43-45).
STATE_STARTING = "STARTING"
STATE_NORMAL = "NORMAL"
STATE_RESIZING = "RESIZING"


@dataclass
class Node:
    id: str
    uri: str = ""
    is_coordinator: bool = False
    # jax.distributed process index when this node is part of a multi-host
    # device-mesh job (None otherwise). The collective plane needs every
    # node's index to map jump-hash shard placement onto global-array slots
    # (parallel/collective.py placement); it propagates via node-join /
    # cluster-status messages and the member monitor's status probes.
    process_idx: Optional[int] = None

    def to_dict(self):
        d = {"id": self.id, "uri": self.uri, "isCoordinator": self.is_coordinator}
        if self.process_idx is not None:
            d["processIdx"] = self.process_idx
        return d

    @classmethod
    def from_dict(cls, d):
        return cls(
            id=d["id"], uri=d.get("uri", ""),
            is_coordinator=d.get("isCoordinator", False),
            process_idx=d.get("processIdx"),
        )


class Cluster:
    """Membership + placement. Single-node by default; multi-node clusters
    append Nodes (sorted by id, as the reference maintains them)."""

    def __init__(
        self,
        node: Optional[Node] = None,
        nodes: Optional[List[Node]] = None,
        replica_n: int = 1,
        partition_n: int = DEFAULT_PARTITION_N,
        hasher=None,
    ):
        self.node = node or Node(id="node0")
        self.nodes: List[Node] = nodes or [self.node]
        self.replica_n = replica_n
        self.partition_n = partition_n
        self.hasher = hasher or JmpHasher()
        self.state = STATE_NORMAL
        # Per-peer fault-tolerance state (cluster/health.py): circuit
        # breakers, retry budget, rolling latencies. The server installs
        # its [resilience] config via health.configure(); library users
        # get the defaults. Placement ignores this; the executor's owner
        # selection, retry, and hedging logic consult it.
        self.health = HealthRegistry()
        # Node ids currently down (failure detector; the reference's
        # memberlist suspicion state). A set-like view over the breaker
        # state: `in` means "breaker not closed", add/discard force it.
        self.unavailable = DownView(self.health)
        # Per-shard routing epochs (cluster/rebalance.py). During a live
        # rebalance `next_nodes` holds the target membership and
        # `migrated` the (index, shard) pairs whose cutover committed:
        # placement for a migrated shard follows the NEXT topology while
        # every other shard stays on the old owners — a half-migrated
        # cluster never serves a hole. `routing_epoch` is monotonic;
        # forwarded requests stamp it, and a receiver that has advanced
        # past the sender's epoch answers 409 (one re-route) instead of
        # serving from a moved/GC'd shard.
        self.routing_epoch = 0
        self.next_nodes: Optional[List[Node]] = None
        self.migrated: Set[Tuple[str, int]] = set()
        self._routing_mu = threading.Lock()
        # shard_hash per (index, shard): the pure half of partition().
        # Placement keeps pure functions only (this and JmpHasher's), and
        # reads nodes, next_nodes, migrated, replica_n and partition_n
        # live on every call, because all of them are also changed in
        # place (tests, add_node, a cutover) where no counter would see.
        self._shard_hashes: Dict[Tuple[str, int], int] = {}

    # ------------------------------------------------------------ placement

    def partition(self, index: str, shard: int) -> int:
        h = self._shard_hashes.get((index, shard))
        if h is None:
            if len(self._shard_hashes) >= MEMO_ENTRIES:
                self._shard_hashes.clear()
            h = self._shard_hashes[(index, shard)] = shard_hash(index, shard)
        return h % self.partition_n

    def _placement(self, nodes: List[Node], partition_id: int) -> List[Node]:
        if not nodes:
            return []
        replica_n = min(self.replica_n, len(nodes)) or 1
        node_index = self.hasher.hash(partition_id, len(nodes))
        return [nodes[(node_index + i) % len(nodes)] for i in range(replica_n)]

    def partition_nodes(self, partition_id: int) -> List[Node]:
        return self._placement(self.nodes, partition_id)

    def shard_nodes(self, index: str, shard: int) -> List[Node]:
        # Snapshot the override state once: a concurrent commit/abort can
        # null next_nodes between a check and a re-read, and
        # _placement(None) would return zero owners for an owned shard.
        nxt = self.next_nodes
        nodes = self.nodes
        if nxt is not None and (index, shard) in self.migrated:
            nodes = nxt
        return self._placement(nodes, self.partition(index, shard))

    def _witness(self, nodes: List[Node]):
        return ([n.id for n in nodes], self.replica_n, self.partition_n,
                self.hasher, self.node.id)

    def placement_witness(self):
        """Everything the owners of a shard depend on besides its index
        and number, as a value to compare with `==`; None while a
        rebalance is in flight (`migrated` then moves with every cutover).
        Equal witnesses mean equal placement, whatever happened between
        the two readings: node ids are compared by value and in order, so
        an assignment, an in-place sort or append, and a rewritten Node.id
        all show. `next_nodes` is read before `nodes`, as in shard_nodes:
        a commit assigns `nodes` first."""
        if self.next_nodes is not None:
            return None
        return self._witness(self.nodes)

    def shard_list_owners(self, index: str, shards: List[int]):
        """(witness, owner ids of each shard in placement order). With a
        witness the ids were computed from the one copy of `nodes` that
        the witness names, so the pair belongs together and may be kept
        for as long as placement_witness() equals it; a partition's list
        of ids is shared by its shards. Without one (a rebalance in
        flight) they are shard_nodes' answers, shard by shard."""
        if self.next_nodes is not None:
            return None, [[n.id for n in self.shard_nodes(index, s)]
                          for s in shards]
        nodes = list(self.nodes)
        witness = self._witness(nodes)
        by_partition: Dict[int, List[str]] = {}
        owners = []
        for shard in shards:
            p = self.partition(index, shard)
            ids = by_partition.get(p)
            if ids is None:
                ids = by_partition[p] = [
                    n.id for n in self._placement(nodes, p)]
            owners.append(ids)
        return witness, owners

    # ------------------------------------------------------ routing epochs

    def _advance_epoch(self, epoch: Optional[int]) -> None:
        # Must hold _routing_mu. An epoch carried by a coordinator
        # message is AUTHORITATIVE: merge with max() only. A local
        # routing change with no message epoch bumps by one. Doing both
        # (max(local+1, msg)) overshoots under message reordering — a
        # later commit's merge jumps the counter, then an earlier
        # commit's +1 pushes it past every number the coordinator will
        # ever send, and the node ends permanently ahead of the cluster.
        if epoch is not None:
            self.routing_epoch = max(self.routing_epoch, epoch)
        else:
            self.routing_epoch += 1

    def begin_rebalance(self, new_nodes: List[Node], committed=(),
                        epoch: Optional[int] = None) -> None:
        """Install the target membership of a live rebalance. Placement
        keeps following the OLD nodes until per-shard cutovers commit."""
        with self._routing_mu:
            self.next_nodes = sorted(new_nodes, key=lambda n: n.id)
            self.migrated = {(i, int(s)) for i, s in committed}
            self._advance_epoch(epoch)

    def apply_cutover(self, index: str, shard: int,
                      epoch: Optional[int] = None) -> None:
        """Commit one shard's routing flip to the next topology."""
        with self._routing_mu:
            if self.next_nodes is None:
                # No rebalance in flight (late/duplicate commit); still
                # merge an authoritative epoch so a node that already
                # collapsed the overrides doesn't fall behind.
                if epoch is not None:
                    self.routing_epoch = max(self.routing_epoch, epoch)
                return
            if (index, shard) in self.migrated:
                # Idempotent: the source flips at freeze time and again on
                # the broadcast commit; only the first advances the epoch.
                if epoch is not None:
                    self.routing_epoch = max(self.routing_epoch, epoch)
                return
            self.migrated.add((index, shard))
            self._advance_epoch(epoch)

    def revert_cutover(self, index: str, shard: int,
                       epoch: Optional[int] = None) -> None:
        """Reverse migration (autoscale abort, docs/rebalance.md): flip
        one committed shard's routing BACK to the prior topology after
        its data has been streamed back to the prior owners. The inverse
        of apply_cutover; idempotent the same way."""
        with self._routing_mu:
            if self.next_nodes is None:
                if epoch is not None:
                    self.routing_epoch = max(self.routing_epoch, epoch)
                return
            if (index, shard) not in self.migrated:
                # Late/duplicate revert; still merge an authoritative
                # epoch so this node doesn't fall behind.
                if epoch is not None:
                    self.routing_epoch = max(self.routing_epoch, epoch)
                return
            self.migrated.discard((index, shard))
            self._advance_epoch(epoch)

    def commit_topology(self, new_nodes: Optional[List[Node]] = None,
                        epoch: Optional[int] = None) -> None:
        """Job completion: the target membership becomes THE membership
        and the per-shard overrides collapse."""
        with self._routing_mu:
            nodes = new_nodes if new_nodes is not None else self.next_nodes
            if nodes is not None:
                self.nodes = sorted(nodes, key=lambda n: n.id)
            self.next_nodes = None
            self.migrated = set()
            self._advance_epoch(epoch)

    def adopt_topology_if_ahead(self, new_nodes: List[Node],
                                epoch: Optional[int]) -> bool:
        """Anti-entropy adoption (member monitor): atomically re-validate
        and commit a peer's post-job topology. The monitor's decision to
        adopt runs OUTSIDE the routing lock, so a rebalance-begin landing
        between the decision and the commit would otherwise have its
        next_nodes/migrated overrides wiped by the late commit — routing
        cut-over shards back to their old owners until the job's complete
        broadcast. Returns False when the adoption lost the race (a begin
        installed overrides, or the epoch caught up meanwhile)."""
        with self._routing_mu:
            if (self.next_nodes is not None
                    or epoch is None
                    or epoch <= self.routing_epoch):
                return False
            self.nodes = sorted(new_nodes, key=lambda n: n.id)
            self.migrated = set()
            self.routing_epoch = epoch
            return True

    def abort_rebalance(self, committed=None) -> bool:
        """Drop a live rebalance. Returns True when routing fully
        reverted to the old topology; False when cutovers had already
        committed — those shards keep the mixed routing (their data now
        lives on the new owners; reverting would lose post-cutover
        writes) until a resumed job finishes the move."""
        with self._routing_mu:
            kept = {(i, int(s)) for i, s in committed} if committed else set()
            kept &= self.migrated
            if not kept:
                self.next_nodes = None
                self.migrated = set()
                self.routing_epoch += 1
                return True
            self.migrated = kept
            self.routing_epoch += 1
            return False

    def mark_unavailable(self, node_id: str) -> None:
        self.unavailable.add(node_id)

    def mark_available(self, node_id: str) -> None:
        self.unavailable.discard(node_id)

    def owns_shard(self, node_id: str, index: str, shard: int) -> bool:
        return any(n.id == node_id for n in self.shard_nodes(index, shard))

    def contains_shards(self, index: str, max_shard: int, node: Node) -> List[int]:
        return [
            s
            for s in range(max_shard + 1)
            if any(n.id == node.id for n in self.partition_nodes(self.partition(index, s)))
        ]

    def node_by_id(self, node_id: str) -> Optional[Node]:
        for n in self.nodes:
            if n.id == node_id:
                return n
        # Mid-rebalance, a cut-over shard's owners come from the target
        # membership (e.g. the joining node) before it appears in `nodes`.
        if self.next_nodes is not None:
            for n in self.next_nodes:
                if n.id == node_id:
                    return n
        return None

    def coordinator_node(self) -> Optional[Node]:
        """The coordinator, preferring an AVAILABLE flagged node: after a
        failover a survivor can transiently hold both the dead
        coordinator's stale flag and the successor's fresh claim — joins
        must route to the live one, not the lowest-id corpse."""
        flagged = [n for n in self.nodes if n.is_coordinator]
        for n in flagged:
            if n.id not in self.unavailable:
                return n
        return flagged[0] if flagged else None

    def is_coordinator(self) -> bool:
        return self.node.is_coordinator

    def add_node(self, node: Node) -> None:
        if self.node_by_id(node.id) is None:
            # One assignment, not append + sort in place: a list is empty
            # to its readers while it sorts, and placement reads `nodes`
            # from every serving thread without a lock.
            self.nodes = sorted(self.nodes + [node], key=lambda n: n.id)

    def remove_node(self, node_id: str) -> bool:
        n = self.node_by_id(node_id)
        if n is None:
            return False
        self.nodes.remove(n)
        # Drop health/availability state with the membership entry: a
        # removed node's stale breaker must not shadow a later re-add
        # that reuses the same id.
        self.health.prune(node_id)
        return True
