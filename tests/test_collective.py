"""Generalized multi-host collective plane (parallel/collective.py).

Unit level: placement follows the REAL jump-hash cluster placement,
ownership is verified at entry (the round-3 silent-zeros bug), the runner
executes descriptors in cluster-wide seq order.

Integration level (the flagship): TWO real Server processes joined in one
jax.distributed job, data imported through the normal cluster write path
(jump-hash placement), and Count / TopN / Sum answered through the
collective backend — plus the failure mode: a peer that drops descriptors
makes the leader's barrier time out and the query falls back to the HTTP
fan-out instead of hanging (VERDICT r3 items 2-4).
"""

import json
import os
import socket
import subprocess
import sys
import textwrap
import time
from concurrent.futures import Future

import numpy as np
import pytest

from pilosa_tpu.cluster.hash import ModHasher
from pilosa_tpu.cluster.node import Cluster, Node
from pilosa_tpu.parallel.collective import (
    CollectiveUnavailable,
    _Runner,
    placement,
)


def free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ----------------------------------------------------------------- placement


def test_placement_follows_jump_hash():
    nodes = [
        Node(id="n0", process_idx=0),
        Node(id="n1", process_idx=1),
        Node(id="n2", process_idx=2),
    ]
    c = Cluster(node=nodes[0], nodes=nodes, replica_n=1)
    n_shards = 64
    slots = placement(c, "i", n_shards, 3)
    assert sorted(s for lst in slots for s in lst) == list(range(n_shards))
    for p, lst in enumerate(slots):
        for s in lst:
            owners = c.shard_nodes("i", s)
            assert owners[0].process_idx == p, (s, p, owners[0].id)


def test_placement_prefers_available_replica():
    nodes = [
        Node(id="n0", process_idx=0),
        Node(id="n1", process_idx=1),
    ]
    c = Cluster(node=nodes[0], nodes=nodes, replica_n=2, hasher=ModHasher())
    c.mark_unavailable("n0")
    slots = placement(c, "i", 8, 2)
    assert slots[0] == []  # nothing assigned to the dead node's process
    assert sorted(slots[1]) == list(range(8))


def test_placement_requires_process_idx():
    nodes = [Node(id="n0", process_idx=0), Node(id="n1")]  # n1 unknown
    c = Cluster(node=nodes[0], nodes=nodes, replica_n=1, hasher=ModHasher())
    with pytest.raises(CollectiveUnavailable, match="process index"):
        placement(c, "i", 8, 2)


def test_ownership_verification_refuses_unowned_shard():
    """The round-3 bug: a process silently contributed zeros for shards it
    did not own. Entry must refuse instead."""
    from types import SimpleNamespace

    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.logger import NopLogger
    from pilosa_tpu.parallel.collective import CollectiveBackend

    nodes = [Node(id="n0", process_idx=0), Node(id="n1", process_idx=1)]
    cluster = Cluster(node=nodes[0], nodes=nodes, replica_n=1, hasher=ModHasher())
    holder = Holder(None)
    holder.open()
    backend = CollectiveBackend(SimpleNamespace(
        holder=holder, logger=NopLogger(), cluster=cluster, client=None,
    ))
    try:
        # ModHasher, 2 nodes: n0 owns even partitions' shards only.
        owned = [s for s in range(8) if cluster.owns_shard("n0", "i", s)]
        unowned = [s for s in range(8) if not cluster.owns_shard("n0", "i", s)]
        assert owned and unowned
        backend._verify_ownership("i", owned)  # fine
        with pytest.raises(CollectiveUnavailable, match="placement mismatch"):
            backend._verify_ownership("i", [unowned[0]])
    finally:
        backend.close()


# -------------------------------------------------------------------- runner


class _StubBackend:
    def __init__(self):
        self.order = []

    def _enter(self, desc):
        self.order.append(desc["seq"])
        return desc["seq"] * 10


def test_runner_executes_in_seq_order():
    b = _StubBackend()
    r = _Runner(b)
    try:
        # Submit out of order; runner must execute 1, 2, 3.
        futs = {}
        futs[2] = r.submit({"seq": 2})
        futs[3] = r.submit({"seq": 3})
        futs[1] = r.submit({"seq": 1})
        for seq, fut in futs.items():
            assert fut.result(timeout=10) == seq * 10
        assert b.order == sorted(b.order)
    finally:
        r.close()


def test_runner_advances_past_seq_gap():
    """A leader that died between seq allocation and broadcast must not
    stall the queue forever — bounded gap wait, then proceed."""
    b = _StubBackend()
    r = _Runner(b)
    r.GAP_TIMEOUT = 0.2
    try:
        fut = r.submit({"seq": 5})  # seqs 1-4 never arrive
        assert fut.result(timeout=10) == 50
    finally:
        r.close()


# ------------------------------------------- two-process cluster integration

WORKER = textwrap.dedent("""
    import json, os, re, sys, time
    import urllib.request

    # Replace (not append) any inherited device-count flag: pytest's
    # conftest exports an 8-device one, and duplicate flags are ambiguous.
    flags = re.sub(r"--xla_force_host_platform_device_count=\\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    jax_coord, pid, port0, port1, tmp = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
        sys.argv[5],
    )
    os.environ["PILOSA_JAX_COORDINATOR"] = jax_coord
    os.environ["PILOSA_JAX_NUM_PROCESSES"] = "2"
    os.environ["PILOSA_JAX_PROCESS_ID"] = str(pid)
    os.environ["PILOSA_COLLECTIVE_TIMEOUT_MS"] = "4000"

    from pilosa_tpu.server.client import InternalClient
    from pilosa_tpu.server.server import Server

    # Trace collective entries to stderr: on failure pytest shows exactly
    # which seq/kind each process entered and whether it completed.
    from pilosa_tpu.parallel import collective as coll

    _orig_enter = coll.CollectiveBackend._enter

    def _traced_enter(self, desc):
        print(f"[p{pid}] enter seq={desc['seq']} kind={desc['kind']} "
              f"slots={desc['slots']}", file=sys.stderr, flush=True)
        try:
            r = _orig_enter(self, desc)
            print(f"[p{pid}] done seq={desc['seq']} -> {r}",
                  file=sys.stderr, flush=True)
            return r
        except BaseException as e:
            print(f"[p{pid}] FAILED seq={desc['seq']}: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            raise

    coll.CollectiveBackend._enter = _traced_enter

    SW = 1 << 20
    hosts = [f"localhost:{port0}", f"localhost:{port1}"]
    s = Server(
        data_dir=f"{tmp}/node{pid}",
        port=[port0, port1][pid],
        cluster_hosts=hosts,
        replica_n=1,
        cache_flush_interval=0,
        anti_entropy_interval=0,
        member_monitor_interval=0.2,
        executor_workers=0,
    )
    s.open()
    try:
        if pid == 1:
            # Serve until the driver finishes; honor the drop-collective
            # order (failure-mode phase) when the sentinel appears.
            dropped = False
            while not os.path.exists(f"{tmp}/done"):
                if not dropped and os.path.exists(f"{tmp}/drop"):
                    s.collective.receive = lambda desc: None
                    dropped = True
                time.sleep(0.05)
            print("WORKER1_OK")
            sys.exit(0)

        client = InternalClient()
        h = hosts[0]

        # Wait for both processes' indexes to propagate (status probes).
        deadline = time.time() + 30
        while time.time() < deadline and not s.collective.active():
            time.sleep(0.1)
        assert s.collective.active(), [
            (n.id, n.process_idx) for n in s.cluster.nodes
        ]

        client.create_index(h, "ci")
        client.create_field(h, "ci", "f")
        client.create_field(h, "ci", "v",
                            {"type": "int", "min": 0, "max": 255})

        # Data through the NORMAL cluster write path: jump-hash placement
        # decides which node stores each shard's fragment.
        row1 = [5, SW + 1, 3 * SW + 7, 11]
        row2 = [5, SW + 1, 9]
        for col in row1:
            client.query(h, "ci", f"Set({col}, f=1)")
        for col in row2:
            client.query(h, "ci", f"Set({col}, f=2)")
        vals = {5: 10, 9: 20, SW + 1: 30}
        for col, val in vals.items():
            client.query(h, "ci", f"SetValue(col={col}, v={val})")

        def counter(name):
            raw = urllib.request.urlopen(
                f"http://{h}/debug/vars", timeout=5
            ).read()
            return json.loads(raw)["counters"].get(name, 0)

        # --- Count through the collective plane.
        got = client.query(h, "ci", "Count(Intersect(Row(f=1), Row(f=2)))")
        assert got["results"][0] == 2, got
        assert counter("CollectiveCount") >= 1, "collective path not taken"

        # --- TopN: phase-2 candidate counts through the collective plane.
        got = client.query(h, "ci", "TopN(f, n=5)")
        pairs = {p["id"]: p["count"] for p in got["results"][0]}
        assert pairs == {1: 4, 2: 3}, pairs
        assert counter("CollectiveTopN") >= 1

        # --- Sum / Min / Max through the collective plane.
        got = client.query(h, "ci", "Sum(field=v)")
        assert got["results"][0] == {"value": 60, "count": 3}, got
        got = client.query(h, "ci", "Sum(Row(f=1), field=v)")
        assert got["results"][0] == {"value": 40, "count": 2}, got
        got = client.query(h, "ci", "Min(field=v)")
        assert got["results"][0] == {"value": 10, "count": 1}, got
        got = client.query(h, "ci", "Max(field=v)")
        assert got["results"][0] == {"value": 30, "count": 1}, got
        assert counter("CollectiveValCount") >= 4

        # --- Failure mode: the peer starts dropping descriptors. The
        # leader's barrier must time out and the query fall back to the
        # HTTP fan-out — same answer, no hang (VERDICT r3 item 4).
        open(f"{tmp}/drop", "w").close()
        time.sleep(0.3)
        t0 = time.time()
        got = client.query(h, "ci", "Count(Intersect(Row(f=1), Row(f=2)))")
        elapsed = time.time() - t0
        assert got["results"][0] == 2, got
        assert counter("CollectiveFallback") >= 1, "no fallback recorded"
        assert elapsed < 25, f"leader stalled {elapsed}s"
        print(f"WORKER0_OK fallback_after={elapsed:.1f}s")
    finally:
        open(f"{tmp}/done", "w").close()
        s.close()
""")


@pytest.mark.parametrize("n_proc", [2])
def test_two_process_cluster_collective_queries(tmp_path, n_proc):
    jax_port = free_port()
    http_ports = [free_port(), free_port()]
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ,
           "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", "")}
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), f"localhost:{jax_port}", str(pid),
             str(http_ports[0]), str(http_ports[1]), str(tmp_path)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        for pid in range(n_proc)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\nstderr:{err[-3000:]}"
    assert any("WORKER0_OK" in out for _, out, _ in outs)
    assert any("WORKER1_OK" in out for _, out, _ in outs)


# --------------------------- resident stacks / batching / health (PR 12)


def _pod(holder, **cfg_kw):
    """Single-process, single-node backend over `holder` — the one-pod
    serving mode ([collective] single-process) every PR 12 unit test
    drives; the barrier degenerates to a no-op and the mesh is the
    8-device test mesh."""
    from types import SimpleNamespace

    from pilosa_tpu.logger import NopLogger
    from pilosa_tpu.parallel import CollectiveConfig
    from pilosa_tpu.parallel.collective import CollectiveBackend

    node = Node(id="n0", process_idx=0)
    cluster = Cluster(node=node, nodes=[node], replica_n=1)
    server = SimpleNamespace(
        holder=holder, logger=NopLogger(), cluster=cluster, client=None,
    )
    cfg_kw.setdefault("single_process", 1)
    backend = CollectiveBackend(server, CollectiveConfig(**cfg_kw))
    return backend, server


def _plant(holder, n_shards=4, rows=(1, 2, 3)):
    from pilosa_tpu.constants import SHARD_WIDTH

    idx = holder.create_index_if_not_exists("ci")
    idx.create_field_if_not_exists("f")
    rng = np.random.default_rng(7)
    exp = {}
    for row in rows:
        cols = []
        for s in range(n_shards):
            local = np.flatnonzero(rng.random(2048) < 0.1)
            cols.extend(int(s * SHARD_WIDTH + c) for c in local)
        idx.field("f").import_bits([row] * len(cols), cols)
        exp[row] = set(cols)
    return idx, exp


def _call(q):
    from pilosa_tpu.pql.parser import parse

    return parse(q).calls[0].children[0]


@pytest.fixture
def holder():
    from pilosa_tpu.core.holder import Holder

    h = Holder(None)
    h.open()
    yield h
    h.close()


def test_single_process_active_requires_single_node(holder):
    backend, server = _pod(holder)
    try:
        assert backend.active()
        server.cluster.nodes.append(Node(id="n1", process_idx=None))
        # Two nodes, one process: remote shards would read as silently
        # empty — the plane must refuse.
        assert not backend.active()
    finally:
        backend.close()


def test_respellings_share_descriptor_sig_and_program(holder):
    """Satellite: the descriptor signature is the CANONICAL plan
    signature, so commutative respellings share one collective
    descriptor signature and ONE compiled collective program."""
    _, exp = _plant(holder)
    backend, _ = _pod(holder)
    try:
        a = _call("Count(Intersect(Row(f=1), Row(f=2)))")
        b = _call("Count(Intersect(Row(f=2), Row(f=1)))")
        assert backend._call_sig("ci", a) == backend._call_sig("ci", b)
        want = len(exp[1] & exp[2])
        assert backend.count("ci", a) == want
        assert backend.count("ci", b) == want
        count_fns = [k for k in backend._fn_cache if k[0] == "count"]
        assert len(count_fns) == 1, count_fns
    finally:
        backend.close()


def test_count_batch_is_one_entry(holder):
    """A batch of N same-signature queries costs ONE collective entry
    (one seq slot, one barrier, one SPMD program), with duplicates
    deduped inside the program and fanned back out."""
    _, exp = _plant(holder)
    backend, _ = _pod(holder)
    try:
        c12 = _call("Count(Intersect(Row(f=1), Row(f=2)))")
        c13 = _call("Count(Intersect(Row(f=1), Row(f=3)))")
        got = backend.count_batch("ci", [c12, c13, c12, c13])
        assert got == [len(exp[1] & exp[2]), len(exp[1] & exp[3])] * 2
        assert backend.counters["entries"] == 1
        assert backend.counters["batched_entries"] == 4
        assert backend.counters["batched_launches"] == 1
    finally:
        backend.close()


def test_resident_stack_delta_refresh(holder):
    """A write to a resident plane refreshes it by a scattered delta
    (dirty-word journal), not a full re-assembly — and the refreshed
    count is bit-exact."""
    idx, exp = _plant(holder)
    backend, _ = _pod(holder)
    try:
        c = _call("Count(Intersect(Row(f=1), Row(f=2)))")
        assert backend.count("ci", c) == len(exp[1] & exp[2])
        full0 = backend.counters["full_refreshes"]
        assert backend.count("ci", c) == len(exp[1] & exp[2])
        assert backend.counters["resident_hits"] >= 2  # warm: no refresh
        assert backend.counters["full_refreshes"] == full0
        # One-bit write: delta path, not re-assembly.
        idx.field("f").import_bits([1], [5])
        exp[1].add(5)
        assert backend.count("ci", c) == len(exp[1] & exp[2])
        assert backend.counters["delta_hits"] >= 1
        assert backend.counters["full_refreshes"] == full0
    finally:
        backend.close()


def test_resident_stack_delta_disabled(holder):
    """delta-max-fraction=0 turns deltas off: every staleness is a full
    re-assembly (the escape hatch), still bit-exact."""
    idx, exp = _plant(holder)
    backend, _ = _pod(holder, delta_max_fraction=0.0)
    try:
        c = _call("Count(Intersect(Row(f=1), Row(f=2)))")
        assert backend.count("ci", c) == len(exp[1] & exp[2])
        full0 = backend.counters["full_refreshes"]
        idx.field("f").import_bits([1], [5])
        exp[1].add(5)
        assert backend.count("ci", c) == len(exp[1] & exp[2])
        assert backend.counters["delta_hits"] == 0
        assert backend.counters["full_refreshes"] > full0
    finally:
        backend.close()


def test_bsi_stack_resident_across_queries(holder):
    """The BSI plane stack is resident: a repeat Sum re-uses the cached
    (D+1, S, W) stack instead of re-walking containers."""
    from pilosa_tpu.core.field import FieldOptions

    idx, _ = _plant(holder)
    idx.create_field_if_not_exists(
        "v", FieldOptions(type="int", min=0, max=255))
    for col, val in [(3, 10), (9, 20), (700, 30)]:
        idx.field("v").set_value(col, val)
    backend, _ = _pod(holder)
    try:
        depth = idx.field("v").bsi_group("v").bit_depth()
        counts = backend.bsi_val_count("ci", "v", "sum", depth)
        full0 = backend.counters["full_refreshes"]
        counts2 = backend.bsi_val_count("ci", "v", "sum", depth)
        assert list(counts) == list(counts2)
        assert backend.counters["full_refreshes"] == full0
        assert backend.counters["resident_hits"] >= 1
    finally:
        backend.close()


def test_delete_recreate_never_aliases_resident_planes(holder):
    """Satellite: the incarnation half of the fingerprint means a
    deleted-and-recreated index whose fresh generation counters climb
    back can never alias the old index's resident planes (the hazard
    the plane-assembly comment warned about; now asserted)."""
    from pilosa_tpu.constants import SHARD_WIDTH

    idx, exp = _plant(holder, n_shards=2)
    backend, _ = _pod(holder)
    try:
        c = _call("Count(Intersect(Row(f=1), Row(f=2)))")
        old = backend.count("ci", c)
        assert old == len(exp[1] & exp[2]) and old > 0
        holder.delete_index("ci")
        idx = holder.create_index_if_not_exists("ci")
        idx.create_field_if_not_exists("f")
        # Fresh data: rows 1 and 2 share exactly one column, imported
        # with enough bits that bare generation counters climb back
        # toward cached values.
        cols1 = [1, 9, SHARD_WIDTH + 4]
        cols2 = [9, 70, SHARD_WIDTH + 8]
        idx.field("f").import_bits([1] * len(cols1), cols1)
        idx.field("f").import_bits([2] * len(cols2), cols2)
        got = backend.count("ci", _call("Count(Intersect(Row(f=1), Row(f=2)))"))
        assert got == 1, got  # the old answer would be `old`
    finally:
        backend.close()


def test_enter_refuses_epoch_divergence(holder):
    """Epoch-aware membership: a peer whose routing epoch diverges from
    the descriptor's refuses BEFORE computing (the leader's fan-out
    fallback serves the query under its own epoch gates)."""
    _plant(holder)
    backend, server = _pod(holder)
    try:
        c = _call("Count(Row(f=1))")
        desc = backend._descriptor("count", "ci", queries=[str(c)],
                                   sig=backend._call_sig("ci", c))
        desc["seq"] = 1
        desc["epoch"] = server.cluster.routing_epoch + 3  # leader is ahead
        with pytest.raises(CollectiveUnavailable, match="epoch") as ei:
            backend._enter(desc)
        assert ei.value.reason == "epoch"
        assert backend.counters["stale_epoch_refusals"] == 1
        # Topology churn must NOT advance the plane breaker.
        assert backend.health.plane_state() == "closed"
    finally:
        backend.close()


def test_enter_discards_result_when_epoch_advances_mid_execution(holder):
    """A cutover committing while planes are being assembled discards
    the collective result (post-commit GC may have read a moved shard
    as silently empty) — the leader re-runs through the fan-out."""
    _plant(holder)
    backend, server = _pod(holder)
    try:
        c = _call("Count(Row(f=1))")
        desc = backend._descriptor("count", "ci", queries=[str(c)],
                                   sig=backend._call_sig("ci", c))
        desc["seq"] = 1
        orig = backend._run_count

        def bump_then_run(*a, **kw):
            server.cluster.routing_epoch += 1
            return orig(*a, **kw)

        backend._run_count = bump_then_run
        with pytest.raises(CollectiveUnavailable, match="advanced") as ei:
            backend._enter(desc)
        assert ei.value.reason == "epoch"
        assert backend.counters["epoch_rechecks"] == 1
    finally:
        backend.close()


def test_placement_follows_committed_cutover():
    """Mid-rebalance, a committed cutover's shard routes to its NEW
    owner in the descriptor placement — the refreshed-descriptor half
    of the acceptance criterion (the stale-view halves are covered by
    ownership verification + the epoch gates)."""
    nodes = [Node(id="n0", process_idx=0), Node(id="n1", process_idx=1)]
    c = Cluster(node=nodes[0], nodes=nodes, replica_n=1, hasher=ModHasher())
    before = placement(c, "i", 4, 2)
    # n0 leaves the cluster: its shards migrate to n1; one cutover has
    # committed so far.
    moved = before[0][0]
    c.begin_rebalance([nodes[1]])
    c.apply_cutover("i", moved)
    after = placement(c, "i", 4, 2)
    assert moved in after[1] and moved not in after[0]
    # Everything else stays put mid-job (no holes).
    assert sorted(after[0] + after[1]) == list(range(4))


def test_barrier_failpoint_opens_breaker_then_recovers(holder):
    """Chaos ladder: barrier failures open the plane breaker after
    `collective-breaker-failures`; once open, queries short-circuit
    INSTANTLY (no barrier wait); after the fault clears, the half-open
    probe query re-closes the plane."""
    from pilosa_tpu import failpoints
    from pilosa_tpu.cluster.health import ResilienceConfig
    from pilosa_tpu.parallel.device_health import CollectivePlaneHealth

    _, exp = _plant(holder)
    backend, _ = _pod(holder)
    clock = [1000.0]
    backend.health = CollectivePlaneHealth(
        ResilienceConfig(collective_breaker_failures=2,
                         collective_breaker_backoff=1.0).validate(),
        clock=lambda: clock[0])
    try:
        c = _call("Count(Intersect(Row(f=1), Row(f=2)))")
        want = len(exp[1] & exp[2])
        assert backend.count("ci", c) == want
        failpoints.configure("collective-barrier", "error")
        for _ in range(2):
            with pytest.raises(CollectiveUnavailable) as ei:
                backend.count("ci", c)
            assert ei.value.reason == "barrier-timeout"
        assert backend.counters["barrier_timeouts"] == 2
        assert backend.health.plane_state() == "open"
        # Open plane: instant refusal, no barrier wait, no seq burned.
        seq_before = backend._local_seq
        with pytest.raises(CollectiveUnavailable) as ei:
            backend.count("ci", c)
        assert ei.value.reason == "breaker-open"
        assert backend._local_seq == seq_before
        assert backend.counters["breaker_short_circuits"] == 1
        # Fault clears; after the backoff the next query is the probe
        # and re-closes the plane.
        failpoints.reset()
        clock[0] += 10.0
        assert backend.count("ci", c) == want
        assert backend.health.plane_state() == "closed"
    finally:
        failpoints.reset()
        backend.close()


def test_mesh_width_never_aliases_resident_planes(holder):
    """Review regression: the resident-cache key carries the mesh width.
    n_shards=4 pads to k=4 at BOTH mesh_devices=4 and =2, so without the
    width in the key the second count would resident-hit the 4-device
    layout's array — a silently wrong device layout."""
    _, exp = _plant(holder)
    backend, _ = _pod(holder)
    try:
        c = _call("Count(Intersect(Row(f=1), Row(f=2)))")
        want = len(exp[1] & exp[2])
        backend.mesh_devices = 4
        assert backend.count("ci", c) == want
        full0 = backend.counters["full_refreshes"]
        backend.mesh_devices = 2
        assert backend.count("ci", c) == want
        assert backend.counters["full_refreshes"] > full0
    finally:
        backend.close()


def test_allow_never_orphans_plane_probe_on_blocked_slice():
    """Review regression: allow() must due-check EVERY breaker before
    claiming any probe — a plane probe claimed and then short-circuited
    by a still-backed-off slice would expire as a failure and double the
    plane backoff from short-circuits alone."""
    from pilosa_tpu.cluster.health import ResilienceConfig
    from pilosa_tpu.parallel.device_health import CollectivePlaneHealth

    clock = [0.0]
    h = CollectivePlaneHealth(
        ResilienceConfig(collective_breaker_failures=1,
                         collective_breaker_backoff=2.0).validate(),
        clock=lambda: clock[0])
    h.record_failure("runtime")  # t=0: plane opens
    clock[0] = 1.0
    h.record_failure("broadcast", [1])  # t=1: slice 1 opens
    clock[0] = 2.5  # plane due (>= 2.0), slice NOT due (>= 3.0)
    assert not h.allow([0, 1])
    assert h.plane_state() == "open"  # no wedged half-open probe
    assert h.counters["plane_probes"] == 0
    assert h.counters["slice_short_circuits"] == 1
    clock[0] = 3.5  # both due: joint probe, one entry resolves both
    assert h.allow([0, 1])
    h.record_success([0, 1])
    assert h.plane_state() == "closed"
    assert h.slice_state(1) == "closed"


def test_broadcast_failure_quarantines_slice():
    from pilosa_tpu.cluster.health import ResilienceConfig
    from pilosa_tpu.parallel.device_health import CollectivePlaneHealth

    clock = [0.0]
    h = CollectivePlaneHealth(
        ResilienceConfig(collective_breaker_failures=1,
                         collective_breaker_backoff=2.0).validate(),
        clock=lambda: clock[0])
    assert h.allow([0, 1])
    h.record_failure("broadcast", [1])
    assert h.slice_state(1) == "open"
    # Plane opened too (failures=1); both short-circuit this entry.
    assert not h.allow([0, 1])
    clock[0] += 2.5
    assert h.allow([0, 1])  # half-open probe claimed
    h.record_success([0, 1])
    assert h.slice_state(1) == "closed"
    assert h.plane_state() == "closed"


def test_executor_falls_back_cleanly_and_counts_reason(holder):
    """A refusing collective plane is a performance event, not an
    availability event: the executor serves the query through the
    fan-out and the refusal reason lands in the collective counter
    group (satellite: fallback-by-reason observability)."""
    from pilosa_tpu.executor import Executor

    _, exp = _plant(holder)
    backend, server = _pod(holder)
    ex = Executor(holder, cluster=server.cluster, workers=0)
    ex.collective = backend
    server.executor = ex
    try:
        def refuse(index, call):
            raise CollectiveUnavailable("mid-rebalance window",
                                        reason="epoch")

        backend.count = refuse
        got = ex.execute("ci", "Count(Intersect(Row(f=1), Row(f=2)))")
        assert got[0] == len(exp[1] & exp[2])
        assert backend.fallbacks == {"epoch": 1}
    finally:
        backend.close()
        ex.close()


def test_barrier_faults_cost_no_answer_through_the_executor(holder):
    """The two tests above, joined at the executor: while every barrier
    fails, each Count is answered rightly by the fan-out (two pay a
    barrier timeout, then the open plane refuses the rest at once); when
    the fault clears, the probe query is served by the plane again."""
    from pilosa_tpu import failpoints
    from pilosa_tpu.cluster.health import ResilienceConfig
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.parallel.device_health import CollectivePlaneHealth

    _, exp = _plant(holder)
    backend, server = _pod(holder)
    clock = [1000.0]
    backend.health = CollectivePlaneHealth(
        ResilienceConfig(collective_breaker_failures=2,
                         collective_breaker_backoff=1.0).validate(),
        clock=lambda: clock[0])
    ex = Executor(holder, cluster=server.cluster, workers=0)
    ex.collective = backend
    server.executor = ex
    pairs = [(a, b) for a in (1, 2, 3) for b in (1, 2, 3) if a != b]
    queries = [f"Count(Intersect(Row(f={a}), Row(f={b})))" for a, b in pairs]
    want = [len(exp[a] & exp[b]) for a, b in pairs]
    try:
        assert [ex.execute("ci", q)[0] for q in queries] == want
        served = backend.counters["served_count"]
        assert served == len(queries)
        failpoints.configure("collective-barrier", "error")
        assert [ex.execute("ci", q)[0] for q in queries] == want
        assert backend.counters["served_count"] == served
        assert backend.counters["barrier_timeouts"] == 2
        assert backend.health.snapshot()["plane_opened"] == 1
        assert backend.fallbacks == {"barrier-timeout": 2,
                                     "breaker-open": len(queries) - 2}
        failpoints.reset()
        clock[0] += 10.0
        assert ex.execute("ci", queries[0])[0] == want[0]
        assert backend.counters["served_count"] == served + 1
        assert backend.health.plane_state() == "closed"
    finally:
        failpoints.reset()
        backend.close()
        ex.close()


def test_collective_eviction_demotes_to_tier(holder):
    """Resident-stack eviction is DEMOTION: past the leaf budget, the
    LRU plane's compressed image lands in the engine's tier manager, and
    the next cold assembly promotes from it instead of walking live
    containers."""
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.tier import TierConfig

    _, exp = _plant(holder)
    # One (8, W) plane block is 1 MiB on the 8-device mesh: budget fits
    # ~2 planes, so the third leaf evicts the first.
    backend, server = _pod(holder, leaf_budget_bytes=2 * (1 << 20) + (1 << 16))
    ex = Executor(holder, cluster=server.cluster, workers=0,
                  tier_config=TierConfig(host_bytes=1 << 24))
    server.executor = ex
    assert ex.engine.tier is not None
    try:
        for row in (1, 2, 3):
            backend.count("ci", _call(f"Count(Row(f={row}))"))
        assert backend.counters["evictions"] >= 1
        ex.engine.tier.drain()
        assert backend.counters["demotions"] >= 1
        # Re-touch the evicted plane: assembled from the compressed
        # image, bit-exact.
        tp0 = backend.counters["tier_promotes"]
        assert backend.count("ci", _call("Count(Row(f=1))")) == len(exp[1])
        assert backend.counters["tier_promotes"] > tp0
    finally:
        backend.close()
        ex.close()


def test_batcher_coalesces_collective_counts(holder):
    """sched/batcher.py collective_count: concurrent same-signature
    Counts coalesce into ONE backend entry (count_batch), results split
    back bit-exact."""
    import threading

    from pilosa_tpu.sched import MicroBatcher

    _, exp = _plant(holder)
    backend, _ = _pod(holder)
    release = threading.Event()

    def wait_window(group, window):
        release.wait(timeout=10)

    b = MicroBatcher(lambda: None, window=0.001, window_max=0.05,
                     batch_max=8, depth_fn=lambda: 8,
                     wait_window=wait_window)
    try:
        c12 = _call("Count(Intersect(Row(f=1), Row(f=2)))")
        c21 = _call("Count(Intersect(Row(f=2), Row(f=1)))")
        sig = ("sig",)
        results = {}
        threads = []

        def run(i, call):
            results[i] = b.collective_count(backend, "ci", call, sig)

        for i, call in enumerate([c12, c21, c12, c21]):
            t = threading.Thread(target=run, args=(i, call))
            t.start()
            threads.append(t)
        deadline = time.time() + 5
        while b.snapshot()["enqueued"] < 4 and time.time() < deadline:
            time.sleep(0.005)
        release.set()
        for t in threads:
            t.join(timeout=10)
        want = len(exp[1] & exp[2])
        assert results == {0: want, 1: want, 2: want, 3: want}
        assert backend.counters["entries"] == 1  # ONE collective entry
        assert b.snapshot()["coalesced"] == 3
    finally:
        backend.close()


def test_runner_rejects_stale_seq():
    """A gap-skipped descriptor arriving late must be rejected, not
    executed — its barrier peers already timed out."""
    b = _StubBackend()
    r = _Runner(b)
    r.GAP_TIMEOUT = 0.2
    try:
        assert r.submit({"seq": 5}).result(timeout=10) == 50
        fut = r.submit({"seq": 3})  # late arrival from a slow broadcast
        with pytest.raises(CollectiveUnavailable, match="stale"):
            fut.result(timeout=10)
        assert b.order == [5]
    finally:
        r.close()
