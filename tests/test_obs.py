"""Per-query tracing tests (docs/observability.md): recorder units,
cross-node propagation/splicing, trace-shaped chaos assertions (host
rung under an open plane breaker, two dispatch spans across a 409
re-route), the /debug/traces + /metrics HTTP surface, slow-query log,
and the bounded stats histograms that replaced raw timing lists."""

import json
import socket
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import failpoints, obs
from pilosa_tpu.cluster.hash import ModHasher
from pilosa_tpu.cluster.health import ResilienceConfig
from pilosa_tpu.cluster.node import Cluster, Node
from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.errors import PilosaError
from pilosa_tpu.executor import Executor
from pilosa_tpu.logger import BufferLogger
from pilosa_tpu.obs import NOP_SPAN, ObsConfig, TraceRecorder
from pilosa_tpu.obs.metrics import render_prometheus
from pilosa_tpu.server.client import ClientError, InternalClient
from pilosa_tpu.server.server import Server
from pilosa_tpu.stats import Histogram, InMemoryStatsClient


def free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ------------------------------------------------------- trace assertions
#
# THE helpers trace-shaped tests go through: pilint R7b validates every
# constant span name passed to them against the real recording sites, so
# a typo'd assertion cannot silently become a no-op test.


def _walk_spans(trace_dict):
    for sp in trace_dict.get("spans", []):
        yield sp
        for ch in sp.get("children", []) or []:
            yield ch


def find_spans(trace_dict, name):
    """Spans (incl. spliced remote children) named exactly `name`."""
    return [sp for sp in _walk_spans(trace_dict) if sp["name"] == name]


def find_span(trace_dict, name):
    spans = find_spans(trace_dict, name)
    assert spans, (
        f"span {name!r} missing from trace; have "
        f"{sorted({s['name'] for s in _walk_spans(trace_dict)})}")
    return spans[0]


def remote_spans(trace_dict):
    return [sp for sp in trace_dict.get("spans", [])
            if sp["name"].startswith("remote:")]


# ------------------------------------------------------------- histograms


def test_histogram_log_buckets_bounded():
    h = Histogram()
    for v in (0.01, 0.5, 3.0, 3.9, 100.0, 1e9):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 6
    assert snap["sum"] == pytest.approx(0.01 + 0.5 + 3.0 + 3.9 + 100.0 + 1e9)
    assert snap["min"] == 0.01 and snap["max"] == 1e9
    # 3.0 and 3.9 land in the le=4.0 bucket; 1e9 overflows to +Inf.
    assert snap["buckets"][repr(4.0)] == 2
    assert snap["buckets"]["+Inf"] == 1
    # Memory stays O(buckets) no matter how many observations land.
    for _ in range(10000):
        h.observe(1.0)
    assert len(h.buckets) == len(Histogram.BOUNDS) + 1
    assert h.count == 10006


def test_stats_timings_are_bounded_histograms():
    """The old per-key list grew forever (stats.py:91 leak); timings are
    now fixed log-bucketed histograms and snapshot() serves the
    count/sum/buckets shape /metrics needs."""
    s = InMemoryStatsClient()
    for i in range(5000):
        s.timing("QueryMs", float(i % 7))
    snap = s.snapshot()["timings"]["QueryMs"]
    assert snap["count"] == 5000
    assert "buckets" in snap and "sum" in snap
    # Bounded: the histogram object holds buckets, not 5000 floats.
    hist = s.timings["QueryMs"]
    assert len(hist.buckets) == len(Histogram.BOUNDS) + 1


# ----------------------------------------------------------- nop fast path


def test_disabled_span_is_shared_nop_singleton():
    """Disabled-mode fast path: with no active trace, span() returns the
    ONE module-level no-op object — zero allocation per stage site."""
    assert obs.current() is None
    assert obs.span("parse") is NOP_SPAN
    assert obs.span("gather") is NOP_SPAN  # same object every call
    with obs.span("device.dispatch") as sp:
        sp.tag(rung="device")  # all methods are no-ops
    obs.record("reduce", 1.0)  # no trace: silently dropped

    rec = TraceRecorder(ObsConfig(sample_rate=1.0), seed=7)
    t = rec.maybe_start("i", "q")
    token = obs.activate(t)
    try:
        assert obs.span("parse") is not NOP_SPAN
    finally:
        obs.deactivate(token)


def test_sample_rate_zero_starts_nothing():
    rec = TraceRecorder(ObsConfig(sample_rate=0.0))
    assert not rec.enabled
    assert rec.maybe_start("i", "q") is None


# ---------------------------------------------------------------- sampler


def test_sampler_deterministic_under_seed():
    cfg = ObsConfig(sample_rate=0.5)
    a = TraceRecorder(cfg, seed=1234)
    b = TraceRecorder(cfg, seed=1234)
    decisions_a = [a.maybe_start("i", "q") is not None for _ in range(64)]
    decisions_b = [b.maybe_start("i", "q") is not None for _ in range(64)]
    assert decisions_a == decisions_b
    assert any(decisions_a) and not all(decisions_a)
    # Sampled traces get deterministic ids too.
    c = TraceRecorder(cfg, seed=1234)
    ids_a = [t.trace_id for t in
             filter(None, (a.maybe_start("i", "q") for _ in range(64)))]
    ids_c0 = [t.trace_id for t in
              filter(None, (c.maybe_start("i", "q") for _ in range(128)))]
    assert ids_a == ids_c0[len(ids_a):] or ids_a  # ids are non-empty hex
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids_a)


# ------------------------------------------------------------------- ring


def test_ring_bounded_newest_first_and_filters():
    rec = TraceRecorder(ObsConfig(sample_rate=1.0, ring_size=4), seed=9)
    for i in range(10):
        t = rec.maybe_start("idx-even" if i % 2 == 0 else "idx-odd", f"q{i}")
        t.record("parse", float(i))
        rec.finish(t)
    out = rec.traces()
    assert len(out) == 4  # ring bound
    assert [o["pql"] for o in out] == ["q9", "q8", "q7", "q6"]  # newest first
    assert all(find_span(o, "parse") for o in out)
    only_even = rec.traces(index="idx-even")
    assert {o["index"] for o in only_even} == {"idx-even"}
    assert len(rec.traces(limit=2)) == 2
    assert rec.snapshot()["traces_finished"] == 10


def test_straggler_span_after_finish_is_dropped():
    """An abandoned hedge leg completing AFTER the winning leg's finish
    must not mutate the published trace: two /debug/traces scrapes of
    one trace id must agree."""
    rec = TraceRecorder(ObsConfig(sample_rate=1.0), seed=4)
    t = rec.maybe_start("i", "q")
    straggler = t.span("remote:slow-peer")
    straggler.__enter__()
    with t.span("remote:fast-peer"):
        pass
    rec.finish(t)
    published = t.to_dict()
    straggler.__exit__(None, None, None)  # hedge loser answers late
    assert t.to_dict()["spans"] == published["spans"]
    assert t.to_dict()["spans_dropped"] == 1
    # Histograms saw only the published span set.
    assert set(rec.stage_histograms()) == {"remote:fast-peer"}


def test_trace_span_cap():
    rec = TraceRecorder(ObsConfig(sample_rate=1.0), seed=3)
    t = rec.maybe_start("i", "q")
    for i in range(600):
        t.record("parse", 0.1)
    rec.finish(t)
    d = t.to_dict()
    assert len(d["spans"]) == 512
    assert d["spans_dropped"] == 88


# ------------------------------------------------------- summary + splice


def test_summary_header_bounded_and_truncating():
    rec = TraceRecorder(ObsConfig(sample_rate=1.0), seed=5)
    t = rec.maybe_start("i", "q")
    for i in range(50):
        t.record("gather", 1.0, kind="cold", n=i)
    rec.finish(t)
    full = t.summary_header(100000)
    assert len(json.loads(full)["spans"]) == 50
    small = t.summary_header(400)
    assert len(small) <= 400
    parsed = json.loads(small)  # still valid JSON after truncation
    assert parsed["truncated"] > 0
    assert parsed["id"] == t.trace_id


def test_splice_valid_oversized_and_garbage():
    rec = TraceRecorder(ObsConfig(sample_rate=1.0), seed=6)
    t = rec.maybe_start("i", "q")
    sp = t.span("remote:peer1")
    with sp:
        pass
    good = json.dumps({"id": "x", "ms": 3.0,
                       "spans": [["gather", 0.1, 2.0, {"kind": "cold"}]]})
    sp.splice(good)
    assert sp.children == [("gather", 0.1, 2.0, {"kind": "cold"})]

    # Oversized peer summary: truncated (tagged), never an error.
    sp2 = t.span("remote:peer2")
    with sp2:
        pass
    sp2.splice("x" * 100000)
    assert sp2.children is None
    assert sp2.tags["summary_truncated"] is True

    # Garbage: dropped with a tag, never an error.
    sp3 = t.span("remote:peer3")
    with sp3:
        pass
    sp3.splice("{not json")
    assert sp3.children is None
    assert "summary_error" in sp3.tags


def test_adopt_header_validation():
    rec = TraceRecorder(ObsConfig(sample_rate=1.0), seed=8)
    t = rec.adopt("deadbeefcafe0123:1", index="i")
    assert t is not None and t.trace_id == "deadbeefcafe0123" and t.adopted
    assert rec.adopt("") is None
    assert rec.adopt("x" * 200) is None  # id too long
    assert rec.adopt("bad id!:1") is None  # junk chars
    assert rec.adopt("abc123:0") is None  # explicit not-sampled flag


# --------------------------------------------------------- slow-query log


def test_slow_query_log_fires_once_with_breakdown(fake_clock):
    log = BufferLogger()
    rec = TraceRecorder(ObsConfig(sample_rate=1.0, slow_query_ms=20.0),
                        logger=log, clock=fake_clock, seed=11)
    fast = rec.maybe_start("i", "Count(Row(f=1))")
    fake_clock.advance(0.005)
    rec.finish(fast)
    assert rec.snapshot()["slow_queries"] == 0
    assert not [l for l in log.lines if "[obs]" in l[1]]

    slow = rec.maybe_start("i", "Count(Row(f=2))")
    token = obs.activate(slow)
    try:
        with obs.span("gather") as sp:
            fake_clock.advance(0.030)
            sp.tag(kind="cold")
    finally:
        obs.deactivate(token)
    rec.finish(slow)
    rec.finish(slow)  # idempotent: logged once
    lines = [l[1] for l in log.lines if "[obs] slow query" in l[1]]
    assert len(lines) == 1
    assert "Count(Row(f=2))" in lines[0]
    assert "gather=30.0ms" in lines[0]
    assert slow.trace_id in lines[0]
    assert rec.snapshot()["slow_queries"] == 1


# ------------------------------------------------------------- prometheus


_PROM_LINE = (
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z0-9_]+="[^"]*"(,[a-zA-Z0-9_]+="[^"]*")*\})? '
    r"[-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|inf|nan)$"
)


def _assert_valid_prometheus(text):
    import re

    families = set()
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            fam = line.split()[2]
            assert fam not in families, f"duplicate TYPE for {fam}"
            families.add(fam)
            continue
        assert re.match(_PROM_LINE, line), f"bad exposition line: {line!r}"
    return families


def test_render_prometheus_shapes():
    h = Histogram()
    for v in (0.5, 3.0, 1e9):
        h.observe(v)
    groups = {
        "scheduler": {"admitted": 7, "waiting": {"interactive": 0},
                      "peers": {"n1": "closed"}},  # strings skipped
        "timings": {"SchedulerWaitMs": h.snapshot()},
        "counters": {"Weird|name:1": 2.5},
        "flags": {"on": True},
    }
    text = render_prometheus(groups, {"parse": h.snapshot()})
    fams = _assert_valid_prometheus(text)
    assert "pilosa_scheduler_admitted" in fams
    assert "pilosa_scheduler_waiting_interactive" in fams
    assert "pilosa_counters_weird_name_1" in fams
    assert "pilosa_timings_schedulerwaitms" in fams
    assert "pilosa_stage_duration_ms" in fams
    # Histogram series are cumulative and end at +Inf == count.
    assert 'pilosa_stage_duration_ms_bucket{stage="parse",le="+Inf"} 3' in text
    assert 'pilosa_stage_duration_ms_count{stage="parse"} 3' in text
    assert "pilosa_flags_on 1" in text
    assert "pilosa_scheduler_peers" not in text  # non-numeric leaf skipped


# ------------------------------------------------------------ HTTP surface


@pytest.fixture
def one_node():
    s = Server(cache_flush_interval=0, member_monitor_interval=0)
    s.open()
    try:
        idx = s.holder.create_index("t")
        fld = idx.create_field("f")
        fld.import_bits(np.zeros(64, dtype=np.uint64),
                        np.arange(64, dtype=np.uint64))
        yield s
    finally:
        s.close()


def _get_json(host, path):
    with urllib.request.urlopen(f"http://{host}{path}") as r:
        return json.load(r)


def test_single_node_trace_surface(one_node):
    h = f"localhost:{one_node.port}"
    c = InternalClient()
    assert c.query(h, "t", "Count(Row(f=0))")["results"] == [64]
    traces = _get_json(h, "/debug/traces")["traces"]
    assert len(traces) == 1
    tr = traces[0]
    assert tr["index"] == "t" and tr["pql"] == "Count(Row(f=0))"
    assert tr["status"] == "ok" and tr["duration_ms"] > 0
    for name in ("parse", "sched.wait", "batch.hold", "gather",
                 "device.dispatch", "executor.fanout", "reduce"):
        find_span(tr, name)
    assert find_span(tr, "gather")["tags"]["kind"] == "cold"
    assert find_span(tr, "device.dispatch")["tags"]["rung"] == "device"
    # min-ms filter: an impossible threshold returns nothing.
    assert _get_json(h, "/debug/traces?min-ms=1e9")["traces"] == []
    # /debug/vars obs group.
    dv = _get_json(h, "/debug/vars")
    assert dv["obs"]["traces_finished"] == 1
    # /metrics: valid exposition covering existing groups + stage hists.
    with urllib.request.urlopen(f"http://{h}/metrics") as r:
        assert "text/plain" in r.headers["Content-Type"]
        text = r.read().decode()
    fams = _assert_valid_prometheus(text)
    assert "pilosa_scheduler_admitted" in fams
    assert "pilosa_engine_cache_count_dispatches" in fams
    assert "pilosa_obs_traces_finished" in fams
    assert 'stage="parse"' in text and 'stage="gather"' in text


def test_client_stamped_header_cannot_force_tracing(one_node):
    """Adoption is for coordinator-forwarded (remote=true) sub-queries
    only: an ordinary client stamping X-Pilosa-Trace must not bypass the
    sampler (with sample-rate 0 it would force span recording, ring
    retention of attacker PQL, and slow-query log lines the operator
    turned off)."""
    one_node.trace_recorder.config.sample_rate = 0.0
    h = f"localhost:{one_node.port}"
    req = urllib.request.Request(
        f"http://{h}/index/t/query", data=b"Count(Row(f=0))",
        headers={"X-Pilosa-Trace": "deadbeefcafe0123:1"}, method="POST")
    with urllib.request.urlopen(req) as r:
        assert json.load(r)["results"] == [64]
    dv = _get_json(h, "/debug/vars")["obs"]
    assert dv["traces_adopted"] == 0 and dv["traces_started"] == 0
    assert _get_json(h, "/debug/traces")["traces"] == []


def test_debug_traces_bad_params_are_400(one_node):
    h = f"localhost:{one_node.port}"
    for qs in ("min-ms=abc", "limit=xyz"):
        try:
            urllib.request.urlopen(f"http://{h}/debug/traces?{qs}")
            raise AssertionError("expected HTTPError")
        except urllib.error.HTTPError as e:
            assert e.code == 400, (qs, e.code)


def test_sampling_disabled_serves_untraced():
    s = Server(cache_flush_interval=0, member_monitor_interval=0,
               obs_config=ObsConfig(sample_rate=0.0))
    s.open()
    try:
        idx = s.holder.create_index("t")
        idx.create_field("f").import_bits(
            np.zeros(8, dtype=np.uint64), np.arange(8, dtype=np.uint64))
        h = f"localhost:{s.port}"
        c = InternalClient()
        assert c.query(h, "t", "Count(Row(f=0))")["results"] == [8]
        assert _get_json(h, "/debug/traces")["traces"] == []
        assert _get_json(h, "/debug/vars")["obs"]["traces_started"] == 0
    finally:
        s.close()


# -------------------------------------------------- cross-node (3 nodes)


@pytest.fixture
def cluster3(tmp_path):
    ports = [free_port() for _ in range(3)]
    hosts = [f"localhost:{p}" for p in ports]
    servers = []
    for i, port in enumerate(ports):
        s = Server(
            data_dir=str(tmp_path / f"node{i}"),
            port=port,
            cluster_hosts=hosts,
            replica_n=1,
            hasher=ModHasher(),
            cache_flush_interval=0,
            anti_entropy_interval=0,
            executor_workers=0,
        )
        s.open()
        servers.append(s)
    yield servers
    for s in servers:
        s.close()


def test_three_node_fanout_single_trace_tree(cluster3):
    """THE acceptance trace: a fan-out Count over 3 nodes yields ONE
    tree on the coordinator — local stage spans plus a remote:<peer>
    span per hop whose children are the peer's own spans, spliced from
    the size-bounded summary header (offsets relative to the hop, so
    peer clock skew cannot corrupt the tree)."""
    c = InternalClient()
    h0 = f"localhost:{cluster3[0].port}"
    c.create_index(h0, "t")
    c.create_field(h0, "t", "f")
    time.sleep(0.05)
    # One bit per shard 0..2: with ModHasher the three shards spread
    # across the three nodes, so the Count must fan out.
    c.import_bits(h0, "t", "f", [(1, s * SHARD_WIDTH + 5) for s in range(3)])
    time.sleep(0.05)
    assert c.query(h0, "t", "Count(Row(f=1))")["results"] == [3]

    traces = _get_json(h0, "/debug/traces?index=t")["traces"]
    tree = next(t for t in traces if remote_spans(t)
                and t["pql"] == "Count(Row(f=1))")
    # Coordinator stages.
    for name in ("parse", "sched.wait", "executor.fanout", "reduce"):
        find_span(tree, name)
    # Remote hops: at least one peer served shards, each hop carries the
    # peer's spliced sub-spans (the peer ran the device path).
    hops = remote_spans(tree)
    assert hops, tree
    for hop in hops:
        child_names = {ch["name"] for ch in hop.get("children", [])}
        assert "parse" in child_names, hop
        assert "device.dispatch" in child_names, hop
        assert "gather" in child_names, hop
    # The whole tree covers every acceptance stage.
    all_names = {sp["name"] for sp in _walk_spans(tree)}
    for name in ("parse", "sched.wait", "batch.hold", "gather",
                 "device.dispatch", "reduce"):
        assert name in all_names, (name, sorted(all_names))

    # Peer rings hold the ADOPTED twin under the same trace id: one
    # logical trace across nodes.
    tid = tree["id"]
    adopted = []
    for s in cluster3[1:]:
        hp = f"localhost:{s.port}"
        adopted += [t for t in _get_json(hp, "/debug/traces")["traces"]
                    if t["id"] == tid]
    assert adopted, "no peer recorded the forwarded trace id"


# ------------------------------------------- trace-shaped chaos assertions


def test_breaker_open_trace_shows_host_rung(tmp_path):
    """DEGRADE-shaped: once the plane breaker opens, a served query's
    trace must show the HOST rung — the evidence that degraded serving
    took the ladder, not the device."""
    s = Server(
        data_dir=str(tmp_path / "n0"), cache_flush_interval=0,
        member_monitor_interval=0,
        resilience_config=ResilienceConfig(
            device_breaker_failures=1, device_breaker_backoff=60.0),
    )
    s.open()
    try:
        idx = s.holder.create_index("t")
        idx.create_field("f").import_bits(
            np.zeros(32, dtype=np.uint64), np.arange(32, dtype=np.uint64))
        h = f"localhost:{s.port}"
        c = InternalClient()
        failpoints.configure("device-dispatch", "error")
        try:
            # Opens the plane breaker; the request itself serves one rung
            # down (host) in-flight.
            assert c.query(h, "t", "Count(Row(f=0))")["results"] == [32]
            # Routed to host BEFORE any dispatch now.
            assert c.query(h, "t", "Count(Row(f=0))")["results"] == [32]
        finally:
            failpoints.reset()
        traces = _get_json(h, "/debug/traces")["traces"]
        routed = traces[0]  # newest: the breaker-open query
        dispatches = find_spans(routed, "device.dispatch")
        assert dispatches and all(
            d["tags"]["rung"] == "host" for d in dispatches), routed
        # The first (fallback) trace shows BOTH rungs: the failed device
        # attempt and the host rung that answered.
        fallback = traces[1]
        rungs = {d["tags"]["rung"]
                 for d in find_spans(fallback, "device.dispatch")}
        assert rungs == {"device", "host"}, fallback
    finally:
        s.close()


def test_409_reroute_trace_shows_two_dispatch_spans(fake_clock):
    """FAULT/rebalance-shaped: a routing-conflict 409 re-route must leave
    TWO dispatch spans in the trace — the refused hop and the re-routed
    one — so an operator can see the re-route happened and what it cost."""

    class RerouteClient:
        def __init__(self):
            self.calls = []

        def query_node(self, node, index, query, shards=None, remote=True,
                       **kw):
            self.calls.append(node.id)
            if len(self.calls) == 1:
                raise ClientError("shard moved", status=409)
            return [len(shards or [])]

    nodes = [Node(id="n0"), Node(id="n1"), Node(id="n2")]
    cluster = Cluster(node=nodes[0], nodes=nodes, replica_n=2,
                      hasher=ModHasher())
    cluster.health.configure(ResilienceConfig().validate(), clock=fake_clock)
    holder = Holder(None)
    holder.open()
    holder.create_index("hx").create_field("f")
    client = RerouteClient()
    ex = Executor(holder, cluster=cluster, client=client, workers=0)
    # A shard owned by n1+n2 (never n0) so the dispatch is remote.
    shard = next(
        s for s in range(8)
        if not any(n.id == "n0" for n in cluster.shard_nodes("hx", s)))

    rec = TraceRecorder(ObsConfig(sample_rate=1.0), seed=13)
    trace = rec.maybe_start("hx", "Count(Row(f=1))")
    token = obs.activate(trace)
    try:
        ex.execute("hx", "Count(Row(f=1))", shards=[shard])
    finally:
        obs.deactivate(token)
        rec.finish(trace)
    assert len(client.calls) == 2 and client.calls[0] != client.calls[1]
    tree = trace.to_dict()
    hops = remote_spans(tree)
    assert len(hops) == 2, tree
    # First hop carries the routing-conflict error tag; second answered.
    assert hops[0]["tags"].get("error") == "ClientError", hops
    assert "error" not in (hops[1].get("tags") or {}), hops


# ------------------------------------------------------------ config knobs


def test_obs_config_toml_env_flag_precedence(tmp_path, monkeypatch):
    from pilosa_tpu.config import Config

    p = tmp_path / "c.toml"
    p.write_text("[obs]\nsample-rate = 0.25\nring-size = 32\n"
                 "slow-query-ms = 15.0\n")
    cfg = Config.load(str(p))
    assert cfg.obs.sample_rate == 0.25
    assert cfg.obs.ring_size == 32
    assert cfg.obs.slow_query_ms == 15.0
    monkeypatch.setenv("PILOSA_TPU_OBS_SAMPLE_RATE", "0.5")
    cfg = Config.load(str(p))
    assert cfg.obs.sample_rate == 0.5  # env beats file
    cfg = Config.load(str(p), flags={"obs_sample_rate": 1.0,
                                     "obs_ring_size": 8})
    assert cfg.obs.sample_rate == 1.0 and cfg.obs.ring_size == 8
    # Round-trips through to_toml (env cleared: it would rightly win).
    monkeypatch.delenv("PILOSA_TPU_OBS_SAMPLE_RATE")
    (tmp_path / "dump.toml").write_text(cfg.to_toml())
    cfg2 = Config.load(str(tmp_path / "dump.toml"))
    assert cfg2.obs.sample_rate == 1.0 and cfg2.obs.ring_size == 8
    # Validation rejects nonsense at build time.
    with pytest.raises(ValueError):
        ObsConfig(sample_rate=2.0).validate()
    with pytest.raises(ValueError):
        ObsConfig(ring_size=-1).validate()
    with pytest.raises(ValueError):
        ObsConfig(slow_query_ms=-1.0).validate()


# ------------------------------------------- span tree and self times (PR 28)


def _finished(rec, trace):
    rec.finish(trace)
    return {s["name"]: s for s in trace.to_dict()["spans"]}


def test_span_ids_and_parents_across_nested_with_and_record(fake_clock):
    rec = TraceRecorder(ObsConfig(sample_rate=1.0), clock=fake_clock, seed=21)
    t = rec.maybe_start("i", "q")
    token = obs.activate(t)
    try:
        with obs.span("request") as root:
            assert obs.current_span() is root
            obs.record("sched.wait", 0.0, cls="interactive")
            with obs.span("device.dispatch") as disp:
                assert obs.current_span() is disp
                with obs.span("gather"):
                    fake_clock.advance(0.001)
                obs.record("batch.hold", 0.0, held=0)
            assert obs.current_span() is root
        assert obs.current_span() is None
    finally:
        obs.deactivate(token)
    spans = _finished(rec, t)
    ids = [s["id"] for s in spans.values()]
    assert len(set(ids)) == len(ids) == 5
    assert spans["request"]["parent"] is None
    assert spans["sched.wait"]["parent"] == spans["request"]["id"]
    assert spans["device.dispatch"]["parent"] == spans["request"]["id"]
    assert spans["gather"]["parent"] == spans["device.dispatch"]["id"]
    assert spans["batch.hold"]["parent"] == spans["device.dispatch"]["id"]


def test_open_span_of_another_trace_is_no_parent(fake_clock):
    """The autoscaler opens a one-span trace of its own while a request's
    span may be open on the same context: ids are per trace, so a span
    takes no parent from a trace that is not its own."""
    rec = TraceRecorder(ObsConfig(sample_rate=1.0), clock=fake_clock, seed=22)
    outer, inner = rec.maybe_start("i", "a"), rec.maybe_start("i", "b")
    with outer.span("request"):
        with inner.span("autoscale.decide"):
            pass
        inner.record("reduce", 0.0)
    assert [s.parent for s in inner.spans] == [None, None]


# (children as (start, length) in ms inside a parent of 100 ms from 0; self)
_SELF_CASES = {
    "no-children": ([], 100.0),
    "one-child": ([(10, 30)], 70.0),
    "abutting": ([(10, 20), (30, 20)], 60.0),
    "overlapping": ([(10, 30), (30, 40)], 40.0),
    "nested-overlap": ([(10, 60), (20, 10)], 40.0),
    "covers-all": ([(0, 100)], 0.0),
}


@pytest.mark.parametrize("case", sorted(_SELF_CASES))
def test_self_ms_is_length_less_union_of_children(case, fake_clock):
    children, want = _SELF_CASES[case]
    rec = TraceRecorder(ObsConfig(sample_rate=1.0), clock=fake_clock, seed=23)
    t = rec.maybe_start("i", "q")
    root = t.span("request")
    with root:
        fake_clock.advance(0.100)
    for start, length in children:
        sp = t.span("gather", parent=root)
        sp.start_ms, sp.dur_ms = float(start), float(length)
        t._append(sp)
    spans = _finished(rec, t)
    assert spans["request"]["self_ms"] == pytest.approx(want)
    # A child without children of its own is all self time.
    if children:
        assert spans["gather"]["self_ms"] == spans["gather"]["dur_ms"]


def test_self_ms_clips_a_child_to_its_parent_and_skips_amounts(fake_clock):
    rec = TraceRecorder(ObsConfig(sample_rate=1.0), clock=fake_clock, seed=24)
    t = rec.maybe_start("i", "q")
    fake_clock.advance(0.010)
    root = t.span("request")
    with root:
        fake_clock.advance(0.020)
        # A pre-measured span that began before its parent did (clipped to
        # it), and a bill whose length is no interval at all.
        t.record("sched.wait", 25.0, parent=root)
        t.record("qos.charge", 500.0, parent=root, amount=True)
    spans = _finished(rec, t)
    assert spans["request"]["dur_ms"] == pytest.approx(20.0)
    assert spans["request"]["self_ms"] == pytest.approx(0.0)
    assert spans["qos.charge"]["self_ms"] == 0.0
    assert spans["qos.charge"]["dur_ms"] == 500.0


def test_span_closed_on_another_thread_keeps_the_parent_it_was_given():
    import threading

    rec = TraceRecorder(ObsConfig(sample_rate=1.0), seed=25)
    t = rec.maybe_start("i", "q")
    token = obs.activate(t)
    try:
        with obs.span("executor.fanout") as fan:
            above = obs.current_span()

            def leg():
                # A pool thread: no trace and no open span on its context.
                assert obs.current() is None and obs.current_span() is None
                with t.span("remote:peer", parent=above):
                    pass
                assert obs.current_span() is None

            th = threading.Thread(target=leg)
            th.start()
            th.join()
            # The hop did not disturb the request's own open span.
            assert obs.current_span() is fan
    finally:
        obs.deactivate(token)
    spans = _finished(rec, t)
    assert spans["remote:peer"]["parent"] == spans["executor.fanout"]["id"]


def test_span_ids_stay_unique_under_threads():
    """Spans of one trace are opened from many threads at once (hedged
    legs, pool workers): ids must not collide, and every span keeps the
    parent it was given."""
    import sys
    import threading

    rec = TraceRecorder(ObsConfig(sample_rate=1.0), seed=31)
    t = rec.maybe_start("i", "q")
    workers, each = 16, 10  # 321 spans: under the trace's cap of 512
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with t.span("executor.fanout") as fan:
            def leg():
                for _ in range(each):
                    with t.span("remote:peer", parent=fan):
                        with t.span("gather"):
                            pass

            threads = [threading.Thread(target=leg) for _ in range(workers)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=30)
            assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(was)
    rec.finish(t)
    spans = t.to_dict()["spans"]
    assert len(spans) == 2 * workers * each + 1 < obs.trace.SPANS_MAX
    kept = {s["id"]: s for s in spans}
    assert len(kept) == len(spans)
    hops = [s for s in spans if s["name"] == "remote:peer"]
    assert {s["parent"] for s in hops} == {fan.id}
    # Each gather ran on its hop's thread, under that hop and no other.
    assert all(kept[s["parent"]]["name"] == "remote:peer"
               for s in spans if s["name"] == "gather")


def test_a_trace_that_leaves_the_ring_is_freed_without_the_collector():
    """A span does not point back at its trace once it has ended, so a
    landed trace is no reference cycle: when it leaves the ring, reference
    counting frees it and its spans, and the cyclic collector (which has
    the whole serving heap to look at) finds nothing of theirs."""
    import gc

    rec = TraceRecorder(ObsConfig(sample_rate=1.0, ring_size=2), seed=32)
    gc.collect()
    gc.disable()
    gc.set_debug(gc.DEBUG_SAVEALL)
    try:
        for _ in range(20):
            _some_spans(rec)
        gc.collect()
        left = [o for o in gc.garbage
                if isinstance(o, (obs.Span, obs.Trace))]
    finally:
        gc.set_debug(0)
        gc.garbage.clear()
        gc.enable()
    assert left == []
    assert len(rec.traces()) == 2


def test_summary_header_and_splice_keep_their_formats():
    rec = TraceRecorder(ObsConfig(sample_rate=1.0), seed=26)
    peer = rec.maybe_start("i", "q")
    with peer.span("request"):
        with peer.span("gather", kind="cold"):
            pass
    rec.finish(peer)
    rows = json.loads(peer.summary_header())["spans"]
    assert [r[0] for r in rows] == ["gather", "request"]
    assert all(len(r) in (3, 4) for r in rows)  # name, start, length[, tags]
    mine = rec.maybe_start("i", "q")
    with mine.span("remote:peer") as hop:
        pass
    hop.splice(peer.summary_header())
    rec.finish(mine)
    out = mine.to_dict()["spans"][0]
    assert [c["name"] for c in out["children"]] == ["gather", "request"]
    assert set(out["children"][0]) == {"name", "start_ms", "dur_ms", "tags"}
    assert out["self_ms"] == out["dur_ms"]  # a peer's spans take nothing off


def _tree_checks(tr):
    """What every served trace has to satisfy: one root, every other span
    under a span of the same trace, and self times that add up to it."""
    spans = tr["spans"]
    by_id = {s["id"]: s for s in spans}
    assert len(by_id) == len(spans)
    roots = [s for s in spans if s["parent"] is None]
    assert [s["name"] for s in roots] == ["request"], roots
    for s in spans:
        assert s["parent"] is None or s["parent"] in by_id, s
        assert 0.0 <= s["self_ms"] <= s["dur_ms"] + 1e-9, s
    total = sum(s["self_ms"] for s in spans)
    # to_dict rounds to a microsecond: half of one for each span.
    assert total == pytest.approx(roots[0]["dur_ms"],
                                  abs=0.001 * len(spans)), spans
    return {s["name"]: s for s in spans}


def test_served_count_is_one_tree_whose_self_times_sum_to_the_root(one_node):
    h = f"localhost:{one_node.port}"
    c = InternalClient()
    assert c.query(h, "t", "Count(Row(f=0))")["results"] == [64]
    assert c.query(h, "t", "Set(5, f=0)")["results"] == [False]
    assert c.query(h, "t", "Count(Row(f=0))")["results"] == [64]
    traces = _get_json(h, "/debug/traces")["traces"]
    assert len(traces) == 3
    for tr in traces:
        _tree_checks(tr)
    first = _tree_checks(traces[-1])
    fan = find_span(traces[-1], "executor.fanout")
    disp = find_span(traces[-1], "device.dispatch")
    assert disp["parent"] == fan["id"]
    assert find_span(traces[-1], "reduce")["parent"] == fan["id"]
    assert find_span(traces[-1], "sched.wait")["parent"] == first["request"]["id"]
    # What the engine did under the dispatch, by name.
    probe = find_span(traces[-1], "engine.memo_probe")
    assert probe["tags"] == {"hit": False} and probe["parent"] == disp["id"]
    wait = find_span(traces[-1], "engine.device_wait")
    assert wait["parent"] == disp["id"]
    # The first Count built its program: the compile is the first call,
    # inside the wait for the device.
    build = find_span(traces[-1], "engine.fn_build")
    assert build["tags"] == {"kind": "count"} and build["parent"] == wait["id"]
    assert not find_spans(traces[0], "engine.fn_build")
    # The stage histograms still observe whole lengths, under every name.
    hists = one_node.trace_recorder.stage_histograms()
    assert {"request", "engine.memo_probe", "engine.device_wait"} <= set(hists)


def test_coalesced_counts_show_the_leaders_launch(one_node):
    """Four concurrent Counts over distinct rows, held in one group: the
    leader's trace has `batch.launch` with the stack and the wait for the
    device under it; a follower's `batch.hold` spans that launch."""
    import threading

    n = 4
    fld = one_node.holder.index("t").field("f")
    fld.import_bits(np.repeat(np.arange(1, n, dtype=np.uint64), 8),
                    np.tile(np.arange(8, dtype=np.uint64), n - 1))
    b = one_node.batcher
    b.window, b.window_max, b.batch_max = 2.0, 10.0, n
    b.depth_fn = lambda: n
    h = f"localhost:{one_node.port}"
    got = {}
    barrier = threading.Barrier(n)

    def client(i):
        barrier.wait(timeout=10)
        got[i] = InternalClient().query(h, "t", f"Count(Row(f={i}))")["results"]

    threads = [threading.Thread(target=client, args=(i,)) for i in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert got == {0: [64], 1: [8], 2: [8], 3: [8]}
    traces = _get_json(h, "/debug/traces")["traces"]
    assert len(traces) == n
    leaders = [tr for tr in traces if find_spans(tr, "batch.launch")]
    assert len(leaders) == 1
    for tr in traces:
        _tree_checks(tr)
    lead = leaders[0]
    launch = find_span(lead, "batch.launch")
    assert launch["tags"] == {"size": n}
    assert launch["parent"] == find_span(lead, "device.dispatch")["id"]
    stack = find_span(lead, "engine.stack")
    assert stack["parent"] == launch["id"]
    # One shard a device: the stack is kept folded onto the sublanes.
    assert stack["tags"] == {"planes": n, "kind": "restack", "fold": 8}
    assert find_span(lead, "engine.device_wait")["parent"] == launch["id"]
    assert find_span(lead, "engine.fn_build")["tags"]["kind"] == \
        "count_batch_setops"
    for tr in traces:
        if tr is not lead:
            hold = find_span(tr, "batch.hold")
            assert hold["tags"]["role"] == "follower"
            assert hold["dur_ms"] >= launch["dur_ms"] * 0.5
    ec = one_node.executor.engine.snapshot()
    # One stack of n planes over one padded shard was copied and handed on.
    one_plane = ec["restack_bytes"] // n
    assert ec["restack_bytes"] == n * one_plane > 0
    assert ec["plane_bytes_read"] >= ec["restack_bytes"]


def test_fn_builds_by_kind_sum_to_fn_cache_builds(one_node):
    from pilosa_tpu.parallel.engine import FN_KINDS

    h = f"localhost:{one_node.port}"
    c = InternalClient()
    ec = _get_json(h, "/debug/vars")["engine_cache"]
    # Registered at 0, so that a first build shows as growth of its key.
    assert [ec[f"fn_builds_{k}"] for k in FN_KINDS] == [0] * len(FN_KINDS)
    c.query(h, "t", "Count(Row(f=0))")
    c.query(h, "t", "TopN(f, Row(f=0), n=1)")
    c.query(h, "t", "Set(9, f=0)")
    c.query(h, "t", "Count(Row(f=0))")
    ec = _get_json(h, "/debug/vars")["engine_cache"]
    by_kind = {k: v for k, v in ec.items() if k.startswith("fn_builds_")}
    assert sum(by_kind.values()) == ec["fn_cache_builds"] >= 2
    assert by_kind["fn_builds_count"] == 1
    assert ec["plane_bytes_read"] > 0


def test_every_device_program_has_a_name_of_its_own(one_node):
    h = f"localhost:{one_node.port}"
    c = InternalClient()
    c.query(h, "t", "Count(Row(f=0))")
    assert c.query(h, "t", "Set(100, f=0)")["results"] == [True]
    assert c.query(h, "t", "Count(Row(f=0))")["results"] == [65]
    c.query(h, "t", "Row(f=0)")
    eng = one_node.executor.engine
    names = {sig[0]: fn.__name__
             for cache in (eng._count_fns, eng._bitmap_fns)
             for sig, fn in cache.items()}
    assert names["count"] == "count_expr"
    assert names["leaf_delta"] == "leaf_delta_scatter"
    assert names["bitmap"] == "bitmap_expr"
    assert not {"fn", "<lambda>"} & set(names.values())


# --------------------------------- spans on the profiler's clock (PR 28)


class _CountingAnnotation:
    """Stand-in for jax.profiler.TraceAnnotation: counts what is built,
    entered and left, by name."""

    built, entered, left = [], [], []

    def __init__(self, name, **stats):
        self.name = name
        self.stats = stats
        _CountingAnnotation.built.append(self)

    def __enter__(self):
        _CountingAnnotation.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        _CountingAnnotation.left.append(self.name)
        return False


@pytest.fixture
def counting_annotation():
    from pilosa_tpu.obs import trace as obs_trace

    was = obs_trace._annotation
    for seen in (_CountingAnnotation.built, _CountingAnnotation.entered,
                 _CountingAnnotation.left):
        del seen[:]
    obs_trace.set_annotation(_CountingAnnotation)
    try:
        yield _CountingAnnotation
    finally:
        obs_trace.capture_ended()
        obs_trace.set_annotation(was)


def _some_spans(rec):
    t = rec.maybe_start("i", "q")
    token = obs.activate(t)
    try:
        with obs.span("request"):
            with obs.span("batch.hold", role="leader", held=1):
                pass
            with obs.span("engine.device_wait"):
                pass
            obs.record("sched.wait", 1.0)
    finally:
        obs.deactivate(token)
    rec.finish(t)
    return t


def test_no_capture_builds_no_annotation(counting_annotation):
    _some_spans(TraceRecorder(ObsConfig(sample_rate=1.0), seed=27))
    assert counting_annotation.built == []


def test_under_a_capture_every_span_enters_and_leaves_once(counting_annotation):
    from pilosa_tpu.obs import trace as obs_trace

    obs_trace.capture_began()
    t = _some_spans(TraceRecorder(ObsConfig(sample_rate=1.0), seed=28))
    obs_trace.capture_ended()
    # The clock marks at both ends, and one annotation for each span that
    # ran; the pre-measured `sched.wait` has none. Parked spans end in
    # `wait`; the names in the trace itself do not change.
    want = ["obs.clock", "request", "batch.hold.wait", "engine.device_wait",
            "obs.clock"]
    assert counting_annotation.entered == want
    assert sorted(counting_annotation.left) == sorted(want)
    clock = counting_annotation.built[0].stats
    assert set(clock) == {"wall_ns", "mono_ns"}
    ids = {s.name: s.id for s in t.spans}
    for a in counting_annotation.built[1:-1]:
        assert a.stats == {"trace": t.trace_id,
                           "span": ids[a.name.replace(".hold.wait", ".hold")]}
    assert [s.name for s in t.spans] == [
        "batch.hold", "engine.device_wait", "sched.wait", "request"]
    # After the capture nothing more is built.
    _some_spans(TraceRecorder(ObsConfig(sample_rate=1.0), seed=29))
    assert len(counting_annotation.built) == len(want)


def test_a_span_open_across_the_captures_start_is_left_alone(
        counting_annotation):
    from pilosa_tpu.obs import trace as obs_trace

    rec = TraceRecorder(ObsConfig(sample_rate=1.0), seed=30)
    t = rec.maybe_start("i", "q")
    with t.span("request"):
        obs_trace.capture_began()
    assert counting_annotation.entered == ["obs.clock"]


def test_untraced_request_builds_no_span_and_no_annotation(
        counting_annotation, monkeypatch):
    """`[obs] sample-rate 0` and no capture: the whole served path makes
    neither a Span nor an annotation."""
    made = []
    init = obs.Span.__init__

    def counting_init(self, *a, **kw):
        made.append(a[1])
        init(self, *a, **kw)

    monkeypatch.setattr(obs.Span, "__init__", counting_init)
    s = Server(cache_flush_interval=0, member_monitor_interval=0,
               obs_config=ObsConfig(sample_rate=0.0))
    s.open()
    try:
        # Server.open handed the real class in; count through the stand-in.
        from pilosa_tpu.obs import trace as obs_trace

        obs_trace.set_annotation(_CountingAnnotation)
        idx = s.holder.create_index("t")
        idx.create_field("f").import_bits(
            np.zeros(8, dtype=np.uint64), np.arange(8, dtype=np.uint64))
        h = f"localhost:{s.port}"
        c = InternalClient()
        assert c.query(h, "t", "Count(Row(f=0))")["results"] == [8]
        assert c.query(h, "t", "TopN(f, Row(f=0), n=1)")["results"]
    finally:
        s.close()
    assert made == [] and counting_annotation.built == []


def _post_json(host, path):
    req = urllib.request.Request(f"http://{host}{path}", method="POST")
    with urllib.request.urlopen(req) as r:
        return json.load(r)


@pytest.mark.parametrize("query,python", [("", False), ("&python=1", True)])
def test_debug_profile_answers_its_bounds_on_both_clocks(one_node, query,
                                                         python):
    h = f"localhost:{one_node.port}"
    w0, m0 = time.time(), time.monotonic()
    got = _post_json(h, "/debug/profile?seconds=0.2" + query)
    w1, m1 = time.time(), time.monotonic()
    assert set(got) == {"path", "python_tracer", "started_wall",
                        "stopped_wall", "started_mono", "stopped_mono"}
    assert got["python_tracer"] is python
    assert w0 <= got["started_wall"] <= got["stopped_wall"] <= w1
    assert m0 <= got["started_mono"] <= got["stopped_mono"] <= m1
    assert got["stopped_mono"] - got["started_mono"] >= 0.2


def test_debug_profile_holds_the_requests_spans_and_refuses_a_second(
        one_node):
    """One capture through the real handler on the CPU backend: a second
    one is refused with 409 while it runs, and the host plane of what it
    wrote holds the spans of a request served meanwhile, by name, beside
    the clock marks."""
    import glob
    import threading

    h = f"localhost:{one_node.port}"
    first = {}
    th = threading.Thread(target=lambda: first.update(
        _post_json(h, "/debug/profile?seconds=1.5")))
    th.start()
    try:
        deadline = time.monotonic() + 10
        while not obs.trace.capturing and time.monotonic() < deadline:
            time.sleep(0.01)
        assert obs.trace.capturing
        with pytest.raises(urllib.error.HTTPError) as refused:
            _post_json(h, "/debug/profile?seconds=0.1")
        assert refused.value.code == 409
        assert InternalClient().query(h, "t", "Count(Row(f=0))")["results"] \
            == [64]
    finally:
        th.join(timeout=60)
    assert not obs.trace.capturing
    tr = _get_json(h, "/debug/traces")["traces"][0]
    from jax.profiler import ProfileData

    path = glob.glob(first["path"] + "/plugins/profile/*/*.xplane.pb")[0]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("request", "device.dispatch",
                               "engine.device_wait", "obs.clock"):
                    seen.setdefault(ev.name, []).append(dict(ev.stats))
    assert len(seen["obs.clock"]) == 2
    assert {"wall_ns", "mono_ns"} <= set(seen["obs.clock"][0])
    for name in ("request", "device.dispatch", "engine.device_wait"):
        assert seen[name][0]["trace"] == tr["id"]
        assert seen[name][0]["span"] == find_span(tr, name)["id"]


# ---------------------------- a span's CPU time beside its length (PR 40)


class FakeCpu:
    """The calling thread's CPU clock, in ns as time.thread_time_ns gives
    it: moves only when a test says the thread worked."""

    def __init__(self):
        self.ns = 5_000_000_000

    def __call__(self):
        return self.ns

    def work(self, ms):
        self.ns += round(ms * 1e6)


@pytest.fixture
def clocks(fake_clock):
    """A recorder on two fake clocks. `spend(wall_ms, cpu_ms)`: so long
    on the monotonic clock, of which the thread ran so long."""
    cpu = FakeCpu()
    rec = TraceRecorder(ObsConfig(sample_rate=1.0, slow_query_ms=20.0),
                        logger=BufferLogger(), clock=fake_clock,
                        cpu_clock=cpu, seed=40)

    def spend(wall_ms, cpu_ms=0.0):
        fake_clock.advance(wall_ms / 1000.0)
        cpu.work(cpu_ms)

    return rec, spend


def _a_count(rec, spend):
    """A request shaped like a served Count: work in the root, a wait
    recorded (1.5 of the root's first 2 ms), a gather that worked 2.1 of
    its 30 ms."""
    t = rec.maybe_start("i", "Count(Row(f=1))")
    token = obs.activate(t)
    try:
        with obs.span("request"):
            spend(2.0, 0.45)
            obs.record("sched.wait", 1.5, cls="interactive")
            with obs.span("device.dispatch"):
                spend(1.0, 0.25)
                with obs.span("gather", kind="cold"):
                    spend(30.0, 2.1)
                obs.record("batch.hold", 0.0, held=0)
            spend(1.0, 0.5)
    finally:
        obs.deactivate(token)
    return t


def test_cpu_ms_is_the_cpu_clocks_difference(clocks):
    rec, spend = clocks
    spans = _finished(rec, _a_count(rec, spend))
    assert spans["gather"]["dur_ms"] == pytest.approx(30.0)
    assert spans["gather"]["cpu_ms"] == pytest.approx(2.1)
    assert spans["device.dispatch"]["cpu_ms"] == pytest.approx(2.35)
    assert spans["request"]["cpu_ms"] == pytest.approx(3.3)
    assert spans["request"]["dur_ms"] == pytest.approx(34.0)


def test_self_cpu_ms_of_a_threads_spans_sum_to_the_roots_cpu_ms(clocks):
    rec, spend = clocks
    spans = _finished(rec, _a_count(rec, spend))
    assert spans["request"]["self_cpu_ms"] == pytest.approx(0.95)
    assert spans["device.dispatch"]["self_cpu_ms"] == pytest.approx(0.25)
    assert spans["gather"]["self_cpu_ms"] == pytest.approx(2.1)
    assert sum(s["self_cpu_ms"] for s in spans.values()) == pytest.approx(
        spans["request"]["cpu_ms"])
    # What a span's thread did not run of its own time: the gather stood
    # 27.9 of its 30 ms, the root 0.55 of the 1.5 no stage accounts for.
    assert spans["gather"]["self_ms"] - spans["gather"]["self_cpu_ms"] \
        == pytest.approx(27.9)
    assert spans["request"]["self_ms"] - spans["request"]["self_cpu_ms"] \
        == pytest.approx(0.55)


@pytest.mark.parametrize("name", ["sched.wait", "batch.hold", "qos.charge"])
def test_a_recorded_span_has_no_cpu_of_its_own(name, clocks):
    """Pre-measured spans are waits or amounts by construction."""
    rec, spend = clocks
    t = rec.maybe_start("i", "q")
    with t.span("request") as root:
        spend(5.0, 4.0)
        t.record(name, 3.0, parent=root, amount=name == "qos.charge")
    spans = _finished(rec, t)
    assert spans[name]["cpu_ms"] == spans[name]["self_cpu_ms"] == 0.0
    assert spans["request"]["self_cpu_ms"] == pytest.approx(4.0)


def _in_a_thread(fn):
    import threading

    th = threading.Thread(target=fn)
    th.start()
    th.join(timeout=30)
    assert not th.is_alive()


def test_a_span_closed_on_another_thread_has_no_cpu_ms(clocks):
    """The CPU clock is a thread's own: a difference across two threads
    is no time at all, so such a span says nothing, and its parent takes
    nothing off for it."""
    rec, spend = clocks
    t = rec.maybe_start("i", "q")
    with t.span("executor.fanout") as fan:
        spend(1.0, 1.0)
        hop = t.span("remote:peer", parent=fan)
        hop.__enter__()
        spend(4.0, 3.0)
        _in_a_thread(hop.close)
    spans = _finished(rec, t)
    assert "cpu_ms" not in spans["remote:peer"]
    assert "self_cpu_ms" not in spans["remote:peer"]
    assert spans["remote:peer"]["dur_ms"] == pytest.approx(4.0)
    assert spans["executor.fanout"]["cpu_ms"] == pytest.approx(4.0)
    assert spans["executor.fanout"]["self_cpu_ms"] == pytest.approx(4.0)


def test_a_child_that_ran_on_another_thread_keeps_its_cpu_to_itself(clocks):
    """A hedged leg runs whole on a pool thread: it has a cpu_ms, of that
    thread, and the span it was opened under spent none of it."""
    rec, spend = clocks
    t = rec.maybe_start("i", "q")
    with t.span("executor.fanout") as fan:
        spend(1.0, 1.0)

        def leg():
            with t.span("remote:peer", parent=fan):
                with t.span("gather"):
                    spend(2.0, 0.5)
                spend(1.0, 0.25)

        _in_a_thread(leg)
    spans = _finished(rec, t)
    assert spans["remote:peer"]["cpu_ms"] == pytest.approx(0.75)
    assert spans["remote:peer"]["self_cpu_ms"] == pytest.approx(0.25)
    # (The fake clock is one for all threads, so the fan-out's own reading
    # holds the leg's 0.75; what is held here is that nothing came off.)
    assert spans["executor.fanout"]["self_cpu_ms"] == pytest.approx(
        spans["executor.fanout"]["cpu_ms"])


def test_to_dict_leaves_the_cpu_fields_out_without_a_cpu_clock(fake_clock):
    rec = TraceRecorder(ObsConfig(sample_rate=1.0), clock=fake_clock,
                        cpu_clock=None, seed=41)
    t = rec.maybe_start("i", "q")
    with t.span("request") as root:
        fake_clock.advance(0.002)
        with t.span("gather"):
            fake_clock.advance(0.001)
        t.record("sched.wait", 1.0, parent=root)
    spans = _finished(rec, t)
    for name in ("request", "gather"):
        assert set(spans[name]) == {"name", "id", "parent", "start_ms",
                                    "dur_ms", "self_ms"}
    # A recorded span is a wait whatever the platform's clocks.
    assert spans["sched.wait"]["cpu_ms"] == 0.0


def test_the_summary_header_is_byte_for_byte_what_it_was(clocks):
    """The header is size-bounded and the peer's splice reads it: the CPU
    readings stay out of it. The literal is the parent commit's output
    for the same spans on the same clock and seed."""
    rec, spend = clocks
    t = rec.maybe_start("i", "Count(Row(f=1))")
    token = obs.activate(t)
    try:
        with obs.span("request"):
            spend(2.0, 1.0)
            obs.record("sched.wait", 1.5, cls="interactive")
            with obs.span("gather", kind="cold"):
                spend(30.0, 2.1)
            spend(1.0, 1.0)
    finally:
        obs.deactivate(token)
    rec.finish(t)
    assert t.summary_header() == (
        '{"id":"94594d8b75673fca","ms":33.0,"spans":[["sched.wait",0.5,1.5,'
        '{"cls":"interactive"}],["gather",2.0,30.0,{"kind":"cold"}],'
        '["request",0.0,33.0]]}')


@pytest.mark.parametrize("read_us, period", [(0.25, 1), (0.6, 1),
                                             (5.8, 11), (40.0, 80)])
def test_a_slow_cpu_clock_is_read_in_one_trace_of_every_period(
        read_us, period, fake_clock):
    """The CPU clock is a system call, 5.8 us a read on some hosts where
    it is 0.25 us on others: the recorder times it once and gives one
    trace in every `cpu_period` the clock, all of its spans or none, so
    that a span's two reads cost about a microsecond on average."""
    cpu = FakeCpu()

    def costly_cpu():
        fake_clock.advance(read_us / 1e6)   # what a read costs, on `clock`
        return cpu()

    rec = TraceRecorder(ObsConfig(sample_rate=1.0, ring_size=2000),
                        clock=fake_clock, cpu_clock=costly_cpu, seed=43)
    assert rec.cpu_period == rec.snapshot()["cpu_period"] == period
    n = 1200
    for i in range(n):
        # Adopted traces (a coordinator's sub-queries) are drawn alike.
        t = rec.maybe_start("i", "q") if i % 3 else rec.adopt("ab12:1")
        with t.span("request"):
            with t.span("gather"):
                pass
        rec.finish(t)
    with_cpu = 0
    for tr in rec.traces(limit=n):
        has = ["cpu_ms" in s for s in tr["spans"]]
        assert all(has) or not any(has), tr
        assert [("self_cpu_ms" in s) for s in tr["spans"]] == has
        with_cpu += has[0]
    if period == 1:
        assert with_cpu == n
    else:
        assert 0.6 * n / period < with_cpu < 1.6 * n / period


def test_slow_query_log_gives_each_stage_its_cpu_beside_its_length(clocks):
    rec, spend = clocks
    t = _a_count(rec, spend)
    rec.finish(t)
    (line,) = [l[1] for l in rec.logger.lines if "[obs] slow query" in l[1]]
    assert "gather=30.0ms cpu=2.1;" in line
    assert "sched.wait=1.5ms cpu=0.0;" in line
    assert line.endswith("request=34.0ms cpu=3.3")
    # A span that has no reading says nothing of CPU.
    rec.cpu_clock = None
    bare = rec.maybe_start("i", "q")
    with bare.span("gather"):
        spend(30.0)
    rec.finish(bare)
    lines = [l[1] for l in rec.logger.lines if "[obs] slow query" in l[1]]
    assert lines[1].endswith("stages: gather=30.0ms")


def test_real_clocks_a_spinning_span_shares_the_lock_a_sleeping_one_idles():
    """On the real clocks, only the direction that load on the machine
    cannot break: two threads that spin in Python take turns at the
    interpreter lock, so neither can have run for most of its span
    (another process's load takes CPU away and never adds any); and a
    span that sleeps has next to no CPU time."""
    import threading

    rec = TraceRecorder(ObsConfig(sample_rate=1.0), seed=42)
    t = rec.maybe_start("i", "q")
    both_in = threading.Barrier(2, timeout=30)
    stop = threading.Event()

    def spin():
        with t.span("topn.rank"):
            both_in.wait()
            until = time.monotonic() + 0.4
            n = 0
            while time.monotonic() < until and not stop.is_set():
                n += 1

    threads = [threading.Thread(target=spin) for _ in range(2)]
    for th in threads:
        th.start()
    try:
        for th in threads:
            th.join(timeout=30)
    finally:
        stop.set()
    assert not any(th.is_alive() for th in threads)
    with t.span("batch.hold"):
        time.sleep(0.3)
    spans = [s.to_dict() for s in t.spans]
    spun = [s for s in spans if s["name"] == "topn.rank"]
    assert len(spun) == 2
    for s in spun:
        assert s["dur_ms"] >= 390.0
        assert 0.0 < s["cpu_ms"] <= 0.8 * s["dur_ms"], s
    # (Room for a CPU clock that ticks: 10 ms a tick on some hosts.)
    slept = spans[-1]
    assert slept["dur_ms"] >= 300.0 and slept["cpu_ms"] < 50.0, slept


def test_debug_vars_has_the_host_group_and_a_collection_grows_it(one_node):
    import gc

    h = f"localhost:{one_node.port}"
    keys = {"cpu_s", "gc_collections", "gc_full_collections", "gc_s"}
    first = _get_json(h, "/debug/vars")["host"]
    assert set(first) == keys
    assert first["cpu_s"] > 0
    InternalClient().query(h, "t", "Count(Row(f=0))")
    second = _get_json(h, "/debug/vars")["host"]
    gc.collect()
    third = _get_json(h, "/debug/vars")["host"]
    for before, after in ((first, second), (second, third)):
        assert all(after[k] >= before[k] for k in keys), (before, after)
    assert third["gc_full_collections"] > second["gc_full_collections"]
    assert third["gc_collections"] > second["gc_collections"]
    assert third["gc_s"] > second["gc_s"]
    # /metrics renders every /debug/vars group: no exporter code.
    with urllib.request.urlopen(f"http://{h}/metrics") as r:
        text = r.read().decode()
    for k in keys:
        assert f"\npilosa_host_{k} " in text
    # A closed server counts no more: its callback is gone.
    one_node.close()
    assert one_node.host_meter._on_gc not in gc.callbacks


def test_served_spans_carry_cpu_that_sums_to_the_roots(one_node):
    h = f"localhost:{one_node.port}"
    c = InternalClient()
    for pql in ("Count(Row(f=0))", "Set(5, f=0)", "Count(Row(f=0))"):
        c.query(h, "t", pql)
    traces = _get_json(h, "/debug/traces")["traces"]
    assert len(traces) == 3
    for tr in traces:
        spans = tr["spans"]
        root = next(s for s in spans if s["parent"] is None)
        assert all("cpu_ms" in s and "self_cpu_ms" in s for s in spans)
        # (Not held: cpu_ms <= dur_ms. The CPU clock is read inside the
        # monotonic interval, but on a host whose CPU clock ticks a span of
        # 0.1 ms can be charged a whole tick of 10.)
        for s in spans:
            assert 0.0 <= s["self_cpu_ms"] <= s["cpu_ms"] + 1e-9, s
        # One node, one thread a request: every span is the root's thread's.
        assert sum(s["self_cpu_ms"] for s in spans) == pytest.approx(
            root["cpu_ms"], abs=0.001 * len(spans))
