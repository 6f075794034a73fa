"""Server + HTTP + multi-node cluster tests.

Single-node tests drive the Getting Started flow (reference README.md:33-47)
through real HTTP. Multi-node tests boot N in-process nodes on localhost
with static membership and a deterministic ModHasher — the reference's
trick for distributed tests without containers (test/pilosa.go:161-238).
"""

import os
import socket
import time

import pytest

from pilosa_tpu.cluster.hash import ModHasher
from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.server.client import InternalClient
from pilosa_tpu.server.server import Server


def free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture
def server(tmp_path):
    s = Server(data_dir=str(tmp_path / "node0"), cache_flush_interval=0)
    s.open()
    yield s
    s.close()


@pytest.fixture
def client():
    return InternalClient()


def host(s):
    return f"localhost:{s.port}"


def test_getting_started_flow(server, client):
    """README stargazer flow: create schema, set bits, query."""
    client.create_index(host(server), "repository")
    client.create_field(host(server), "repository", "stargazer")
    for col in [1, 2, 3]:
        client.query(host(server), "repository", f"Set({col}, stargazer=10)")
    resp = client.query(host(server), "repository", "Row(stargazer=10)")
    assert resp["results"][0]["columns"] == [1, 2, 3]
    resp = client.query(host(server), "repository", "Count(Row(stargazer=10))")
    assert resp["results"][0] == 3
    resp = client.query(
        host(server), "repository", "TopN(stargazer, n=1)"
    )
    assert resp["results"][0] == [{"id": 10, "count": 3}]


def test_schema_and_status_endpoints(server, client):
    client.create_index(host(server), "i1")
    client.create_field(host(server), "i1", "f1")
    schema = client.schema(host(server))
    assert schema[0]["name"] == "i1"
    assert schema[0]["fields"][0]["name"] == "f1"
    status = client.status(host(server))
    assert status["state"] == "NORMAL"
    assert len(status["nodes"]) == 1


def test_http_import(server, client):
    client.create_index(host(server), "imp")
    client.create_field(host(server), "imp", "f")
    bits = [(1, 10), (1, 20), (2, SHARD_WIDTH + 5)]
    client.import_bits(host(server), "imp", "f", bits)
    resp = client.query(host(server), "imp", "Row(f=1)")
    assert resp["results"][0]["columns"] == [10, 20]
    resp = client.query(host(server), "imp", "Row(f=2)")
    assert resp["results"][0]["columns"] == [SHARD_WIDTH + 5]
    assert client.shards_max(host(server)) == {"imp": 1}


def test_http_import_values(server, client):
    client.create_index(host(server), "impv")
    client.create_field(
        host(server), "impv", "v", {"type": "int", "min": 0, "max": 1000}
    )
    client.import_values(host(server), "impv", "v", [(1, 100), (2, 200)])
    resp = client.query(host(server), "impv", "Sum(field=v)")
    assert resp["results"][0] == {"value": 300, "count": 2}


def test_error_responses(server, client):
    from pilosa_tpu.server.client import ClientError

    with pytest.raises(ClientError, match="not found|NotFound"):
        client.query(host(server), "nosuch", "Row(f=1)")


def test_export(server, client):
    client.create_index(host(server), "ex")
    client.create_field(host(server), "ex", "f")
    client.query(host(server), "ex", "Set(7, f=3)")
    import urllib.request

    with urllib.request.urlopen(
        f"http://{host(server)}/export?index=ex&field=f&shard=0"
    ) as resp:
        assert resp.read().decode() == "3,7\n"


# --------------------------------------------------------------- multi-node


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
            hasher=ModHasher(),
            cache_flush_interval=0,
            executor_workers=0,
        )
        s.open()
        servers.append(s)
    yield servers
    for s in servers:
        s.close()


def test_cluster_membership(cluster3):
    for s in cluster3:
        assert len(s.cluster.nodes) == 3
        assert {n.id for n in s.cluster.nodes} == {n.uri for n in s.cluster.nodes}


def test_cluster_schema_broadcast(cluster3, client):
    client.create_index(host(cluster3[0]), "ci")
    client.create_field(host(cluster3[0]), "ci", "f")
    time.sleep(0.1)
    for s in cluster3:
        assert s.holder.index("ci") is not None
        assert s.holder.index("ci").field("f") is not None


def test_cluster_remote_query(cluster3, client):
    """Bits planted across shards; any node answers the full query
    (reference executor_test.go TestExecutor_Execute_Remote_Row)."""
    client.create_index(host(cluster3[0]), "ci")
    client.create_field(host(cluster3[0]), "ci", "f")
    time.sleep(0.1)
    # With ModHasher, shard s lives on node partition(s) % 3 — plant bits in
    # three different shards through node 0; writes route to owners.
    cols = [1, SHARD_WIDTH + 2, 2 * SHARD_WIDTH + 3, 3 * SHARD_WIDTH + 4]
    for col in cols:
        client.query(host(cluster3[0]), "ci", f"Set({col}, f=9)")
    # Shards must be distributed across more than one node.
    owners = {
        cluster3[0].cluster.shard_nodes("ci", s)[0].id for s in range(4)
    }
    assert len(owners) > 1
    for s in cluster3:
        resp = client.query(host(s), "ci", "Row(f=9)")
        assert resp["results"][0]["columns"] == cols
        resp = client.query(host(s), "ci", "Count(Row(f=9))")
        assert resp["results"][0] == 4


def test_cluster_remote_topn(cluster3, client):
    client.create_index(host(cluster3[0]), "ct")
    client.create_field(host(cluster3[0]), "ct", "f")
    time.sleep(0.1)
    for col in [0, 1, SHARD_WIDTH, SHARD_WIDTH + 1, 2 * SHARD_WIDTH]:
        client.query(host(cluster3[0]), "ct", f"Set({col}, f=10)")
    for col in [2, 3]:
        client.query(host(cluster3[0]), "ct", f"Set({col}, f=20)")
    resp = client.query(host(cluster3[1]), "ct", "TopN(f, n=2)")
    assert resp["results"][0] == [
        {"id": 10, "count": 5},
        {"id": 20, "count": 2},
    ]


def test_cluster_sum_remote(cluster3, client):
    client.create_index(host(cluster3[0]), "cs")
    client.create_field(
        host(cluster3[0]), "cs", "v", {"type": "int", "min": 0, "max": 100}
    )
    time.sleep(0.1)
    client.import_values(
        host(cluster3[0]), "cs", "v",
        [(1, 10), (SHARD_WIDTH + 1, 20), (2 * SHARD_WIDTH + 1, 30)],
    )
    resp = client.query(host(cluster3[2]), "cs", "Sum(field=v)")
    assert resp["results"][0] == {"value": 60, "count": 3}


def test_cluster_attr_broadcast(cluster3, client):
    client.create_index(host(cluster3[0]), "ca")
    client.create_field(host(cluster3[0]), "ca", "f")
    time.sleep(0.1)
    client.query(host(cluster3[0]), "ca", 'SetRowAttrs(f, 1, color="red")')
    for s in cluster3:
        assert s.holder.field("ca", "f").row_attr_store.attrs(1) == {"color": "red"}


def test_debug_vars_and_diagnostics(server, client):
    import json
    import urllib.request

    client.create_index(host(server), "dv")
    client.create_field(host(server), "dv", "f")
    client.query(host(server), "dv", "Set(1, f=1)")
    with urllib.request.urlopen(f"http://{host(server)}/debug/vars") as resp:
        snap = json.loads(resp.read())
    assert "counters" in snap and snap["counters"].get("setBit", 0) >= 1
    # The engine is built at open(), so a server says what it runs on
    # before it has served a single device query.
    dev = snap["device"]
    assert dev["platform"] == "cpu" and dev["device_kind"] == "cpu"
    assert dev["n_devices"] == len(dev["devices"]) == 8
    assert dev["mesh_shape"] == {"shards": 8}
    assert set(dev["devices"][0]) == {"id", "bytes_in_use",
                                      "peak_bytes_in_use"}
    assert snap["native"] == {"loaded": True}
    with urllib.request.urlopen(f"http://{host(server)}/internal/diagnostics") as resp:
        diag = json.loads(resp.read())
    assert diag["numIndexes"] >= 1 and diag["version"]


def test_debug_threads_and_profile(server):
    import json
    import urllib.request

    with urllib.request.urlopen(f"http://{host(server)}/debug/threads") as resp:
        dump = json.loads(resp.read())
    assert dump["count"] >= 1
    # The serving thread's own stack must be present and show the handler.
    assert any(
        any("handle_debug_threads" in line for line in stack)
        for stack in dump["threads"].values()
    )
    req = urllib.request.Request(
        f"http://{host(server)}/debug/profile?seconds=0.1", method="POST"
    )
    with urllib.request.urlopen(req) as resp:
        prof = json.loads(resp.read())
    assert os.path.isdir(prof["path"])
    # The capture must have written a trace artifact, not just the dir.
    assert any(files for _, _, files in os.walk(prof["path"]))


def test_long_query_logging(tmp_path):
    from pilosa_tpu.logger import BufferLogger
    from pilosa_tpu.server.client import InternalClient

    logger = BufferLogger()
    s = Server(
        data_dir=str(tmp_path / "lq"), cache_flush_interval=0,
        long_query_time=0.000001, logger=logger,
    )
    s.open()
    try:
        c = InternalClient()
        c.create_index(f"localhost:{s.port}", "lq")
        c.create_field(f"localhost:{s.port}", "lq", "f")
        c.query(f"localhost:{s.port}", "lq", "Set(1, f=1)")
        assert any("long-query-time" in line for _, line in logger.lines)
    finally:
        s.close()


def test_cors_preflight_and_header(tmp_path):
    """CORS parity (reference server/handler_test.go:555-581): OPTIONS is 405
    with no allowed origins; with origins configured, preflight is 200 and the
    Access-Control-Allow-Origin header echoes an allowed origin."""
    import urllib.request

    s = Server(data_dir=str(tmp_path / "nc"), cache_flush_interval=0)
    s.open()
    try:
        req = urllib.request.Request(
            f"http://localhost:{s.port}/index/foo/query", method="OPTIONS")
        req.add_header("Origin", "http://test/")
        req.add_header("Access-Control-Request-Method", "POST")
        try:
            urllib.request.urlopen(req)
            assert False, "expected 405"
        except urllib.error.HTTPError as e:
            assert e.code == 405
    finally:
        s.close()

    s = Server(data_dir=str(tmp_path / "c"), cache_flush_interval=0,
               allowed_origins=["http://test/"])
    s.open()
    try:
        req = urllib.request.Request(
            f"http://localhost:{s.port}/index/foo/query", method="OPTIONS")
        req.add_header("Origin", "http://test/")
        req.add_header("Access-Control-Request-Method", "POST")
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 200
            assert resp.headers["Access-Control-Allow-Origin"] == "http://test/"
        # Header also present on a normal request from an allowed origin.
        req = urllib.request.Request(f"http://localhost:{s.port}/schema")
        req.add_header("Origin", "http://test/")
        with urllib.request.urlopen(req) as resp:
            assert resp.headers["Access-Control-Allow-Origin"] == "http://test/"
        # Disallowed origin: no CORS header.
        req = urllib.request.Request(f"http://localhost:{s.port}/schema")
        req.add_header("Origin", "http://evil/")
        with urllib.request.urlopen(req) as resp:
            assert resp.headers.get("Access-Control-Allow-Origin") is None
    finally:
        s.close()


def test_tls_server(tmp_path, tls_cert):
    """https bind with a self-signed cert (reference server/server.go:203-232);
    internal client with skip_verify talks to it."""
    cert, key = tls_cert
    s = Server(
        data_dir=str(tmp_path / "tls"), cache_flush_interval=0,
        scheme="https", tls_certificate=cert, tls_certificate_key=key,
        tls_skip_verify=True,
    )
    s.open()
    try:
        assert s.node.uri.startswith("https://")
        c = InternalClient(skip_verify=True)
        c.create_index(s.node.uri, "sec")
        c.create_field(s.node.uri, "sec", "f")
        c.query(s.node.uri, "sec", "Set(1, f=1)")
        res = c.query(s.node.uri, "sec", "Count(Row(f=1))")
        assert res["results"][0] == 1
    finally:
        s.close()


def test_tls_requires_cert():
    with pytest.raises(ValueError):
        Server(scheme="https")


def test_tls_static_cluster(tmp_path, tls_cert):
    """Static https cluster with schemeless host entries: the self-entry
    still matches (no phantom node) and peers are dialed over https."""
    cert, key = tls_cert
    ports = [free_port() for _ in range(2)]
    hosts = [f"localhost:{p}" for p in ports]
    servers = []
    try:
        for i, port in enumerate(ports):
            s = Server(
                data_dir=str(tmp_path / f"node{i}"), port=port,
                cluster_hosts=hosts, hasher=ModHasher(),
                cache_flush_interval=0, executor_workers=0,
                scheme="https", tls_certificate=cert,
                tls_certificate_key=key, tls_skip_verify=True,
            )
            s.open()
            servers.append(s)
        for s in servers:
            assert len(s.cluster.nodes) == 2, [n.uri for n in s.cluster.nodes]
            assert all(n.uri.startswith("https://") for n in s.cluster.nodes)
        c = InternalClient(skip_verify=True)
        c.create_index(servers[0].node.uri, "tc")
        c.create_field(servers[0].node.uri, "tc", "f")
        time.sleep(0.1)
        # Bits in two shards: with ModHasher over 2 nodes they land on
        # different owners, forcing node-to-node fan-out over https.
        c.query(servers[0].node.uri, "tc", "Set(1, f=5)")
        c.query(servers[0].node.uri, "tc", f"Set({SHARD_WIDTH + 2}, f=5)")
        for s in servers:
            resp = c.query(s.node.uri, "tc", "Count(Row(f=5))")
            assert resp["results"][0] == 2
    finally:
        for s in servers:
            s.close()


def test_id_mode_import_missing_rows_is_400(server, client):
    """ID-mode import with columnIDs but no rowIDs must 400, not silently
    import nothing."""
    import json as _json
    import urllib.error
    import urllib.request

    client.create_index(host(server), "idm")
    client.create_field(host(server), "idm", "f")
    req = urllib.request.Request(
        f"http://{host(server)}/index/idm/field/f/import",
        data=_json.dumps({"columnIDs": [1, 2, 3]}).encode(), method="POST",
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    assert ei.value.code == 400
    assert "mismatch" in ei.value.read().decode()


def test_key_import_forwarding_to_translation_primary(tmp_path):
    """Key-mode bit AND value imports against a translation replica are
    forwarded to the primary (reference PrimaryTranslateStore semantics)."""
    primary = Server(data_dir=str(tmp_path / "pri"), cache_flush_interval=0)
    primary.open()
    c = InternalClient()
    try:
        c.create_index(host(primary), "ki", {"keys": True})
        c.create_field(host(primary), "ki", "b", {"keys": True})
        c.create_field(host(primary), "ki", "v", {"type": "int", "min": 0, "max": 100})
        replica = Server(
            data_dir=str(tmp_path / "rep"), cache_flush_interval=0,
            primary_translate_store_url=f"http://{host(primary)}",
        )
        replica.open()
        try:
            assert replica.translate_store.read_only
            # Schema must exist on the replica too (it forwards, but the
            # field lookup happens first).
            c.create_index(host(replica), "ki", {"keys": True})
            c.create_field(host(replica), "ki", "b", {"keys": True})
            c.create_field(host(replica), "ki", "v", {"type": "int", "min": 0, "max": 100})
            c.import_bits(host(replica), "ki", "b", [("r1", "alice"), ("r1", "bob")])
            c.import_values(host(replica), "ki", "v", [("alice", 42), ("bob", 58)])
            resp = c.query(host(primary), "ki", 'Count(Row(b="r1"))')
            assert resp["results"][0] == 2
            resp = c.query(host(primary), "ki", "Sum(field=v)")
            assert resp["results"][0] == {"value": 100, "count": 2}
        finally:
            replica.close()
    finally:
        primary.close()
