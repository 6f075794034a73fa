"""InternalClient: node-to-node HTTP operations.

Port of the interface in /root/reference/client.go:34-60 and implementation
http/client.go: query fan-out, import routing, fragment block diff, shard
retrieval for resize, cluster message send, translate-log streaming.
Transport: stdlib http.client over per-thread keep-alive connection pools
(see _conn); wire format JSON/protobuf per route.
"""

from __future__ import annotations

import http.client
import json
import socket
import struct
import threading
import time
import urllib.parse
from typing import Any, Dict, List, Optional, Sequence

from ..errors import PilosaError
from .handler import deserialize_remote
from .mux import MuxError, MuxUnavailable


class ClientError(PilosaError):
    def __init__(self, message: str, status: int = 0):
        super().__init__(message)
        self.status = status


def load_cluster_key(path: str) -> str:
    """Read + validate a cluster shared-secret file (gossip.key analog).

    One loader shared by Server and the ctl CLI so both reject the same
    misconfigurations the same way: a missing file, an empty file (which
    would silently produce an unauthenticated client), or non-ASCII
    content (HTTP headers are latin-1 on the wire; an emoji key would
    brick every authenticated request with opaque errors)."""
    try:
        with open(path) as f:
            key = f.read().strip()
    except OSError as e:
        raise PilosaError(f"cannot read gossip key file {path!r}: {e}") from e
    if not key:
        raise PilosaError(f"gossip key file {path!r} is empty")
    if not key.isascii() or any(ord(c) < 33 or ord(c) == 127 for c in key):
        # Printable ASCII with no whitespace/control chars: anything else
        # either breaks http.client at header-send time (interior newline
        # -> 'Invalid header value') or invites invisible mismatches.
        raise PilosaError(
            f"gossip key file {path!r} must be printable ASCII on one line"
        )
    return key


def _node_url(node) -> str:
    uri = node.uri if not isinstance(node, str) else node
    if not uri.startswith("http"):
        uri = "http://" + uri
    return uri.rstrip("/")


class InternalClient:
    def __init__(self, timeout: float = 30.0, skip_verify: bool = False,
                 key: Optional[str] = None):
        self.timeout = timeout
        # Cluster shared secret (gossip.key analog): sent on every request;
        # peers with a key configured refuse unauthenticated /internal/*.
        self.key = key
        # Optional mux.MuxTransport (docs/transport.md), installed by the
        # owning Server when [transport] enabled: http-scheme requests
        # ride persistent multiplexed frames, with per-peer HTTP fallback
        # when the handshake fails (mixed / mux-disabled clusters).
        self.mux = None
        # Per-thread keep-alive connection pool (see _conn). Every
        # thread's pool dict is also tracked in _pools so close() can
        # drain sockets owned by threads that no longer exist.
        self._local = threading.local()
        self._pools_mu = threading.Lock()
        self._pools: list = []
        # TLS peer-verification opt-out for self-signed cluster certs
        # (reference server/server.go:216-218 InsecureSkipVerify).
        self._ssl_context = None
        if skip_verify:
            import ssl

            self._ssl_context = ssl.create_default_context()
            self._ssl_context.check_hostname = False
            self._ssl_context.verify_mode = ssl.CERT_NONE

    # Reuse a pooled connection only if it was used this recently: the
    # server closes idle keep-alive connections (handler read timeout
    # 60s), and reusing one the server is about to (or did) close risks
    # a request that cannot be safely replayed. Well under the server
    # timeout, so stale reuse needs a peer crash/restart, not mere idleness.
    IDLE_REUSE_S = 20.0

    def _conn(self, scheme: str, netloc: str):
        """Per-thread keep-alive connection to `netloc`, returned as
        (conn, fresh). urllib opens a fresh TCP connection per request,
        which put ~0.7 ms of setup on every node-to-node call (fan-out,
        replication, heartbeats); pooled HTTP/1.1 connections cut a serial
        query round trip ~2x. Thread-local, so no cross-thread sharing of
        http.client state. `fresh` is True when the connection was just
        opened — the retry policy needs to know, because only on a fresh
        connection does a send-phase error prove the peer never saw the
        request (a pooled connection's close race can deliver a partial
        body the peer may have already acted on)."""
        pool = getattr(self._local, "conns", None)
        if pool is None:
            pool = self._local.conns = {}
            with self._pools_mu:
                self._pools.append(pool)
        entry = pool.get((scheme, netloc))
        if entry is not None:
            conn, last_used = entry
            if time.monotonic() - last_used < self.IDLE_REUSE_S:
                return conn, False
            conn.close()
            del pool[(scheme, netloc)]
        if scheme == "https":
            import ssl

            ctx = self._ssl_context or ssl.create_default_context()
            conn = http.client.HTTPSConnection(
                netloc, timeout=self.timeout, context=ctx)
        else:
            conn = http.client.HTTPConnection(netloc, timeout=self.timeout)
        conn.connect()
        # Nagle off: small keep-alive requests otherwise stall ~40ms
        # per round trip on the delayed-ACK interaction.
        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        pool[(scheme, netloc)] = (conn, time.monotonic())
        return conn, True

    def _touch_conn(self, scheme: str, netloc: str) -> None:
        pool = getattr(self._local, "conns", None)
        if pool is not None and (scheme, netloc) in pool:
            pool[(scheme, netloc)] = (
                pool[(scheme, netloc)][0], time.monotonic())

    def _drop_conn(self, scheme: str, netloc: str) -> None:
        pool = getattr(self._local, "conns", None)
        if pool is not None:
            entry = pool.pop((scheme, netloc), None)
            if entry is not None:
                entry[0].close()

    def close(self) -> None:
        """Drain every thread's keep-alive pool. The pools are per-thread
        but registered centrally at creation, so shutdown can close
        sockets opened by worker threads that have since exited —
        previously they leaked until process exit (visible as climbing
        open-fd counts in tests that churn servers). Idempotent, and a
        send AFTER close builds (and re-registers) a fresh pool, so the
        Server and the Executor both closing the shared client is fine."""
        with self._pools_mu:
            pools, self._pools = self._pools, []
        for pool in pools:
            for entry in list(pool.values()):
                try:
                    entry[0].close()
                except OSError:  # pragma: no cover - best-effort teardown
                    pass
            pool.clear()

    def _request(self, method: str, url: str, body: Optional[bytes] = None,
                 content_type: str = "application/json",
                 accept: Optional[str] = None,
                 extra_headers: Optional[Dict[str, str]] = None,
                 want_headers: bool = False, idempotent: bool = False):
        """Returns the response body, or (body, lowercased-header-dict)
        when want_headers — the tracing path reads the peer's
        X-Pilosa-Trace-Summary off the response. ``idempotent`` marks a
        POST whose replay is harmless (PQL forwards: WRITE_CALLS all
        have value semantics) so the mux may retry it over HTTP when
        the peer cannot fit the response in a frame."""
        parts = urllib.parse.urlsplit(url)
        path = parts.path + (f"?{parts.query}" if parts.query else "")
        headers = {}
        if body is not None:
            headers["Content-Type"] = content_type
        if accept:
            headers["Accept"] = accept
        if self.key:
            headers["X-Pilosa-Key"] = self.key
        if extra_headers:
            headers.update(extra_headers)
        if self.mux is not None and parts.scheme == "http":
            try:
                status, data, rheaders = self.mux.request(
                    method, parts.netloc, path, body=body,
                    content_type=content_type if body is not None else None,
                    accept=accept, headers=extra_headers,
                    idempotent=idempotent)
            except MuxUnavailable:
                # Disabled / peer demoted / handshake failed / oversized
                # frame: routing, not an error — serve over plain HTTP.
                if self.mux.stats is not None:
                    self.mux.stats.bump("requests_http")
            except MuxError as e:
                # Same evidence shape as an HTTP socket fault: status 0
                # feeds the breaker and the executor's replica-retry
                # classification exactly like a connect failure.
                self._local.transport = "mux"
                raise ClientError(f"{method} {url}: {e}") from e
            else:
                self._local.transport = "mux"
                if status >= 400:
                    detail = data.decode(errors="replace")
                    raise ClientError(
                        f"{method} {url}: {status} {detail}", status=status)
                if want_headers:
                    return data, rheaders
                return data
        self._local.transport = "http"
        # Retry policy (one silent retry, always on a FRESH connection):
        #   - send-phase errors on a FRESHLY-OPENED connection: the peer
        #     provably never processed the request — retry any method;
        #   - send-phase errors on a POOLED connection: the keep-alive
        #     close race can deliver a partial body that proto3 may parse
        #     as a valid truncated message, so a non-GET replay could
        #     double-apply (e.g. a cluster message) — retry GET only.
        #     Deliberate tradeoff: the unretried POST surfaces as status 0
        #     and may transiently mark a healthy peer unavailable, but the
        #     member monitor re-marks it available on its next successful
        #     probe (~seconds), while a double-applied write diverges
        #     replicas until anti-entropy (~minutes);
        #   - response-phase zero-byte disconnects (RemoteDisconnected):
        #     the keep-alive race; retry only idempotent methods (GET) —
        #     a POST may have been processed before the connection died,
        #     and replaying e.g. a create turns success into a conflict.
        # Upper layers own non-idempotent recovery (executor replica
        # retry, member monitor), so surfacing the POST error is correct.
        from .. import failpoints

        for attempt in (0, 1):
            sent = False
            # Starts True so an exception INSIDE _conn (connect refused,
            # DNS) keeps any-method retry: a failed connection attempt
            # provably never reached the peer. Overwritten with the real
            # freshness once _conn returns (False = pooled keep-alive).
            fresh = True
            try:
                # Inside the try: an injected send fault (OSError) takes the
                # SAME classification path as a real one — it is retried
                # only when the policy below says a real fault would be.
                # The peer's netloc rides along so chaos tests can target
                # one node's link (drop/latency/flaky) and leave the rest
                # of the cluster healthy.
                failpoints.fire("client-send", target=parts.netloc)
                conn, fresh = self._conn(parts.scheme, parts.netloc)
                conn.request(method, path, body=body, headers=headers)
                sent = True
                resp = conn.getresponse()
                data = resp.read()
            except (http.client.HTTPException, ConnectionError, OSError) as e:
                self._drop_conn(parts.scheme, parts.netloc)
                retryable = (not sent and (fresh or method == "GET")) or (
                    method == "GET"
                    and isinstance(e, (http.client.RemoteDisconnected,
                                       http.client.BadStatusLine,
                                       ConnectionResetError))
                )
                if attempt == 0 and retryable and not isinstance(
                        e, TimeoutError):
                    continue
                raise ClientError(f"{method} {url}: {e}") from e
            if resp.will_close:
                # Server asked to close (send_error, HTTP/1.0 downgrade):
                # http.client would silently auto-reconnect WITHOUT our
                # TCP_NODELAY setup — evict so the next call rebuilds.
                self._drop_conn(parts.scheme, parts.netloc)
            else:
                self._touch_conn(parts.scheme, parts.netloc)
            if resp.status >= 400:
                detail = data.decode(errors="replace")
                raise ClientError(
                    f"{method} {url}: {resp.status} {detail}", status=resp.status
                )
            if want_headers:
                return data, {k.lower(): v for k, v in resp.getheaders()}
            return data

    def last_transport(self) -> str:
        """Which path the calling thread's most recent _request rode —
        'mux' or 'http'. query_node tags its remote span with it so
        traces show per-hop which transport carried the request."""
        return getattr(self._local, "transport", "http")

    # ---------------------------------------------------------------- query

    def query_node(self, node, index: str, query: str,
                   shards: Optional[Sequence[int]] = None, remote: bool = True,
                   deadline: Optional[float] = None,
                   epoch: Optional[int] = None, trace=None,
                   tenant: Optional[str] = None) -> List[Any]:
        """Execute PQL on a peer restricted to its shards (http/client.go
        QueryNode). `deadline` is the coordinator's REMAINING budget in
        seconds; it rides X-Pilosa-Deadline so the peer aborts its own
        device dispatches at the same cutoff. `epoch` is the sender's
        routing epoch (X-Pilosa-Epoch): a peer that has advanced past it
        and no longer serves the requested shards answers 409 instead of
        a hole from a migrated/GC'd fragment. `trace` is the caller's
        remote-hop Span (obs.Span): the trace id rides X-Pilosa-Trace so
        the peer records into the same cross-node tree, and the peer's
        X-Pilosa-Trace-Summary response header is spliced back as the
        hop's child spans."""
        from . import wire

        params = {"remote": "true"} if remote else {}
        url = f"{_node_url(node)}/index/{index}/query"
        if params:
            url += "?" + urllib.parse.urlencode(params)
        body = json.dumps({"query": query, "shards": list(shards) if shards else None}).encode()
        extra = {}
        if deadline is not None:
            extra["X-Pilosa-Deadline"] = f"{max(deadline, 0.0):.6f}"
        if epoch is not None:
            extra["X-Pilosa-Epoch"] = str(int(epoch))
        if trace is not None:
            extra["X-Pilosa-Trace"] = trace.wire_id()
        if tenant is not None:
            # QoS identity rides the hop so the data node's trace spans
            # carry the same tenant tag (budget charging itself stays on
            # the coordinator: forwarded sub-queries bypass admission).
            extra["X-Pilosa-Tenant"] = tenant
        extra = extra or None
        raw, resp_headers = self._request(
            "POST", url, body, accept=wire.CONTENT_TYPE,
            extra_headers=extra, want_headers=True, idempotent=True)
        if trace is not None:
            trace.tag(transport=self.last_transport())
            summary = resp_headers.get("x-pilosa-trace-summary")
            if summary:
                trace.splice(summary)
        # Binary data plane when the peer speaks it (packed bitplanes);
        # JSON fallback keeps mixed-version clusters working.
        if wire.is_wire(raw):
            try:
                return wire.decode_results(raw)
            except (ValueError, KeyError, TypeError, struct.error) as e:
                # A corrupt body is a NODE fault, whatever shape the
                # corruption takes (bad spans, truncated frame, missing
                # header fields): status 0 routes it through the
                # executor's replica-retry classification instead of
                # killing the whole query.
                raise ClientError(f"corrupt wire body from {url}: {e!r}") from e
        data = json.loads(raw)
        if "error" in data:
            # The peer executed the request and rejected it: a deterministic
            # application error, not node death. status=400 lets callers
            # (executor retry logic) distinguish it from transport failures
            # (status=0) and server faults (5xx).
            raise ClientError(data["error"], status=400)
        return [deserialize_remote(r) for r in data["results"]]

    def query(self, host: str, index: str, query: str, **params) -> dict:
        """Public query against a host; returns the raw JSON response."""
        url = f"{_node_url(host)}/index/{index}/query"
        if params:
            url += "?" + urllib.parse.urlencode(params)
        return json.loads(self._request("POST", url, query.encode(), "text/plain"))

    # --------------------------------------------------------------- schema

    def create_index(self, host, index: str, options: Optional[dict] = None) -> dict:
        body = json.dumps({"options": options or {}}).encode()
        return json.loads(self._request("POST", f"{_node_url(host)}/index/{index}", body))

    def create_field(self, host, index: str, field: str, options: Optional[dict] = None) -> dict:
        body = json.dumps({"options": options or {}}).encode()
        return json.loads(
            self._request("POST", f"{_node_url(host)}/index/{index}/field/{field}", body)
        )

    def ensure_index(self, host, index: str, options: Optional[dict] = None) -> None:
        try:
            self.create_index(host, index, options)
        except ClientError as e:
            if "exists" not in str(e).lower():
                raise

    def ensure_field(self, host, index: str, field: str, options: Optional[dict] = None) -> None:
        try:
            self.create_field(host, index, field, options)
        except ClientError as e:
            if "exists" not in str(e).lower():
                raise

    def schema(self, host) -> List[dict]:
        return json.loads(self._request("GET", f"{_node_url(host)}/schema"))["indexes"]

    def status(self, host) -> dict:
        return json.loads(self._request("GET", f"{_node_url(host)}/status"))

    def shards_max(self, host) -> Dict[str, int]:
        return json.loads(self._request("GET", f"{_node_url(host)}/internal/shards/max"))["standard"]

    # --------------------------------------------------------------- import

    def import_node(self, node, index: str, field: str, shard: int,
                    row_ids, column_ids, timestamps=None) -> None:
        body = json.dumps({
            "shard": shard,
            "rowIDs": [int(r) for r in row_ids],
            "columnIDs": [int(c) for c in column_ids],
            "timestamps": timestamps,
            "remote": True,
        }).encode()
        self._request("POST", f"{_node_url(node)}/index/{index}/field/{field}/import", body)

    # Marks a request as already admitted by the sending node's scheduler:
    # the receiver skips re-admission (the body cannot carry remote:true —
    # the translation primary must still run its own owner fan-out).
    FORWARDED_HEADER = {"X-Pilosa-Forwarded": "1"}

    def import_keys_node(self, node, index: str, field: str,
                         row_ids, column_ids, row_keys, column_keys, timestamps) -> None:
        """Forward a key-mode import to the translation primary."""
        body = json.dumps({
            "rowIDs": list(row_ids) if row_ids is not None and not row_keys else None,
            "columnIDs": list(column_ids) if column_ids is not None and not column_keys else None,
            "rowKeys": list(row_keys) if row_keys else None,
            "columnKeys": list(column_keys) if column_keys else None,
            "timestamps": list(timestamps) if timestamps else None,
        }).encode()
        self._request("POST", f"{_node_url(node)}/index/{index}/field/{field}/import",
                      body, extra_headers=self.FORWARDED_HEADER)

    def import_value_keys_node(self, node, index: str, field: str,
                               column_keys, values) -> None:
        """Forward a key-mode value import to the translation primary."""
        body = json.dumps({
            "columnKeys": list(column_keys),
            "values": [int(v) for v in values],
        }).encode()
        self._request("POST", f"{_node_url(node)}/index/{index}/field/{field}/import",
                      body, extra_headers=self.FORWARDED_HEADER)

    def import_value_node(self, node, index: str, field: str, shard: int,
                          column_ids, values) -> None:
        body = json.dumps({
            "shard": shard,
            "columnIDs": [int(c) for c in column_ids],
            "values": [int(v) for v in values],
            "remote": True,
        }).encode()
        self._request("POST", f"{_node_url(node)}/index/{index}/field/{field}/import", body)

    def import_bits(self, host, index: str, field: str, bits) -> None:
        """Public bulk import: group (row, col) bits by shard and POST each
        group to an owning node (http/client.go:276 Import). Bits with
        string row/column values go through the key-translation import."""
        from ..constants import SHARD_WIDTH

        if bits and (isinstance(bits[0][0], str) or isinstance(bits[0][1], str)):
            body = json.dumps({
                "rowKeys": [b[0] for b in bits] if isinstance(bits[0][0], str) else None,
                "rowIDs": None if isinstance(bits[0][0], str) else [b[0] for b in bits],
                "columnKeys": [b[1] for b in bits] if isinstance(bits[0][1], str) else None,
                "columnIDs": None if isinstance(bits[0][1], str) else [b[1] for b in bits],
                "timestamps": [b[2] if len(b) > 2 else None for b in bits],
            }).encode()
            self._request("POST", f"{_node_url(host)}/index/{index}/field/{field}/import", body)
            return

        by_shard: Dict[int, List] = {}
        for bit in bits:
            row, col = bit[0], bit[1]
            ts = bit[2] if len(bit) > 2 else None
            by_shard.setdefault(col // SHARD_WIDTH, []).append((row, col, ts))
        by_node: Dict[str, List] = {}
        for shard, group in sorted(by_shard.items()):
            nodes = self.fragment_nodes(host, index, shard)
            target = nodes[0]["uri"] if nodes else host
            body = json.dumps({
                "shard": shard,
                "rowIDs": [b[0] for b in group],
                "columnIDs": [b[1] for b in group],
                "timestamps": [b[2] for b in group],
            }).encode()
            by_node.setdefault(target, []).append(body)
        self._send_import_groups(index, field, by_node)

    def import_values(self, host, index: str, field: str, field_values) -> None:
        from ..constants import SHARD_WIDTH

        if field_values and isinstance(field_values[0][0], str):
            body = json.dumps({
                "columnKeys": [c for c, _ in field_values],
                "values": [int(v) for _, v in field_values],
            }).encode()
            self._request("POST", f"{_node_url(host)}/index/{index}/field/{field}/import", body)
            return

        by_shard: Dict[int, List] = {}
        for col, val in field_values:
            by_shard.setdefault(col // SHARD_WIDTH, []).append((col, val))
        by_node: Dict[str, List] = {}
        for shard, group in sorted(by_shard.items()):
            nodes = self.fragment_nodes(host, index, shard)
            target = nodes[0]["uri"] if nodes else host
            body = json.dumps({
                "shard": shard,
                "columnIDs": [g[0] for g in group],
                "values": [g[1] for g in group],
            }).encode()
            by_node.setdefault(target, []).append(body)
        self._send_import_groups(index, field, by_node)

    def _send_import_groups(self, index: str, field: str,
                            by_node: Dict[str, List]) -> None:
        """POST pre-encoded shard import bodies, nodes in PARALLEL and a
        node's batches in order: each worker thread owns its per-thread
        keep-alive pool, so a multi-node bulk load streams every target
        concurrently instead of serializing the whole import behind one
        node's round trips. Every node is attempted; the first error is
        raised after all sends complete (partial progress is repaired by
        anti-entropy, exactly like the server-side tolerant fan-out)."""
        def run(target, bodies):
            for body in bodies:
                self._request(
                    "POST",
                    f"{_node_url(target)}/index/{index}/field/{field}/import",
                    body)

        if len(by_node) <= 1:
            for target, bodies in by_node.items():
                run(target, bodies)
            return
        from concurrent.futures import ThreadPoolExecutor

        first_error = None
        with ThreadPoolExecutor(max_workers=min(len(by_node), 8)) as pool:
            futs = [pool.submit(run, t, b) for t, b in by_node.items()]
            for f in futs:
                try:
                    f.result()
                except Exception as e:
                    first_error = first_error or e
        if first_error is not None:
            raise first_error

    # ------------------------------------------------------------- internal

    def fragment_nodes(self, host, index: str, shard: int) -> List[dict]:
        url = f"{_node_url(host)}/internal/fragment/nodes?index={index}&shard={shard}"
        return json.loads(self._request("GET", url))

    def fragment_blocks(self, node, index: str, field: str, shard: int,
                        view: str = "standard") -> List[dict]:
        # The reference RPC is view-blind (http/handler.go:1058 hardcodes
        # standard); carrying the view avoids cross-view checksum
        # comparisons when the syncer walks time/bsig views.
        url = (f"{_node_url(node)}/internal/fragment/blocks?"
               f"index={index}&field={field}&view={view}&shard={shard}")
        try:
            return json.loads(self._request("GET", url))["blocks"]
        except ClientError as e:
            if e.status == 404:
                # Replica doesn't have the fragment yet: empty block set, so
                # the syncer pushes everything (client.go:666-668).
                return []
            raise

    def send_block_diff(self, node, index: str, field: str, view: str, shard: int,
                        block: int, sets, clears) -> None:
        """Apply a merged block diff to a replica's exact view. Set/Clear
        PQL (the reference's push, fragment.go:1814-1903) can only reach the
        standard view; non-standard views need a view-addressed write."""
        url = (f"{_node_url(node)}/internal/fragment/block/data?"
               f"index={index}&field={field}&view={view}&shard={shard}&block={block}")
        body = json.dumps({"sets": sets, "clears": clears}).encode()
        self._request("POST", url, body)

    def send_hint_ops(self, node, index: str, field: str, view: str,
                      shard: int, data: bytes) -> None:
        """Deliver one hinted-handoff record (cluster/hints.py): a raw
        run of storage/bitmap.py WAL op records the peer replays into the
        addressed fragment. Idempotent on the receiver, so the client's
        fresh-connection send retry is safe here like everywhere else."""
        url = (f"{_node_url(node)}/internal/fragment/hints?"
               f"index={index}&field={field}&view={view}&shard={shard}")
        self._request("POST", url, data,
                      content_type="application/octet-stream")

    def block_data(self, node, index: str, field: str, view: str, shard: int, block: int) -> dict:
        url = (f"{_node_url(node)}/internal/fragment/block/data?"
               f"index={index}&field={field}&view={view}&shard={shard}&block={block}")
        try:
            return json.loads(self._request("GET", url))
        except ClientError as e:
            if e.status == 404:
                return {"rowIDs": [], "columnIDs": []}
            raise

    # ------------------------------------------------------ live migration

    def migrate_begin(self, uri, index: str, field: str, view: str,
                      shard: int):
        """Open a migration stream for one fragment: returns (header,
        base_bytes) where header carries the session id and the WAL
        position the base corresponds to (cluster/rebalance.py framing)."""
        from ..cluster.rebalance import unpack_framed

        body = json.dumps({"index": index, "field": field, "view": view,
                           "shard": shard}).encode()
        raw = self._request(
            "POST", f"{_node_url(uri)}/internal/migrate/begin", body)
        return unpack_framed(raw)

    def migrate_delta(self, uri, session: str, from_pos=None):
        """Pull the WAL tail appended since `from_pos` (the receiver's
        cursor — sending it makes a retried pull re-read the same chunk,
        never skip one): (header, wal_bytes); header {"restart": true}
        means the source's file layout changed and the stream must begin
        again."""
        from ..cluster.rebalance import unpack_framed

        body = json.dumps({"session": session, "from": from_pos}).encode()
        raw = self._request(
            "POST", f"{_node_url(uri)}/internal/migrate/delta", body)
        return unpack_framed(raw)

    def migrate_freeze(self, uri, index: str, shard: int) -> dict:
        """Cut a shard over on its source: fragments stop accepting
        writes and the source's routing flips to the new owner."""
        body = json.dumps({"index": index, "shard": shard}).encode()
        return json.loads(self._request(
            "POST", f"{_node_url(uri)}/internal/migrate/freeze", body))

    def migrate_close(self, uri, sessions) -> None:
        body = json.dumps({"sessions": list(sessions)}).encode()
        self._request(
            "POST", f"{_node_url(uri)}/internal/migrate/close", body)

    def retrieve_shard_from_uri(self, uri: str, index: str, field: str, view: str, shard: int) -> bytes:
        url = (f"{_node_url(uri)}/internal/fragment/data?"
               f"index={index}&field={field}&view={view}&shard={shard}")
        return self._request("GET", url)

    def send_fragment_data(self, node, index: str, field: str, view: str, shard: int, data: bytes) -> None:
        url = (f"{_node_url(node)}/internal/fragment/data?"
               f"index={index}&field={field}&view={view}&shard={shard}")
        self._request("POST", url, data, "application/octet-stream")

    def send_message(self, node, msg: dict) -> None:
        """Cluster envelope POST (reference http/client.go SendMessage).

        Default wire format is the reference's type-byte + protobuf
        envelope (broadcast.go:52-162, proto/envelope.py); repo-native
        message types ride a JSON extension frame inside it.
        PILOSA_TPU_CLUSTER_JSON=1 forces plain JSON (the debug fallback
        the handler always accepts)."""
        import os

        if os.environ.get("PILOSA_TPU_CLUSTER_JSON") == "1":
            body, ctype = json.dumps(msg).encode(), "application/json"
        else:
            from .proto import envelope

            body, ctype = envelope.encode_message(msg), "application/x-protobuf"
        self._request("POST", f"{_node_url(node)}/internal/cluster/message",
                      body, ctype)

    # ------------------------------------------------------------- cdc + geo

    def cdc_stream(self, host, index: str, from_pos: int,
                   incarnation: Optional[str] = None,
                   timeout: Optional[float] = None,
                   max_bytes: Optional[int] = None):
        """One long-poll chunk of a peer's change stream (GET
        /cdc/stream — the geo tailer's feed). Returns (raw framed
        records, lowercased response headers); the caller reads the
        resume cursor off x-pilosa-cdc-next and the lag anchors off
        x-pilosa-cdc-head-pos/-time. A 410 ClientError means the cursor
        fell behind retention (or the index was recreated): re-seed via
        cdc_bootstrap. Safe to retry: a replayed GET re-reads the same
        positions."""
        url = f"{_node_url(host)}/cdc/stream?index={index}&from={int(from_pos)}"
        if incarnation:
            qinc = urllib.parse.quote(incarnation, safe="")
            url += f"&incarnation={qinc}"
        if timeout is not None:
            url += f"&timeout={timeout}"
        if max_bytes is not None:
            url += f"&max-bytes={int(max_bytes)}"
        return self._request("GET", url, want_headers=True)

    def cdc_bootstrap(self, host, index: str) -> dict:
        return json.loads(self._request(
            "GET", f"{_node_url(host)}/cdc/bootstrap?index={index}"))

    def geo_demote(self, host, leader: str, epoch: int) -> dict:
        """The fencing handshake (POST /geo/demote): tell a deposed
        leader it has been fenced at `epoch` and should re-tail
        `leader`. 409 means the target holds an equal-or-higher epoch."""
        body = json.dumps({"leader": leader, "epoch": int(epoch)}).encode()
        return json.loads(self._request(
            "POST", f"{_node_url(host)}/geo/demote", body))

    def translate_data(self, node, offset: int) -> bytes:
        url = f"{_node_url(node)}/internal/translate/data?offset={offset}"
        return self._request("GET", url)

    def attr_diff(self, node, index: str, field: Optional[str], blocks: List[dict]) -> Dict[int, dict]:
        if field:
            url = f"{_node_url(node)}/internal/index/{index}/field/{field}/attr/diff"
        else:
            url = f"{_node_url(node)}/internal/index/{index}/attr/diff"
        data = json.loads(self._request("POST", url, json.dumps({"blocks": blocks}).encode()))
        return {int(k): v for k, v in data["attrs"].items()}
