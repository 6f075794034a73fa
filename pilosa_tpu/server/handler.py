"""HTTP transport: stdlib ThreadingHTTPServer REST handler.

Route table mirrors /root/reference/http/handler.go:189-231 (public
/index//field//query/import/schema/status plus /internal/* node-to-node
routes). Wire format is JSON (the reference negotiates JSON/protobuf;
JSON is canonical here). Remote (node-to-node) query responses carry type
tags so the coordinator can rehydrate Row/Pair/ValCount objects.
"""

from __future__ import annotations

import json
import re
import threading
import time
import traceback
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from ..core import cache as cache_mod
from ..core.cache import Pair
from ..core.row import Row
from ..errors import PilosaError
from ..executor import ValCount
from .api import API


def serialize_remote(r) -> dict:
    """Type-tagged result encoding for node-to-node responses."""
    if isinstance(r, Row):
        return {"type": "row", "columns": [int(c) for c in r.columns()],
                "attrs": r.attrs or {}}
    if isinstance(r, ValCount):
        return {"type": "valcount", "value": r.val, "count": r.count}
    if isinstance(r, list) and (not r or isinstance(r[0], Pair)):
        return {"type": "pairs", "pairs": [p.to_dict() for p in r]}
    if isinstance(r, bool):
        return {"type": "bool", "value": r}
    if isinstance(r, int):
        return {"type": "uint64", "value": r}
    return {"type": "none", "value": None}


def deserialize_remote(d: dict):
    t = d.get("type")
    if t == "row":
        row = Row(columns=d.get("columns", []))
        row.attrs = d.get("attrs", {})
        return row
    if t == "valcount":
        return ValCount(val=d["value"], count=d["count"])
    if t == "pairs":
        return [Pair(id=p["id"], count=p["count"], key=p.get("key", "")) for p in d["pairs"]]
    if t in ("bool", "uint64"):
        return d["value"]
    return None


def _json_body(body: bytes, default=None) -> dict:
    """Parse a JSON request body; malformed input is a client error (400),
    not an internal one."""
    if not body:
        if default is not None:
            return default
        raise PilosaError("request body required")
    try:
        return json.loads(body)
    except json.JSONDecodeError as e:
        raise PilosaError(f"malformed JSON body: {e}") from None


class Route:
    def __init__(self, method: str, pattern: str, fn: Callable):
        self.method = method
        self.regex = re.compile("^" + pattern + "$")
        self.fn = fn


class Handler:
    """Routes HTTP requests to API methods."""

    def __init__(self, api: API, logger=None, allowed_origins: Optional[List[str]] = None,
                 internal_key: Optional[str] = None):
        self.api = api
        self.logger = logger
        # Cluster shared secret (gossip.key analog): when set, /internal/*
        # requires a matching X-Pilosa-Key header — an unkeyed or
        # wrong-keyed node cannot join or deliver cluster messages. Public
        # API routes (incl. /status, which heartbeat probes read) stay
        # open, matching the reference's HTTP plane.
        self.internal_key = internal_key
        # CORS allowed origins (reference http/handler.go:83-91 wraps the
        # router in gorilla handlers.CORS when configured; empty = no CORS,
        # preflight gets 405 per server/handler_test.go:555-567).
        self.allowed_origins = list(allowed_origins or [])
        self.routes: List[Route] = [
            Route("GET", r"/", self.handle_home),
            Route("GET", r"/index", self.handle_get_indexes),
            Route("GET", r"/index/(?P<index>[^/]+)", self.handle_get_index),
            Route("POST", r"/index/(?P<index>[^/]+)", self.handle_post_index),
            Route("DELETE", r"/index/(?P<index>[^/]+)", self.handle_delete_index),
            Route("POST", r"/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)", self.handle_post_field),
            Route("DELETE", r"/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)", self.handle_delete_field),
            Route("POST", r"/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import", self.handle_post_import),
            Route("POST", r"/index/(?P<index>[^/]+)/query", self.handle_post_query),
            Route("GET", r"/export", self.handle_get_export),
            Route("GET", r"/schema", self.handle_get_schema),
            Route("GET", r"/status", self.handle_get_status),
            Route("GET", r"/info", self.handle_get_info),
            Route("GET", r"/version", self.handle_get_version),
            Route("POST", r"/recalculate-caches", self.handle_recalculate_caches),
            Route("POST", r"/cluster/resize/abort", self.handle_resize_abort),
            Route("POST", r"/cluster/resize/remove-node", self.handle_remove_node),
            Route("POST", r"/cluster/resize/set-coordinator", self.handle_set_coordinator),
            Route("POST", r"/internal/cluster/message", self.handle_cluster_message),
            Route("POST", r"/internal/collective/count", self.handle_collective_count),
            Route("GET", r"/internal/fragment/blocks", self.handle_fragment_blocks),
            Route("GET", r"/internal/fragment/block/data", self.handle_fragment_block_data),
            Route("POST", r"/internal/fragment/block/data", self.handle_post_block_data),
            Route("GET", r"/internal/fragment/nodes", self.handle_fragment_nodes),
            Route("GET", r"/internal/fragment/data", self.handle_fragment_data),
            Route("POST", r"/internal/fragment/data", self.handle_post_fragment_data),
            Route("POST", r"/internal/migrate/begin", self.handle_migrate_begin),
            Route("POST", r"/internal/migrate/delta", self.handle_migrate_delta),
            Route("POST", r"/internal/migrate/freeze", self.handle_migrate_freeze),
            Route("POST", r"/internal/migrate/close", self.handle_migrate_close),
            Route("GET", r"/internal/shards/max", self.handle_shards_max),
            Route("GET", r"/internal/translate/data", self.handle_translate_data),
            Route("POST", r"/internal/index/(?P<index>[^/]+)/attr/diff", self.handle_index_attr_diff),
            Route("POST", r"/internal/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/attr/diff", self.handle_field_attr_diff),
            Route("POST", r"/internal/fragment/hints", self.handle_post_hint_ops),
            Route("GET", r"/cdc/stream", self.handle_cdc_stream),
            Route("GET", r"/cdc/bootstrap", self.handle_cdc_bootstrap),
            Route("POST", r"/cdc/standing", self.handle_cdc_standing_register),
            Route("GET", r"/cdc/standing", self.handle_cdc_standing_list),
            Route("GET", r"/cdc/standing/(?P<sid>[^/]+)/poll", self.handle_cdc_standing_poll),
            Route("DELETE", r"/cdc/standing/(?P<sid>[^/]+)", self.handle_cdc_standing_delete),
            Route("POST", r"/geo/promote", self.handle_geo_promote),
            Route("POST", r"/geo/demote", self.handle_geo_demote),
            Route("GET", r"/geo/status", self.handle_geo_status),
            Route("GET", r"/debug/vars", self.handle_debug_vars),
            Route("GET", r"/debug/traces", self.handle_debug_traces),
            Route("GET", r"/metrics", self.handle_metrics),
            Route("POST", r"/debug/profile", self.handle_debug_profile),
            Route("GET", r"/debug/threads", self.handle_debug_threads),
            Route("GET", r"/internal/diagnostics", self.handle_diagnostics),
        ]

    def dispatch(self, method: str, path: str, query: Dict[str, List[str]], body: bytes,
                 headers: Optional[Dict[str, str]] = None):
        """Returns (status, content_type, payload_bytes) or the same plus
        an extra-response-headers dict (429 carries Retry-After)."""
        headers = {k.lower(): v for k, v in (headers or {}).items()}
        if self.internal_key and path.startswith("/internal/"):
            import hmac

            # compare_digest on BYTES: the shared secret must not leak
            # through comparison timing, and the str overload raises
            # TypeError on non-ASCII input (http.server decodes headers as
            # latin-1, so an arbitrary-byte header must not crash the
            # connection — it must 403).
            presented = headers.get("x-pilosa-key", "").encode("latin-1", "replace")
            if not hmac.compare_digest(presented, self.internal_key.encode()):
                return 403, "application/json", json.dumps(
                    {"error": "cluster key required"}
                ).encode()
        for route in self.routes:
            if route.method != method:
                continue
            m = route.regex.match(path)
            if m is None:
                continue
            try:
                start = time.monotonic()
                result = route.fn(query=query, body=body, headers=headers, **m.groupdict())
                elapsed = time.monotonic() - start
                lqt = getattr(self.api.server, "long_query_time", 0)
                if lqt and elapsed > lqt and self.logger:
                    self.logger.info("%s %s %.3fs > long-query-time", method, path, elapsed)
                if isinstance(result, tuple):
                    return result
                return 200, "application/json", json.dumps(result).encode()
            except PilosaError as e:
                from ..errors import FragmentNotFoundError
                from ..sched import DeadlineExceededError, QueueFullError

                if isinstance(e, QueueFullError):
                    # Load shed: tell the client WHEN to come back instead
                    # of letting it hammer a saturated queue (Retry-After
                    # is integer seconds per RFC 9110). A tenant-budget
                    # shed (TenantBudgetError) echoes the tenant so a
                    # multiplexing client can throttle ONE tenant's
                    # traffic instead of backing everything off.
                    import math

                    retry = str(max(1, math.ceil(e.retry_after)))
                    hdrs = {"Retry-After": retry}
                    tenant = getattr(e, "tenant", None)
                    if tenant is not None:
                        hdrs["X-Pilosa-Tenant"] = str(tenant)
                    return (429, "application/json",
                            json.dumps({"error": str(e)}).encode(),
                            hdrs)
                if isinstance(e, DeadlineExceededError):
                    # The budget ran out server-side; 503 (not 400) so
                    # clients/balancers treat it as overload, not a bad
                    # request.
                    return (503, "application/json",
                            json.dumps({"error": str(e)}).encode())
                from ..errors import WriteConsistencyError

                if isinstance(e, WriteConsistencyError):
                    # Degraded write path (too few live owners for the
                    # configured [replication] write-consistency level, or
                    # total owner loss): RETRYABLE 503, not a 400 — the
                    # request is fine, the cluster is degraded. The
                    # applied copies stand (no rollback) and hints were
                    # enqueued before this surfaced, so a client retry
                    # after Retry-After re-applies idempotent ops.
                    return (503, "application/json",
                            json.dumps({"error": str(e)}).encode(),
                            {"Retry-After": "1"})
                from ..errors import CdcGoneError

                if isinstance(e, CdcGoneError):
                    # Typed retention miss (docs/cdc.md): the cursor or
                    # at-position fell behind the change log's fold line,
                    # or the index was deleted+recreated (stale
                    # incarnation). 410 GONE — retrying the same cursor
                    # can never succeed; the body carries the retained
                    # window + live incarnation so the consumer re-seeds
                    # via /cdc/bootstrap instead of guessing.
                    payload = {"error": str(e)}
                    if e.first is not None:
                        payload["first"] = e.first
                    if e.last is not None:
                        payload["last"] = e.last
                    if e.incarnation is not None:
                        payload["incarnation"] = e.incarnation
                    return (410, "application/json",
                            json.dumps(payload).encode())
                from ..errors import ShardMovedError, StaleRoutingEpochError

                if isinstance(e, (ShardMovedError, StaleRoutingEpochError)):
                    # Routing conflict (live rebalance cutover): 409 tells
                    # the sender to re-route once on refreshed placement —
                    # distinct from 400 (deterministic rejection) and 5xx
                    # (node fault), neither of which should re-route.
                    return (409, "application/json",
                            json.dumps({"error": str(e)}).encode())
                from ..errors import StaleGeoEpochError, StaleReadError

                if isinstance(e, StaleReadError):
                    # Bounded-staleness refusal (docs/geo-replication.md):
                    # a geo follower's replication lag exceeds the
                    # request's X-Pilosa-Max-Staleness bound. 409 with
                    # the CURRENT lag so the client can choose — relax
                    # the bound and re-read here, or fail over to the
                    # leader. Never a silently-stale answer.
                    payload = {"error": str(e)}
                    if e.lag is not None:
                        payload["lag"] = (e.lag if e.lag != float("inf")
                                          else None)
                    if e.bound is not None:
                        payload["bound"] = e.bound
                    if e.position is not None:
                        payload["position"] = e.position
                    return (409, "application/json",
                            json.dumps(payload).encode())
                if isinstance(e, StaleGeoEpochError):
                    # Geo fence (split-brain guard): a write reached a
                    # follower, or a demote handshake presented an epoch
                    # this cluster is already fenced past. 409; a deposed
                    # leader demotes and re-tails, a client re-routes to
                    # the leader.
                    payload = {"error": str(e)}
                    if e.epoch is not None:
                        payload["epoch"] = e.epoch
                    if e.current is not None:
                        payload["current"] = e.current
                    return (409, "application/json",
                            json.dumps(payload).encode())
                # Missing fragments map to 404 so the anti-entropy client can
                # treat the replica as empty instead of failing the sync
                # (reference http/handler.go:776,984,1030).
                status = 404 if isinstance(e, FragmentNotFoundError) else 400
                return status, "application/json", json.dumps({"error": str(e)}).encode()
            except Exception as e:  # pragma: no cover - defensive
                if self.logger:
                    self.logger.error("handler error: %s", traceback.format_exc())
                return 500, "application/json", json.dumps({"error": str(e)}).encode()
        if path == "/index/" or re.match(r"^/index/[^/]+/query$", path):
            return 405, "text/plain", b"method not allowed"
        return 404, "application/json", json.dumps({"error": "not found"}).encode()

    # ---------------------------------------------------------------- CORS

    def cors_origin(self, origin: Optional[str]) -> Optional[str]:
        """The Access-Control-Allow-Origin value for a request, or None."""
        if not origin or not self.allowed_origins:
            return None
        if "*" in self.allowed_origins:
            return "*"
        return origin if origin in self.allowed_origins else None

    def preflight(self, origin: Optional[str]):
        """Handle an OPTIONS preflight. Returns (status, extra_headers)."""
        if not self.allowed_origins:
            return 405, {}
        headers = {
            "Access-Control-Allow-Methods": "GET, POST, DELETE, OPTIONS",
            "Access-Control-Allow-Headers": "Content-Type",
            "Vary": "Origin",
        }
        allow = self.cors_origin(origin)
        if allow:
            headers["Access-Control-Allow-Origin"] = allow
        return 200, headers

    # ------------------------------------------------------------- handlers

    def handle_home(self, **kw):
        return {"message": "pilosa-tpu server. Send queries to /index/{index}/query"}

    def handle_get_indexes(self, **kw):
        return {"indexes": self.api.schema()}

    def handle_get_schema(self, **kw):
        return {"indexes": self.api.schema()}

    def handle_get_index(self, index, **kw):
        for info in self.api.schema():
            if info["name"] == index:
                return info
        from ..errors import IndexNotFoundError

        raise IndexNotFoundError(index)

    def handle_post_index(self, index, body, **kw):
        opts = _json_body(body, default={}).get("options", {})
        return self.api.create_index(index, opts)

    def handle_delete_index(self, index, **kw):
        self.api.delete_index(index)
        return {}

    def handle_post_field(self, index, field, body, **kw):
        opts = _json_body(body, default={}).get("options", {})
        return self.api.create_field(index, field, opts)

    def handle_delete_field(self, index, field, **kw):
        self.api.delete_field(index, field)
        return {}

    def handle_post_import(self, index, field, body, headers=None, **kw):
        headers = headers or {}
        if "application/x-protobuf" in headers.get("content-type", ""):
            from . import proto
            from ..constants import FIELD_TYPE_INT

            fld = self.api.holder.field(index, field)
            if fld is not None and fld.type() == FIELD_TYPE_INT:
                req = proto.decode_import_value_request(body)
            else:
                req = proto.decode_import_request(body)
        else:
            req = _json_body(body)
        shard = req.get("shard", 0)

        def run():
            if "values" in req:
                self.api.import_values(
                    index, field, shard, req.get("columnIDs"), req["values"],
                    remote=req.get("remote", False),
                    column_keys=req.get("columnKeys"),
                )
            else:
                self.api.import_bits(
                    index, field, shard, req.get("rowIDs", []), req.get("columnIDs", []),
                    req.get("timestamps"), remote=req.get("remote", False),
                    row_keys=req.get("rowKeys"), column_keys=req.get("columnKeys"),
                )

        # Imports ride the scheduler's batch class — bounded concurrency
        # keeps bulk loads from starving interactive queries of executor
        # slots, and a full queue sheds with 429 backpressure. Admission
        # happens HERE (not inside import_bits) because key-mode imports
        # recurse per shard; admitting inside the recursion would nest
        # slot acquisitions and self-deadlock at low concurrency limits.
        # Replication forwards (remote=True) and key-mode imports
        # forwarded to the translation primary (X-Pilosa-Forwarded; the
        # body can't say remote:true because the primary must run its own
        # owner fan-out) skip admission for the same reason remote
        # queries do: the originating node already admitted the work, and
        # nodes holding batch slots while blocked in each other's
        # admission queues would deadlock the write path.
        scheduler = getattr(self.api.server, "scheduler", None)
        forwarded = (headers or {}).get("x-pilosa-forwarded") == "1"
        if forwarded and self.internal_key:
            # On a keyed cluster, only an authenticated peer may claim
            # "already admitted" — otherwise any public client could strap
            # the header onto bulk imports and bypass batch-class shedding.
            # (Open clusters trust it, matching the trust model of the
            # equally-spoofable remote flag in the body.)
            import hmac

            presented = (headers or {}).get(
                "x-pilosa-key", "").encode("latin-1", "replace")
            forwarded = hmac.compare_digest(
                presented, self.internal_key.encode())
        if scheduler is None or req.get("remote") or forwarded:
            run()
        else:
            from ..sched import CLASS_BATCH

            # Imports charge the tenant's budget too (X-Pilosa-Tenant,
            # default: index) — bulk-load device time is exactly the
            # noisy-tenant cost the ledger exists to bound. Batch class
            # sheds FIRST when the bucket runs dry (docs/scheduler.md).
            tenant = (headers or {}).get("x-pilosa-tenant") or index
            with scheduler.admit(CLASS_BATCH, tenant=tenant):
                run()
        return {}

    def handle_post_query(self, index, body, query, headers=None, **kw):
        headers = headers or {}
        wants_proto = "application/x-protobuf" in headers.get("accept", "")
        is_proto = "application/x-protobuf" in headers.get("content-type", "")
        shards = None
        # Per-request budget: X-Pilosa-Deadline carries REMAINING seconds
        # (coordinators forward their leftover budget to peers); absent,
        # the scheduler's configured default applies.
        scheduler = getattr(self.api.server, "scheduler", None)
        deadline = None
        if scheduler is not None:
            deadline = scheduler.deadline_for(headers.get("x-pilosa-deadline"))
        # Sender's routing epoch (live rebalance): lets this node detect a
        # forwarded request routed under a placement older than its own.
        epoch = None
        raw_epoch = headers.get("x-pilosa-epoch")
        if raw_epoch:
            try:
                epoch = int(raw_epoch)
            except ValueError:
                epoch = None
        # Point-in-time read (docs/cdc.md): execute against the index as
        # of this CDC position instead of live storage. Also accepted as
        # ?atPosition= for clients that can't set headers.
        at_position = None
        raw_at = headers.get("x-pilosa-at-position") or \
            query.get("atPosition", [None])[0]
        if raw_at:
            try:
                at_position = int(raw_at)
            except ValueError:
                raise PilosaError(
                    f"invalid at-position value: {raw_at!r}") from None
        # Bounded-staleness read (docs/geo-replication.md): on a geo
        # follower, answer from local state only when replication lag is
        # within this many seconds, else 409 with the current lag. On a
        # leader or non-geo node the header is a clean no-op — local
        # state is the source of truth, never stale.
        max_staleness = None
        raw_stale = headers.get("x-pilosa-max-staleness")
        if raw_stale:
            try:
                max_staleness = float(raw_stale)
            except ValueError:
                raise PilosaError(
                    f"invalid max-staleness value: {raw_stale!r}") from None
            if max_staleness < 0:
                raise PilosaError(
                    f"invalid max-staleness value: {raw_stale!r}")
        # QoS tenant identity (docs/scheduler.md): budget charging and
        # SLO-classed shedding key on this. Defaults (in api.query) to
        # the index name so single-tenant deployments need no header.
        tenant = headers.get("x-pilosa-tenant") or None
        remote = query.get("remote", ["false"])[0] == "true"
        column_attrs = query.get("columnAttrs", ["false"])[0] == "true"
        exclude_row_attrs = query.get("excludeRowAttrs", ["false"])[0] == "true"
        exclude_columns = query.get("excludeColumns", ["false"])[0] == "true"

        if is_proto:
            from . import proto

            req = proto.decode_query_request(body)
            pql = req["query"]
            shards = req["shards"]
            remote = remote or req["remote"]
            column_attrs = column_attrs or req["columnAttrs"]
            exclude_row_attrs = exclude_row_attrs or req["excludeRowAttrs"]
            exclude_columns = exclude_columns or req["excludeColumns"]
        else:
            body_text = body.decode() if body else ""
            if body_text.startswith("{"):
                req = _json_body(body)
                pql = req.get("query", "")
                shards = req.get("shards")
            else:
                pql = body_text
        if "shards" in query:
            shards = [int(s) for s in query["shards"][0].split(",")]

        # Per-query tracing (docs/observability.md): adopt the
        # coordinator's trace id from X-Pilosa-Trace (stamped next to the
        # deadline/epoch headers) so this node's spans splice into ONE
        # cross-node tree, else roll the ingress sampler. Downstream
        # stages record through the obs contextvar; the trace lands in
        # the /debug/traces ring (and the slow-query log) at finish.
        from .. import obs as _obs

        recorder = getattr(self.api.server, "trace_recorder", None)
        trace = None
        if recorder is not None:
            trace_hdr = headers.get("x-pilosa-trace")
            if trace_hdr and remote:
                # Adoption is for coordinator-forwarded sub-queries ONLY
                # (remote=true): they bypass the local sampler because
                # the coordinator already rolled it. An ordinary client
                # stamping the header must not force tracing on a node
                # whose operator set sample-rate 0 — the knob's whole
                # point is bounding overhead and /debug/traces retention.
                trace = recorder.adopt(trace_hdr, index=index, pql=pql)
            elif not remote:
                trace = recorder.maybe_start(index=index, pql=pql)
        if trace is None:
            return self._post_query_traced(
                index, pql, shards, remote, column_attrs, exclude_row_attrs,
                exclude_columns, deadline, epoch, wants_proto, headers,
                None, None, at_position, max_staleness, tenant)
        token = _obs.activate(trace)
        status = "ok"
        try:
            # The root of the request's span tree: every other span
            # descends from it, and its self time is what no stage
            # accounts for (HTTP framing, JSON, result encoding).
            with trace.span("request"):
                return self._post_query_traced(
                    index, pql, shards, remote, column_attrs,
                    exclude_row_attrs, exclude_columns, deadline, epoch,
                    wants_proto, headers, recorder, trace, at_position,
                    max_staleness, tenant)
        except BaseException:
            status = "error"
            raise
        finally:
            _obs.deactivate(token)
            recorder.finish(trace, status=status)

    def _post_query_traced(self, index, pql, shards, remote, column_attrs,
                           exclude_row_attrs, exclude_columns, deadline,
                           epoch, wants_proto, headers, recorder, trace,
                           at_position=None, max_staleness=None, tenant=None):
        if wants_proto:
            from . import proto
            from ..errors import PilosaError

            try:
                results = self.api.query(
                    index, pql, shards=shards, remote=remote,
                    exclude_row_attrs=exclude_row_attrs,
                    exclude_columns=exclude_columns,
                    deadline=deadline,
                    at_position=at_position,
                    max_staleness=max_staleness,
                    tenant=tenant,
                )
            except PilosaError as e:
                from ..sched import DeadlineExceededError, QueueFullError

                if isinstance(e, (QueueFullError, DeadlineExceededError)):
                    raise  # keep 429/503 semantics over a proto 400
                return 400, "application/x-protobuf", proto.encode_query_response([], err=str(e))
            cas = None
            if column_attrs:
                cas = self._column_attr_sets(index, results)
            payload = proto.encode_query_response(results, cas)
            return 200, "application/x-protobuf", payload

        if remote:
            results = self.api.query(index, pql, shards=shards, remote=True,
                                     deadline=deadline, epoch=epoch,
                                     at_position=at_position,
                                     max_staleness=max_staleness,
                                     tenant=tenant)
            from . import wire

            extra = {}
            if trace is not None:
                # The peer side of cross-node splicing: finish THIS node's
                # trace now (all spans are complete — the query returned)
                # and return its stage summary, size-bounded, so the
                # coordinator attaches it as child spans of its
                # remote:<peer> span. finish() is idempotent; the
                # handler's finally only re-lands errors. The root span
                # ends first (the query returned, so it is the one span
                # still open here), or the summary would lack it.
                from ..obs.trace import SUMMARY_MAX_BYTES, current_span

                current_span().close()
                recorder.finish(trace)

                extra["X-Pilosa-Trace-Summary"] = trace.summary_header(
                    SUMMARY_MAX_BYTES)
            if wire.CONTENT_TYPE in headers.get("accept", ""):
                # Binary data plane: packed bitplanes instead of JSON column
                # lists (a dense 1M-column Row is 128KiB, not ~10MB).
                return 200, wire.CONTENT_TYPE, wire.encode_results(results), extra
            return (200, "application/json",
                    json.dumps({"results": [serialize_remote(r)
                                            for r in results]}).encode(),
                    extra)
        return self.api.query_response(
            index, pql, shards=shards, column_attrs=column_attrs,
            exclude_row_attrs=exclude_row_attrs, exclude_columns=exclude_columns,
            deadline=deadline, at_position=at_position,
            max_staleness=max_staleness, tenant=tenant,
        )

    def _column_attr_sets(self, index, results):
        cols = set()
        for r in results:
            if isinstance(r, Row):
                cols.update(int(c) for c in r.columns())
        idx = self.api.holder.index(index)
        out = []
        for col in sorted(cols):
            a = idx.column_attr_store.attrs(col)
            if a:
                out.append({"id": col, "attrs": a})
        return out

    def handle_get_export(self, query, **kw):
        index = query["index"][0]
        field = query["field"][0]
        shard = int(query["shard"][0])
        csv = self.api.export_csv(index, field, shard)
        return 200, "text/csv", csv.encode()

    def handle_get_status(self, **kw):
        return self.api.status()

    def handle_get_info(self, **kw):
        return self.api.info()

    def handle_get_version(self, **kw):
        from .. import __version__

        return {"version": __version__}

    def handle_recalculate_caches(self, **kw):
        self.api.recalculate_caches()
        return {}

    def handle_resize_abort(self, **kw):
        self.api.server.resize_abort()
        return {}

    def handle_remove_node(self, body, **kw):
        req = _json_body(body, default={})
        self.api.remove_node(req.get("id", ""))
        return {}

    def handle_set_coordinator(self, body, **kw):
        req = _json_body(body, default={})
        self.api.set_coordinator(req.get("id", ""))
        return {}

    def handle_cluster_message(self, body, headers=None, **kw):
        """Cluster envelope receive: protobuf type-byte envelope on
        Content-Type: application/x-protobuf (the reference's only wire
        format, broadcast.go:116-162), JSON otherwise (debug fallback)."""
        ctype = (headers or {}).get("content-type", "")
        if "protobuf" in ctype:
            from .proto import envelope

            self.api.cluster_message(envelope.decode_message(body))
        else:
            self.api.cluster_message(_json_body(body))
        return {}

    def handle_collective_count(self, body, **kw):
        data = _json_body(body)
        return {
            "count": self.api.collective_count(
                data["index"], data["field"], data.get("rows", [])
            )
        }

    def handle_fragment_blocks(self, query, **kw):
        # view is optional for reference parity (its RPC has no view param);
        # absent means standard.
        view = query.get("view", ["standard"])[0]
        return {
            "blocks": self.api.fragment_blocks(
                query["index"][0], query["field"][0], int(query["shard"][0]),
                view=view,
            )
        }

    def handle_fragment_block_data(self, query, **kw):
        return self.api.fragment_block_data(
            query["index"][0], query["field"][0], query["view"][0],
            int(query["shard"][0]), int(query["block"][0]),
        )

    def handle_post_hint_ops(self, query, body, **kw):
        """Hinted-handoff delivery (cluster/hints.py): the body is a raw
        run of storage/bitmap.py WAL op records for one fragment."""
        self.api.apply_hint_ops(
            query["index"][0], query["field"][0], query["view"][0],
            int(query["shard"][0]), body,
        )
        return {}

    # ------------------------------------------------------------------ cdc

    def handle_cdc_stream(self, query, **kw):
        """GET /cdc/stream?index=X&from=P — one long-poll chunk of the
        change stream: raw framed op records (cdc/log.py framing — the
        response bytes are byte-identical to the on-disk log slice) for
        positions > P. X-Pilosa-Cdc-Next is the cursor for the next
        request; X-Pilosa-Cdc-Incarnation pins the index generation
        (pass it back as &incarnation= to get a 410 instead of silent
        aliasing after a delete+recreate). Empty body = timeout with no
        new records (re-poll from the same cursor)."""
        if "index" not in query:
            raise PilosaError("index parameter required")
        index = query["index"][0]
        try:
            from_pos = int(query.get("from", ["0"])[0])
            timeout = (float(query["timeout"][0]) if "timeout" in query
                       else None)
            max_bytes = int(query.get("max-bytes", [str(4 << 20)])[0])
        except ValueError as e:
            raise PilosaError(f"invalid /cdc/stream parameter: {e}") from None
        inc = query.get("incarnation", [None])[0]
        data, nxt, incarnation = self.api.cdc_stream(
            index, from_pos, incarnation=inc, timeout=timeout,
            max_bytes=max_bytes)
        # Lag anchors for geo followers (docs/geo-replication.md): the
        # newest assigned position and THIS node's wall clock, read
        # together, so the consumer computes staleness entirely from
        # leader-side times (its own clock never enters the formula).
        head_pos, head_time = self.api.server.cdc.head(index)
        return (200, "application/octet-stream", data,
                {"X-Pilosa-Cdc-Next": str(nxt),
                 "X-Pilosa-Cdc-Incarnation": incarnation,
                 "X-Pilosa-Cdc-Head-Pos": str(head_pos),
                 "X-Pilosa-Cdc-Head-Time": repr(head_time)})

    def handle_cdc_bootstrap(self, query, **kw):
        """GET /cdc/bootstrap?index=X — snapshot re-seed for a consumer
        whose cursor 410'd: zlib-compressed base64 roaring images per
        fragment plus the position each was cut at. Resume the stream
        from the returned `from`; overlap replays idempotently."""
        if "index" not in query:
            raise PilosaError("index parameter required")
        return self.api.cdc_bootstrap(query["index"][0])

    def handle_cdc_standing_register(self, body, **kw):
        req = _json_body(body)
        index = req.get("index", "")
        pql = req.get("query", "")
        if not index or not pql:
            raise PilosaError("index and query fields required")
        return self.api.cdc_standing_register(index, pql)

    def handle_cdc_standing_list(self, **kw):
        return self.api.cdc_standing_list()

    def handle_cdc_standing_poll(self, sid, query, **kw):
        try:
            after = int(query.get("version", ["0"])[0])
            timeout = (float(query["timeout"][0]) if "timeout" in query
                       else None)
        except ValueError as e:
            raise PilosaError(
                f"invalid /cdc/standing poll parameter: {e}") from None
        return self.api.cdc_standing_poll(sid, after, timeout)

    def handle_cdc_standing_delete(self, sid, **kw):
        self.api.cdc_standing_delete(sid)
        return {}

    # ------------------------------------------------------------------ geo

    def handle_geo_promote(self, **kw):
        """POST /geo/promote — operator-initiated leader-loss promotion
        (docs/geo-replication.md): this follower becomes the leader
        under a bumped fencing geo epoch. Idempotent on a leader."""
        return self.api.geo_promote()

    def handle_geo_demote(self, body, **kw):
        """POST /geo/demote {"leader": uri, "epoch": n} — the fencing
        handshake: re-tail `leader` under the authoritative epoch, or
        409 when already fenced at or past it."""
        req = _json_body(body)
        leader = req.get("leader")
        if not leader:
            raise PilosaError("leader required")
        try:
            epoch = int(req["epoch"])
        except (KeyError, TypeError, ValueError):
            raise PilosaError("valid epoch required") from None
        return self.api.geo_demote(leader, epoch)

    def handle_geo_status(self, **kw):
        return self.api.geo_status()

    def handle_post_block_data(self, query, body, **kw):
        data = _json_body(body)
        self.api.apply_block_diff(
            query["index"][0], query["field"][0], query["view"][0],
            int(query["shard"][0]),
            data.get("sets", []), data.get("clears", []),
        )
        return {}

    def handle_fragment_nodes(self, query, **kw):
        index = query["index"][0]
        shard = int(query["shard"][0])
        return [n.to_dict() for n in self.api.cluster.shard_nodes(index, shard)]

    def handle_fragment_data(self, query, **kw):
        """Stream a fragment's storage for shard relocation (resize)."""
        import io

        frag = self.api.holder.fragment(
            query["index"][0], query["field"][0], query["view"][0], int(query["shard"][0])
        )
        if frag is None:
            from ..errors import FragmentNotFoundError

            raise FragmentNotFoundError("fragment not found")
        if frag.quarantined:
            # Serving a quarantined fragment's (empty, degraded) storage as
            # the real shard would let a resize install the empty copy and
            # then garbage-collect the healthy replicas — permanent loss.
            # Erroring makes the resize abort/pick another source and makes
            # a repairing peer try the next replica.
            from ..errors import PilosaError

            raise PilosaError(
                "fragment is quarantined pending repair; refusing to serve "
                "as a shard source"
            )
        buf = io.BytesIO()
        frag.write_to(buf)
        return 200, "application/octet-stream", buf.getvalue()

    def handle_post_fragment_data(self, query, body, **kw):
        import io

        holder = self.api.holder
        index, field = query["index"][0], query["field"][0]
        view, shard = query["view"][0], int(query["shard"][0])
        fld = holder.field(index, field)
        v = fld.create_view_if_not_exists(view)
        frag = v.create_fragment_if_not_exists(shard)
        frag.read_from(io.BytesIO(body))
        return {}

    def handle_migrate_begin(self, body, **kw):
        """Open a live-migration stream for one fragment: the response is
        a binary frame (header json + raw base bytes, cluster/rebalance.py
        framing) so a multi-MiB fragment base never rides base64."""
        from ..cluster.rebalance import pack_framed

        req = _json_body(body)
        hdr, data = self.api.server.migration_source.begin(
            req["index"], req["field"], req["view"], int(req["shard"]))
        return 200, "application/octet-stream", pack_framed(hdr, data)

    def handle_migrate_delta(self, body, **kw):
        from ..cluster.rebalance import pack_framed

        req = _json_body(body)
        hdr, data = self.api.server.migration_source.delta(
            req["session"], from_pos=req.get("from"))
        return 200, "application/octet-stream", pack_framed(hdr, data)

    def handle_migrate_freeze(self, body, **kw):
        req = _json_body(body)
        return self.api.server.migration_source.freeze(
            req["index"], int(req["shard"]))

    def handle_migrate_close(self, body, **kw):
        req = _json_body(body)
        self.api.server.migration_source.close(req.get("sessions", []))
        return {}

    def handle_shards_max(self, **kw):
        return {"standard": self.api.shards_max()}

    def handle_translate_data(self, query, **kw):
        offset = int(query.get("offset", ["0"])[0])
        return 200, "application/octet-stream", self.api.translate_data(offset)

    def handle_debug_vars(self, **kw):
        """expvar equivalent (reference mounts /debug/vars,
        http/handler.go:196): stats counters/gauges/timings as JSON, plus
        the device engine's cache hit/eviction counters."""
        stats = self.api.server.stats
        out = stats.snapshot() if hasattr(stats, "snapshot") else {}
        engine = getattr(getattr(self.api, "executor", None), "engine", None)
        if engine is not None:
            out = dict(out)
            # What the engine runs on, as JAX reports it: a server on the
            # CPU backend answers every query correctly, so this group is
            # the only place the difference shows.
            out["device"] = engine.device_info()
            engine_cache = engine.snapshot()
            out["engine_cache"] = engine_cache
            # Delta-refresh health pulled out as its own group: the on-call
            # question under mixed read/write traffic is "are writes
            # costing scattered KiB updates or full plane re-uploads", and
            # that should not require knowing the counter-dict layout.
            # Derived from the one locked snapshot above so the two groups
            # can never disagree within a single response.
            out["delta_refresh"] = {
                k: engine_cache.get(k, 0)
                for k in ("leaf_delta_hits", "stack_delta_hits",
                          "delta_bytes", "full_refresh_bytes")
            }
            # Effective cache bounds after env > [engine] > [tier] >
            # platform-default resolution — the knobs are spread across
            # three config surfaces, so a deployment must be able to SEE
            # what they resolved to without reading the resolution code.
            out["engine_budgets"] = dict(engine.budgets)
            # Tiered-storage health (docs/tiered-storage.md): per-tier
            # bytes/entries plus promotion/demotion/prefetch/delta-fold
            # counters — the on-call question under HBM pressure is "are
            # evictions coming back as sub-ms promotions or full
            # regathers" (leaf_tier_hits vs leaf_misses above answers the
            # other half).
            if engine.tier is not None:
                out["tier"] = engine.tier.snapshot()
            # Device-plane fault health (docs/fault-tolerance.md): breaker
            # states, classified dispatch failures, and the host-ladder
            # counters from engine_cache above — the on-call question
            # during a device incident is "is the plane breaker open, and
            # are queries being answered from the host ladder or erroring".
            out["device_plane"] = engine.device_health.snapshot()
        # Query-plan compiler health (docs/query-compiler.md):
        # canonical lowerings vs on-Call cache hits plus the
        # canonicalization effect counters (reorders / k-ary flattens).
        # Module-level: the plan compiler serves every engine in the
        # process.
        from ..plan import snapshot as _plan_snapshot

        out = dict(out)
        out["plan"] = _plan_snapshot()
        # Whether the C++ host kernels loaded (native/__init__.py): every
        # entry point has a numpy fallback, so nothing else shows it.
        from .. import native as _native

        out["native"] = {"loaded": _native.available()}
        # What the whole process spent (obs/host.py): CPU seconds of every
        # thread, the collector's runs and seconds. Beside
        # `scheduler.admitted` they are CPU and collector time an answer.
        host_meter = getattr(self.api.server, "host_meter", None)
        if host_meter is not None:
            out["host"] = host_meter.snapshot()
        # Scheduler lifecycle metrics: queue depth, admit/shed/deadline
        # counts, and the micro-batcher's launch/coalesce counters (wait
        # time and batch-size histograms live in the stats timings above).
        scheduler = getattr(self.api.server, "scheduler", None)
        if scheduler is not None:
            out = dict(out)
            out["scheduler"] = scheduler.snapshot()
        batcher = getattr(self.api.server, "batcher", None)
        if batcher is not None:
            out = dict(out)
            out["batcher"] = batcher.snapshot()
        # Multi-tenant QoS health (docs/scheduler.md "Tenant budgets"):
        # per-tenant balances/debt/mean cost plus charge/shed/defer
        # counters — the on-call question during a noisy-neighbor event
        # is "which tenant is over budget, and is it being shed or just
        # deferred behind in-budget traffic".
        qos = getattr(self.api.server, "qos", None)
        if qos is not None:
            out = dict(out)
            out["qos"] = qos.snapshot()
        # Autoscaler health (docs/rebalance.md "Autoscaling"): the sample
        # window, last decision, scale/skip counters, and which nodes the
        # controller added — the on-call question is "why did (or didn't)
        # the cluster scale, and what does the controller think the load
        # is".
        autoscaler = getattr(self.api.server, "autoscaler", None)
        if autoscaler is not None:
            out = dict(out)
            out["autoscale"] = autoscaler.snapshot()
        # Crash-safety health: which fragments are serving degraded
        # (quarantined at open, repair pending), how often queries touched
        # one, and any armed failpoints (nonempty only under fault tests).
        quarantined = self.api.holder.quarantined_fragments()
        executor = getattr(self.api, "executor", None)
        out = dict(out)
        out["storage"] = {
            "quarantined": [
                {
                    "index": f.index, "field": f.field, "view": f.view,
                    "shard": f.shard, "reason": f.quarantine_reason,
                }
                for f in quarantined
            ],
            "quarantined_reads": getattr(executor, "quarantined_reads", 0),
        }
        # Shard-list placement kept across queries (executor._shard_owners):
        # a read query is a hit; a walk is a placement worked out shard by
        # shard, once per shard list per topology and on every assignment
        # while a rebalance is in flight.
        if executor is not None:
            out["executor"] = {
                "assign_hits": executor.assign_hits,
                "assign_walks": executor.assign_walks,
                # Batched TopN runner calls answered on arrays, and shards
                # such a runner handed to the per-shard rung instead.
                "topn_array_walks": executor.topn_array_walks,
                "topn_shard_replays": executor.topn_shard_replays,
                # The candidate phase of filtered TopNs: calls answered,
                # device programs (chunks) launched, rows in them.
                "topn_queries": executor.topn_queries,
                "topn_chunks": executor.topn_chunks,
                "topn_candidate_rows": executor.topn_candidate_rows,
                # Rank-cache rebuilds (a write drops a fragment's ranking;
                # the next reader ranks it again) and the rows they ranked.
                "rank_rebuilds": cache_mod.rank_rebuilds,
                "rank_rows_sorted": cache_mod.rank_rows_sorted,
            }
        # Ingest health (docs/ingest.md): un-snapshotted WAL bytes across
        # fragments, background-snapshot counters and queue depth, and how
        # many shard batches the import surface has applied/routed — the
        # on-call question under heavy ingest is "are snapshots keeping up
        # with the write rate" (wal_bytes climbing without bound means no).
        ingest = self.api.holder.ingest_stats() if hasattr(
            self.api.holder, "ingest_stats") else {}
        ingest["import_batches"] = getattr(self.api, "import_batches", 0)
        out["ingest"] = ingest
        # Peer fault-tolerance health: per-peer breaker states plus the
        # breaker/retry/hedge counters — the evidence for "a blackholed
        # peer costs zero connect attempts between half-open probes" and
        # "replica retries stayed inside the budget".
        out["resilience"] = self.api.server.cluster.health.snapshot()
        # Collective-plane health (docs/multichip.md): served/batched
        # counts, fallbacks BY REASON, barrier timeouts, resident-stack
        # hit/delta/eviction counters, and the plane/slice breaker states
        # — the on-call question when full-index qps drops is "did the
        # fast path stop serving, and WHY did it refuse".
        coll = getattr(self.api.server, "collective", None)
        if coll is not None:
            out["collective"] = coll.snapshot()
        # Live-rebalance health (docs/rebalance.md): fragments moved vs
        # pending, bytes streamed, catch-up rounds, cutover write-pause
        # percentiles, and the routing epoch — the on-call question during
        # an elastic resize is "is the migration making progress, and what
        # did cutovers cost the write path".
        stats = getattr(self.api.server, "rebalance_stats", None)
        if stats is not None:
            cluster = self.api.server.cluster
            rb = stats.snapshot()
            rb["epoch"] = cluster.routing_epoch
            rb["active"] = cluster.next_nodes is not None
            rb["migrated_shards"] = len(cluster.migrated)
            out["rebalance"] = rb
        # Durable write replication (docs/durability.md "Write-path
        # consistency"): configured ack level, per-peer pending hint
        # backlog, append/deliver/expire counters — the on-call question
        # after a replica outage is "are the missed writes queued and
        # draining, or waiting on the anti-entropy backstop".
        hints = getattr(self.api.server, "hints", None)
        if hints is not None:
            out["replication"] = hints.snapshot()
        # CDC health (docs/cdc.md): per-index position window + retention
        # counters, PIT cache hit rate, standing-query eval/push/stale
        # totals — the on-call question for a lagging consumer is "did my
        # cursor fall behind the fold line, and how fast is it moving".
        cdc = getattr(self.api.server, "cdc", None)
        if cdc is not None:
            out["cdc"] = cdc.debug_vars()
        # Geo replication (docs/geo-replication.md): role/epoch, per-link
        # tail positions + lag, breaker state, promotion/demotion/fence
        # counters — the on-call question is "how far behind is this
        # follower, and who holds the fencing epoch".
        geo = getattr(self.api.server, "geo", None)
        if geo is not None:
            out["geo"] = geo.debug_vars()
        # pmux internal transport (docs/transport.md): connection churn,
        # frame/byte totals, handshake fallbacks, inflight high-water —
        # the on-call question after flipping [transport] on is "are
        # hops actually riding the mux, and is any peer demoted to
        # HTTP". Always present (the stats object exists even when
        # disabled) so dashboards need no conditional.
        tstats = getattr(self.api.server, "transport_stats", None)
        if tstats is not None:
            tr = tstats.snapshot()
            tcfg = getattr(self.api.server, "transport_config", None)
            tr["enabled"] = bool(tcfg.enabled) if tcfg is not None else False
            mux_t = getattr(self.api.server, "mux_transport", None)
            if mux_t is not None:
                tr.update(mux_t.snapshot())
            mux_s = getattr(self.api.server, "mux_server", None)
            if mux_s is not None:
                tr["server"] = mux_s.snapshot()
            out["transport"] = tr
        # Per-query tracing health (docs/observability.md): sampler
        # counters, ring depth, slow-query count — the aggregate next to
        # the per-trace detail /debug/traces serves.
        recorder = getattr(self.api.server, "trace_recorder", None)
        if recorder is not None:
            out["obs"] = recorder.snapshot()
        from .. import failpoints as _fp

        if _fp.active():
            out["failpoints"] = _fp.active()
        return out

    def handle_debug_traces(self, query, **kw):
        """Completed per-query traces from the recorder's bounded ring,
        newest first. Filters: ?min-ms= (minimum duration), ?index=,
        ?limit= (default 64). Each trace is the FULL cross-node tree the
        coordinator assembled (remote hops carry the peer's spliced child
        spans)."""
        recorder = getattr(self.api.server, "trace_recorder", None)
        if recorder is None:
            return {"traces": []}
        try:
            min_ms = float(query.get("min-ms", ["0"])[0])
            limit = int(query.get("limit", ["64"])[0])
        except ValueError as e:
            # Malformed operator input is a 400, not a 500 traceback.
            raise PilosaError(f"invalid /debug/traces parameter: {e}") from None
        index = query.get("index", [None])[0]
        return {"traces": recorder.traces(min_ms=min_ms, index=index,
                                          limit=limit)}

    def handle_metrics(self, **kw):
        """Prometheus text exposition: the /debug/vars counter groups
        (same dict — the two surfaces cannot disagree) plus the trace
        recorder's per-stage latency histograms, so the node is
        scrapeable without custom tooling."""
        from ..obs import metrics as _metrics

        out = self.handle_debug_vars()
        recorder = getattr(self.api.server, "trace_recorder", None)
        hists = recorder.stage_histograms() if recorder is not None else {}
        text = _metrics.render_prometheus(out, hists)
        return 200, _metrics.CONTENT_TYPE, text.encode()

    _profile_lock = threading.Lock()

    def handle_debug_profile(self, query, **kw):
        """Capture a JAX profiler trace (the pprof-equivalent for the
        device hot path). POST /debug/profile?seconds=2 writes a trace
        under <data_dir>/profiles and answers its path and the capture's
        bounds on the host's two clocks (`time.time()` and
        `time.monotonic()`, seconds). The profiler's Python tracer is OFF
        unless `?python=1` asks for it: it slows the server several times
        over, and a capture must not slow what it measures. While the
        capture runs every request span is also an annotation in the
        profiler's trace (docs/observability.md). The profiler is
        process-global: concurrent captures are rejected with 409."""
        import os
        import uuid

        import jax

        from ..obs import trace as obs_trace

        seconds = min(max(float(query.get("seconds", ["1"])[0]), 0.0), 30.0)
        python = query.get("python", ["0"])[0] in ("1", "true")
        if not self._profile_lock.acquire(blocking=False):
            return 409, "application/json", json.dumps(
                {"error": "a profile capture is already running"}
            ).encode()
        try:
            base = self.api.server.data_dir or "/tmp"
            out = os.path.join(base, "profiles",
                               f"{int(time.time())}-{uuid.uuid4().hex[:6]}")
            os.makedirs(out, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = int(python)
            jax.profiler.start_trace(out, profiler_options=options)
            bounds = {"started_wall": time.time(),
                      "started_mono": time.monotonic()}
            try:
                obs_trace.capture_began()
                # pilint: allow-blocking(the sleep IS the capture window; _profile_lock is a try-acquire busy flag — contenders 409 instead of waiting, so nothing can queue behind this)
                time.sleep(seconds)
            finally:
                obs_trace.capture_ended()
                bounds["stopped_wall"] = time.time()
                bounds["stopped_mono"] = time.monotonic()
                jax.profiler.stop_trace()
        finally:
            self._profile_lock.release()
        return {"path": out, "python_tracer": python, **bounds}

    def handle_debug_threads(self, **kw):
        """Stack dump of every live Python thread — the goroutine-dump half
        of the reference's /debug/pprof mount (http/handler.go:195). A hung
        monitor or a stuck device dispatch shows up here without attaching
        a debugger to the live node."""
        import sys
        import traceback

        frames = sys._current_frames()
        names = {t.ident: t for t in threading.enumerate()}
        out = {}
        for ident, frame in frames.items():
            t = names.get(ident)
            # The ident keeps duplicate-named threads distinct (multiple
            # in-process nodes each run a 'collective-runner' etc.).
            label = (
                f"{t.name}-{ident} ({'daemon' if t.daemon else 'thread'})"
                if t else f"thread-{ident}"
            )
            out[label] = traceback.format_stack(frame)
        return {"threads": out, "count": len(out)}

    def handle_diagnostics(self, **kw):
        return self.api.server.diagnostics.gather()

    def handle_index_attr_diff(self, index, body, **kw):
        req = _json_body(body)
        attrs = self.api.attr_diff(index, None, req.get("blocks", []))
        return {"attrs": {str(k): v for k, v in attrs.items()}}

    def handle_field_attr_diff(self, index, field, body, **kw):
        req = _json_body(body)
        attrs = self.api.attr_diff(index, field, req.get("blocks", []))
        return {"attrs": {str(k): v for k, v in attrs.items()}}


class _RequestHandler(BaseHTTPRequestHandler):
    handler: Handler = None  # set by serve()
    protocol_version = "HTTP/1.1"
    # Nagle off (StreamRequestHandler.setup reads this): the response is
    # written as several small sends, and with Nagle on a keep-alive
    # client stalls ~40ms per request on the delayed-ACK interaction.
    disable_nagle_algorithm = True
    # Idle keep-alive read timeout: without it every silent client pins a
    # handler thread in readline() forever (handle_one_request maps a
    # socket timeout to close_connection). Clients bound their reuse to
    # well under this (InternalClient.IDLE_REUSE_S).
    timeout = 60

    def _do(self, method: str):
        parsed = urlparse(self.path)
        length = int(self.headers.get("Content-Length") or 0)
        body = self.rfile.read(length) if length else b""
        result = self.handler.dispatch(
            method, parsed.path.rstrip("/") or "/", parse_qs(parsed.query), body,
            headers=dict(self.headers),
        )
        extra_headers = {}
        if len(result) == 4:
            status, ctype, payload, extra_headers = result
        else:
            status, ctype, payload = result
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(payload)))
        for k, v in extra_headers.items():
            self.send_header(k, v)
        if self.handler.allowed_origins:
            # The ACAO value varies with the request Origin; shared caches
            # must not serve one origin's response to another.
            self.send_header("Vary", "Origin")
            allow = self.handler.cors_origin(self.headers.get("Origin"))
            if allow:
                self.send_header("Access-Control-Allow-Origin", allow)
        self.end_headers()
        self.wfile.write(payload)

    def do_GET(self):
        self._do("GET")

    def do_POST(self):
        self._do("POST")

    def do_DELETE(self):
        self._do("DELETE")

    def do_OPTIONS(self):
        status, headers = self.handler.preflight(self.headers.get("Origin"))
        self.send_response(status)
        for k, v in headers.items():
            self.send_header(k, v)
        self.send_header("Content-Length", "0")
        self.end_headers()

    def log_message(self, fmt, *args):  # silence default stderr logging
        pass


class _Server(ThreadingHTTPServer):
    # The stdlib default backlog of 5 drops (RSTs) connections under
    # concurrent load — 16 clients opening sockets faster than the accept
    # loop drains them is routine for a serving benchmark, let alone
    # production. Match Go's effective unbounded accept behavior closely
    # enough that the OS queue, not the library, is the limit.
    request_queue_size = 128
    daemon_threads = True

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # Live per-connection sockets: keep-alive means a handler thread
        # can sit in readline() long after the listener closes, so
        # server_close must SEVER established connections too (Go's
        # http.Server.Close semantics) — otherwise an in-process "dead"
        # node keeps answering its pooled peers forever.
        self._live = set()
        self._live_mu = threading.Lock()

    def process_request(self, request, client_address):
        with self._live_mu:
            self._live.add(request)
        super().process_request(request, client_address)

    def close_request(self, request):
        with self._live_mu:
            self._live.discard(request)
        super().close_request(request)

    def server_close(self):
        super().server_close()
        import socket as _socket

        with self._live_mu:
            live = list(self._live)
            self._live.clear()
        for sock in live:
            try:
                sock.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass

    def handle_error(self, request, client_address):
        """Peer disconnects (reset/broken pipe/timeouts) are routine with
        keep-alive pools and severed-on-close peers — not stderr-traceback
        events. Anything else keeps the stdlib's loud default."""
        import sys

        # sys.exc_info, not sys.exception: the latter is 3.11+ and this
        # runs on 3.10 — an AttributeError here replaced every quiet
        # disconnect with a scarier traceback of its own.
        exc = sys.exc_info()[1]
        if isinstance(exc, (ConnectionResetError, BrokenPipeError,
                            ConnectionAbortedError, TimeoutError)):
            return
        super().handle_error(request, client_address)


def serve(handler: Handler, host: str = "localhost", port: int = 0,
          ssl_context=None) -> Tuple[ThreadingHTTPServer, threading.Thread, int]:
    cls = type("BoundHandler", (_RequestHandler,), {"handler": handler})
    httpd = _Server((host, port), cls)
    if ssl_context is not None:
        # https bind (reference server/server.go:367-375 getListener wraps
        # the listener in tls.Listen when the bind scheme is https).
        # do_handshake_on_connect=False: the handshake must run in the
        # per-connection worker thread, not the single accept loop, or one
        # stalled client blocks every other connection.
        httpd.socket = ssl_context.wrap_socket(
            httpd.socket, server_side=True, do_handshake_on_connect=False
        )
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    return httpd, thread, httpd.server_address[1]
