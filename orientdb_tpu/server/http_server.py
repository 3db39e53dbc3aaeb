"""HTTP/REST listener.

Analog of [E] ONetworkProtocolHttpDb (port 2480, SURVEY.md §2 "HTTP/REST"),
with the reference's REST shapes:

  GET    /listDatabases
  POST   /database/<db>                    create database
  GET    /database/<db>                    database info
  GET    /query/<db>/sql/<urlencoded sql>[/<limit>]
  POST   /command/<db>/sql                 body = sql text or {"command": ...}
  GET    /document/<db>/<rid>
  POST   /document/<db>                    body = JSON doc with @class
  PUT    /document/<db>/<rid>              body = JSON fields
  DELETE /document/<db>/<rid>
  GET    /class/<db>/<name>                schema info

All endpoints require HTTP Basic auth against the server's security
manager; query/command check read/write permission on the target.
"""

from __future__ import annotations

import base64
import json
import threading
import urllib.error
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Tuple

from orientdb_tpu.models.record import Document, Edge, Vertex
from orientdb_tpu.models.rid import RID
from orientdb_tpu.models.security import (
    RES_DATABASE,
    RES_RECORD,
    SecurityError,
    classify_sql,
)
from orientdb_tpu.utils.logging import get_logger

log = get_logger("http")


def _doc_json(doc: Document) -> dict:
    out = dict(doc.to_dict())
    return out


class _DeferredHttpError(Exception):
    """An HTTP error decided inside a db-lock critical section but SENT
    after the lock releases (a stalled client socket must never block
    the database's write path)."""

    def __init__(self, code: int, msg: str) -> None:
        super().__init__(msg)
        self.code = code
        self.msg = msg


def _traced(fn):
    """Wrap an HTTP verb handler in a server span that CONTINUES the
    caller's trace when the request carries propagation headers
    (obs/propagation) — the receiving half of cross-node tracing for
    forwarding, 2PC phases, and quorum pushes. Also maintains the
    listener's in-flight depth (the admission-control signal)."""

    verb = fn.__name__[3:]

    def wrapper(self):
        import orientdb_tpu.obs.critpath as critpath
        from orientdb_tpu.obs.propagation import (
            continue_trace,
            extract_headers,
        )
        from orientdb_tpu.utils.metrics import metrics

        srv = self.server
        with srv.inflight_lock:
            srv.inflight += 1
            metrics.gauge("http.inflight", srv.inflight)
        path = urllib.parse.urlparse(self.path).path
        # the critical-path record covers the whole handler window:
        # route parse, admission, execution, response marshal+flush
        cp = critpath.begin_request("http")
        try:
            with continue_trace(
                f"http.{verb}", extract_headers(self.headers),
                path=path[:120],
            ):
                with critpath.active(cp):
                    return fn(self)
        finally:
            critpath.commit(cp)
            with srv.inflight_lock:
                srv.inflight -= 1
                metrics.gauge("http.inflight", srv.inflight)

    wrapper.__name__ = fn.__name__
    return wrapper


class _Handler(BaseHTTPRequestHandler):
    server_version = "orientdb-tpu/0.1"
    protocol_version = "HTTP/1.1"

    # -- plumbing -----------------------------------------------------------

    def log_message(self, fmt, *args):  # route through our logger
        log.debug("http: " + fmt, *args)

    def _send(self, code: int, payload) -> None:
        def enc(v):
            if isinstance(v, (bytes, bytearray)):  # blob payloads
                from orientdb_tpu.storage.durability import bytes_to_wire

                return bytes_to_wire(v)
            # anything else non-serializable stays a TypeError (a visible
            # 500), not silently stringified response data
            raise TypeError(f"not JSON-serializable: {type(v).__name__}")

        import orientdb_tpu.obs.critpath as critpath

        with critpath.segment("marshal"):
            body = json.dumps(payload, default=enc).encode()
        with critpath.segment("flush"):
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    def _error(self, code: int, msg: str) -> None:
        self._send(code, {"errors": [{"code": code, "content": msg}]})

    #: write routes exempt from admission shedding: a replication apply
    #: or a 2PC phase carries an already-made decision — refusing it
    #: would CREATE gaps / in-doubt transactions instead of load relief.
    #: A changefeed cursor ack is exempt too: acking lets a lagging
    #: consumer DRAIN, which reduces pressure rather than adding it.
    _ADMISSION_EXEMPT = frozenset({"replication", "tx2pc", "changes"})

    def _shed_write(self, head: str, dbname: Optional[str]) -> bool:
        """Admission control for write verbs: True when the request was
        shed (a 503 with Retry-After has been sent). Sheds on listener
        in-flight depth plus the shared db-pressure checks
        (server/admission: staged-2PC backlog, quorum-lost read-only
        degradation). A ``POST /command`` carrying a READ statement
        (SELECT/MATCH through the standard REST command path) is never
        shed — degradation means read-only, not read-nothing."""
        from orientdb_tpu.server.admission import db_pressure
        from orientdb_tpu.utils.config import config
        from orientdb_tpu.utils.metrics import metrics

        if head in self._ADMISSION_EXEMPT:
            return False
        if head == "command" and self._command_is_read():
            return False
        reason = None
        retry_after = config.retry_after_s
        maxin = config.http_max_inflight
        if maxin and self.server.inflight > maxin:
            reason = (
                f"in-flight depth {self.server.inflight} > {maxin}"
            )
        if reason is None:
            db = (
                self.server.ot_server.get_database(dbname)
                if dbname
                else None
            )
            reason, retry_after = db_pressure(db)
        if reason is None:
            return False
        metrics.incr("http.shed")
        body = json.dumps(
            {
                "errors": [{"code": 503, "content": reason}],
                "retry_after": retry_after,
            }
        ).encode()
        self.send_response(503)
        self.send_header("Content-Type", "application/json")
        self.send_header("Retry-After", f"{retry_after:g}")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)
        return True

    def _auth(self):
        hdr = self.headers.get("Authorization", "")
        if hdr.startswith("Basic "):
            try:
                user, _, pw = base64.b64decode(hdr[6:]).decode().partition(":")
            except Exception:
                user, pw = "", ""
            u = self.server.ot_server.security.authenticate(user, pw)
            if u is not None:
                return u
        elif hdr.startswith("Bearer "):
            # session tokens ([E] OTokenHandler): the credential carries
            # the identity, so the user field is empty — only a chain
            # with a TokenAuthenticator (server/auth.py) accepts these
            u = self.server.ot_server.security.authenticate("", hdr[7:])
            if u is not None:
                return u
        self.send_response(401)
        self.send_header("WWW-Authenticate", 'Basic realm="orientdb-tpu"')
        self.send_header("Content-Length", "0")
        self.end_headers()
        return None

    def _body(self) -> bytes:
        # _command_is_read may have consumed the stream already (the
        # request body can only be read once): serve the cached copy
        cached = self.__dict__.pop("_body_cache", None)
        if cached is not None:
            return cached
        n = int(self.headers.get("Content-Length") or 0)
        return self.rfile.read(n) if n else b""

    def _command_is_read(self) -> bool:
        """Classify a POST /command body before admission shedding: a
        READ statement rides through degradation. The body is cached
        for the route handler's own _body() call."""
        try:
            body = self._body()
            self._body_cache = body
            text = body.decode(errors="replace")
            try:
                sql = json.loads(text).get("command", text)
            except (json.JSONDecodeError, AttributeError):
                sql = text
            _resource, op = classify_sql(sql)
            return op == "read"
        except Exception:
            return False  # unclassifiable: treat as a write

    def _route(self) -> Tuple[str, list]:
        path = urllib.parse.urlparse(self.path).path
        parts = [urllib.parse.unquote(p) for p in path.split("/") if p]
        return (parts[0] if parts else ""), parts[1:]

    def _db(self, name: str):
        db = self.server.ot_server.get_database(name)
        if db is None:
            self._error(404, f"database '{name}' not found")
        return db

    def _batch_op(self, db, op):
        """One authorized /batch operation; runs inside the batch tx
        unless the payload opted out."""
        from orientdb_tpu.storage.durability import _dec

        typ = op.get("type")
        if typ == "script":
            rows = db.execute(
                op.get("language", "sql"),
                op["script"],
                op.get("parameters") or {},
            )
            return [r.to_dict() for r in rows]
        if typ == "cmd":
            return db.command(
                op.get("command", ""), op.get("parameters") or {}
            ).to_dicts()
        if typ == "c":
            rec = dict(op.get("record", {}))
            cls = rec.pop("@class", "O")
            kind = rec.pop("@type", None)
            fields = {
                k: _dec(v) for k, v in rec.items() if not k.startswith("@")
            }
            c = db.schema.get_class(cls)
            # kind dispatch mirrors the /document route: a record in a
            # vertex class must BE a Vertex or edges against it crash
            if (c is not None and c.is_vertex_type) or (
                c is None and kind == "vertex"
            ):
                doc = db.new_vertex(cls, **fields)
            else:
                doc = db.new_element(cls, **fields)
            return doc  # rendered post-commit (real rid)
        if typ == "u":
            rec = dict(op.get("record", {}))
            rid = RID.parse(rec.pop("@rid"))
            cur = db.load(rid)
            if cur is None:
                raise _DeferredHttpError(404, f"{rid} not found")
            for k, v in rec.items():
                if not k.startswith("@"):
                    cur.set(k, _dec(v))
            db.save(cur)
            return cur  # rendered post-commit
        # typ == "d" (validated upstream)
        rec = op.get("record", {})
        rid = RID.parse(rec.get("@rid") if isinstance(rec, dict) else rec)
        cur = db.load(rid)
        if cur is not None:
            db.delete(cur)
        return {"deleted": str(rid)}

    def _check_tx_ops(self, user, ops) -> None:
        """Authorize a tx op batch PER OP KIND, matching the single-op
        routes: a delete inside a tx needs the delete grant, etc."""
        _actions = {
            "create": "create",
            "edge": "create",
            "update": "update",
            "delete": "delete",
        }
        for action in sorted(
            {_actions.get(op.get("kind"), "update") for op in ops}
        ):
            self.server.ot_server.security.check(user, RES_RECORD, action)

    # -- verbs --------------------------------------------------------------

    @_traced
    def do_GET(self):  # noqa: N802
        head, rest = self._route()
        if head in ("studio", ""):
            # the Studio UI shell is public ([E] the studio webapp is
            # served pre-login too); every data call it makes carries
            # credentials and authenticates like any other client
            from orientdb_tpu.server.studio import STUDIO_HTML

            body = STUDIO_HTML.encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/html; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        user = self._auth()
        if user is None:
            return
        try:
            if head == "listDatabases":
                return self._send(
                    200, {"databases": sorted(self.server.ot_server.databases)}
                )
            if head == "metrics":
                # the [E] /profiler analog (SURVEY.md §5.1/§5.5):
                # Prometheus text exposition by default (scrapeable);
                # ?format=json or Accept: application/json keeps the
                # raw registry snapshot for programmatic readers
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query
                )
                accept = self.headers.get("Accept", "")
                if "json" in q.get("format", []) or (
                    "application/json" in accept
                ):
                    from orientdb_tpu.obs.registry import snapshot_all

                    # snapshot_all is the shape /cluster/metrics fans
                    # in per member — this endpoint must serve exactly
                    # it, or scraped members drift from the local one
                    return self._send(200, snapshot_all())
                from orientdb_tpu.obs.registry import render_prometheus

                body = render_prometheus().encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type",
                    "text/plain; version=0.0.4; charset=utf-8",
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if head == "alerts" and not rest:
                # the alerting plane (obs/alerts, obs/watchdog): active
                # pending/firing alerts with exemplar trace ids, the
                # resolved history ring, and the watchdog summary. JSON
                # by default; ?format=prometheus serves the per-rule
                # orienttpu_alert_firing{rule=...} state gauges.
                from orientdb_tpu.obs.alerts import (
                    engine as alert_engine,
                    render_alerts_prometheus,
                )

                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query
                )
                if "prometheus" in q.get("format", []):
                    body = render_alerts_prometheus().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                return self._send(200, alert_engine.report())
            if head == "slo" and not rest:
                # the SLO-verdict plane (obs/slo): the last traffic-
                # simulator run's machine-readable report — verdict,
                # per-class windowed quantiles vs targets, failures
                # naming their rule/key — or an explicit "none" marker
                # when no run has been judged in this process
                from orientdb_tpu.obs.slo import engine as slo_engine

                return self._send(200, slo_engine.report())
            if head == "stats" and rest == ["critpath"]:
                # the critical-path attribution plane (obs/critpath):
                # per-class and per-fingerprint segment breakdowns with
                # dominant bottleneck, the segment catalog, and recent
                # decompositions; ?k= bounds the fingerprint list
                from orientdb_tpu.obs.critpath import plane as cp_plane

                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query
                )
                try:
                    k = int(q.get("k", ["20"])[0])
                except ValueError:
                    k = 20
                return self._send(200, cp_plane.report(k))
            if head == "stats" and rest in (["queries"], ["profile"]):
                # the query-statistics plane (obs/stats, obs/profile):
                # per-fingerprint cumulative cost, top-K by any column,
                # and the span-profile self-time tree. JSON by default
                # (an operator/API surface); ?format=prometheus serves
                # the promlint-clean per-fingerprint exposition.
                if rest == ["profile"]:
                    from orientdb_tpu.obs.profile import profiler

                    return self._send(200, profiler.profile())
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query
                )
                from orientdb_tpu.obs.stats import (
                    render_stats_prometheus,
                    resolve_sort_column,
                    stats,
                )

                if "prometheus" in q.get("format", []):
                    body = render_stats_prometheus().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                try:
                    k = int(q.get("k", ["50"])[0])
                except ValueError:
                    k = 50
                by = resolve_sort_column(q.get("by", ["total_s"])[0])
                return self._send(
                    200,
                    {
                        "by": by,
                        "queries": stats.top(k, by=by),
                    },
                )
            if head == "cluster" and rest in (["health"], ["metrics"]):
                # fleet-level aggregation plane (obs/cluster_view):
                # per-member liveness/role/lag/in-doubt, and the fan-in
                # exposition that merges every member's registries into
                # one scrape labeled by member
                from orientdb_tpu.obs.cluster_view import (
                    cluster_health,
                    cluster_metrics_json,
                    cluster_metrics_text,
                )

                if rest == ["health"]:
                    return self._send(
                        200, cluster_health(self.server.ot_server)
                    )
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query
                )
                if "json" in q.get("format", []) or (
                    "application/json" in self.headers.get("Accept", "")
                ):
                    return self._send(
                        200, cluster_metrics_json(self.server.ot_server)
                    )
                body = cluster_metrics_text(self.server.ot_server).encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type",
                    "text/plain; version=0.0.4; charset=utf-8",
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if head == "debug" and rest == ["timeline"]:
                # the dispatch flight recorder (obs/timeline): the
                # recent window as Chrome-trace JSON — load it straight
                # into Perfetto (ui.perfetto.dev) or chrome://tracing.
                # Admin-only (records carry fingerprints + trace ids,
                # like the bundle); ?window=<s> bounds it (default
                # config.timeline_window_s), ?format=json serves raw
                # records + the overlap report instead.
                self.server.ot_server.security.check(
                    user, "server.debug", "read"
                )
                from orientdb_tpu.obs.timeline import recorder
                from orientdb_tpu.utils.config import config

                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query
                )
                try:
                    window = float(q.get("window", ["0"])[0])
                except ValueError:
                    window = 0.0
                if window <= 0:
                    window = config.timeline_window_s
                if "json" in q.get("format", []):
                    return self._send(
                        200,
                        {
                            "overlap": recorder.overlap(window_s=window),
                            "records": recorder.records(
                                window_s=window, limit=500
                            ),
                        },
                    )
                return self._send(200, recorder.chrome_trace(window_s=window))
            if head == "debug" and rest == ["memory"]:
                # the device-memory ledger (obs/memledger): per-owner
                # rollup, watermark ring, live reconciliation vs
                # jax.live_arrays, outstanding/stale epoch leases, and
                # the last tier refusal. Admin-only (owner ids name
                # snapshots and plans). ?reconcile=0 skips the live
                # pass and serves the last cached report.
                self.server.ot_server.security.check(
                    user, "server.debug", "read"
                )
                from orientdb_tpu.obs.memledger import memledger

                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query
                )
                rec = q.get("reconcile", ["1"])[0] != "0"
                return self._send(200, memledger.report(reconcile=rec))
            if head == "debug" and rest == ["fsck"]:
                # durable-state fsck (tools/fsck): per-database WAL
                # CRC chains + segment continuity, checkpoint/delta/
                # epoch content hashes, coldstore tails. Admin-only
                # (reports name on-disk paths). ?dir=<path> checks an
                # explicit tree instead of the server databases'
                # durability directories.
                self.server.ot_server.security.check(
                    user, "server.debug", "read"
                )
                from orientdb_tpu.tools.fsck import fsck_tree

                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query
                )
                explicit = q.get("dir", [None])[0]
                if explicit:
                    dirs = {"": explicit}
                else:
                    dirs = {
                        name: d
                        for name, db in (
                            self.server.ot_server.databases.items()
                        )
                        if (d := getattr(db, "_durability_dir", None))
                    }
                reports = {
                    name or "tree": fsck_tree(d)
                    for name, d in dirs.items()
                }
                return self._send(
                    200,
                    {
                        "clean": all(
                            r["clean"] for r in reports.values()
                        ),
                        "reports": reports,
                    },
                )
            if head == "debug" and rest == ["bundle"]:
                # the flight-recorder bundle (obs/bundle): recent
                # cross-node traces assembled by trace_id, slowlog,
                # metrics snapshot, and in-doubt 2PC state — admin-only
                # (traces carry SQL text, like the replication stream
                # carries records)
                self.server.ot_server.security.check(
                    user, "server.debug", "read"
                )
                from orientdb_tpu.obs.bundle import debug_bundle

                srv = self.server.ot_server
                return self._send(
                    200,
                    debug_bundle(
                        dbs=list(srv.databases.values()),
                        member=srv.name,
                        cluster=getattr(srv, "cluster", None),
                    ),
                )
            if head == "changes" and len(rest) == 1:
                # resumable changefeed pull (orientdb_tpu/cdc): WAL-
                # derived change events with lsn > the cursor, long-poll
                # when caught up. ?since=<lsn> (explicit cursor) or
                # ?cursor=<name> (durable named cursor; since overrides);
                # ?timeout= bounds the long-poll, ?limit= the batch,
                # ?class=A,B filters (subclass-aware), ?where= adds a
                # predicate. A pruned range answers 410: resync, never a
                # silent gap.
                db = self._db(rest[0])
                if db is None:
                    return
                self.server.ot_server.security.check(user, RES_RECORD, "read")
                import time as _time

                from orientdb_tpu.cdc.feed import (
                    CdcGapError,
                    event_matches,
                    feed_of,
                    parse_where,
                )
                from orientdb_tpu.chaos import fault
                from orientdb_tpu.obs.trace import span
                from orientdb_tpu.utils.config import config

                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query
                )
                feed = feed_of(db)
                cursor = q.get("cursor", [None])[0]
                if "since" in q:
                    since = int(q["since"][0])
                elif cursor:
                    # first contact with a NEW named cursor starts at
                    # the head (new changes only) — same semantics as
                    # binary cdc_subscribe, and it cannot 410 on a
                    # database whose early archives were retired.
                    # Explicit ?since=0 still requests a full replay.
                    # An EXPIRED cursor answers 410 loudly instead.
                    try:
                        stored = feed.cursors.get(cursor)
                    except CdcGapError as e:
                        return self._error(410, str(e))
                    since = feed.head_lsn if stored is None else stored
                else:
                    since = 0
                timeout = min(
                    float(q.get("timeout", [config.cdc_poll_timeout_s])[0]),
                    60.0,
                )
                limit = max(1, int(q.get("limit", ["1000"])[0]))
                classes = [
                    c for c in ",".join(q.get("class", [])).split(",") if c
                ] or None
                where = q.get("where", [None])[0]
                where_ast = (
                    parse_where(where, classes[0] if classes else None)
                    if where
                    else None
                )
                deadline = _time.monotonic() + timeout
                while True:
                    try:
                        events, covered, head_lsn = feed.events_since(
                            since, limit=limit
                        )
                    except CdcGapError as e:
                        return self._error(410, str(e))
                    events = [
                        ev
                        for ev in events
                        if event_matches(db, ev, classes, where_ast)
                    ]
                    left = deadline - _time.monotonic()
                    if events or covered > since or left <= 0:
                        break
                    feed.wait_beyond(since, left)
                with span(
                    "cdc.push", transport="http", events=len(events)
                ), fault.point("cdc.push"):
                    return self._send(
                        200,
                        {
                            "events": events,
                            "cursor": covered,
                            "head": head_lsn,
                        },
                    )
            if head == "replication" and len(rest) == 2:
                # WAL shipping for replicas ([E] the distributed delta-sync
                # request); admin-only — the stream exposes every record
                # "server.replication" falls outside reader/writer's
                # per-resource grants; only admin's '*' covers it
                self.server.ot_server.security.check(
                    user, "server.replication", "read"
                )
                db = self._db(rest[0])
                if db is None:
                    return
                from orientdb_tpu.parallel.replication import entries_after

                # exact=1: the replica asserts it holds state-as-of the
                # requested LSN exactly (it restored our checkpoint), so
                # a base-state checkpoint must not be re-served
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query
                )
                return self._send(
                    200,
                    entries_after(
                        db, int(rest[1]), exact_ok="exact" in q
                    ),
                )
            if head == "database" and rest:
                db = self._db(rest[0])
                if db is None:
                    return
                classes = [
                    {
                        "name": c.name,
                        "records": db.count_class(c.name, polymorphic=False)
                        if not c.abstract
                        else 0,
                    }
                    for c in db.schema.classes()
                ]
                return self._send(200, {"server": {}, "classes": classes})
            if head == "query" and len(rest) >= 3 and rest[1] == "sql":
                db = self._db(rest[0])
                if db is None:
                    return
                self.server.ot_server.security.check(user, RES_RECORD, "read")
                sql = rest[2]
                limit = int(rest[3]) if len(rest) > 3 else None
                # singles ride the cross-session lane path (server/
                # coalesce.py) exactly like binary `query` ops do:
                # concurrent HTTP sessions' queries merge into one
                # micro-batch instead of each paying a lone dispatch
                rows, _engine = self.server.ot_server.coalescer.submit(
                    db, sql, None
                )
                if limit is not None:
                    rows = rows[:limit]
                return self._send(200, {"result": rows})
            if head == "document" and len(rest) == 2:
                db = self._db(rest[0])
                if db is None:
                    return
                self.server.ot_server.security.check(user, RES_RECORD, "read")
                doc = db.load(RID.parse(rest[1]))
                if doc is None:
                    return self._error(404, f"record {rest[1]} not found")
                return self._send(200, _doc_json(doc))
            if head == "class" and len(rest) == 2:
                db = self._db(rest[0])
                if db is None:
                    return
                cls = db.schema.get_class(rest[1])
                if cls is None:
                    return self._error(404, f"class '{rest[1]}' not found")
                return self._send(
                    200,
                    {
                        "name": cls.name,
                        "superClasses": [s.name for s in cls.superclasses],
                        "abstract": cls.abstract,
                        "properties": [
                            {"name": p.name, "type": p.type.name}
                            for p in cls.properties.values()
                        ],
                        "records": 0
                        if cls.abstract
                        else db.count_class(cls.name, polymorphic=False),
                    },
                )
            return self._error(404, f"no route for GET /{head}")
        except SecurityError as e:
            return self._error(403, str(e))
        except Exception as e:
            return self._error(500, f"{type(e).__name__}: {e}")

    @_traced
    def do_POST(self):  # noqa: N802
        head, rest = self._route()
        # auth FIRST: an unauthenticated client must see its 401 (and
        # must not get its body parsed) even while the listener sheds
        user = self._auth()
        if user is None:
            return
        if self._shed_write(head, rest[0] if rest else None):
            return
        try:
            if head == "database" and rest:
                self.server.ot_server.security.check(user, RES_DATABASE, "create")
                db = self.server.ot_server.create_database(rest[0])
                return self._send(200, {"created": db.name})
            if head == "command" and len(rest) >= 2 and rest[1] == "sql":
                db = self._db(rest[0])
                if db is None:
                    return
                body = self._body().decode()
                try:
                    sql = json.loads(body).get("command", body)
                except (json.JSONDecodeError, AttributeError):
                    sql = body
                resource, op = classify_sql(sql)
                self.server.ot_server.security.check(user, resource, op)
                rows = db.command(sql).to_dicts()
                return self._send(200, {"result": rows})
            if head == "changes" and len(rest) == 2 and rest[1] == "ack":
                # persist a named changefeed cursor: the consumer has
                # durably processed everything at/below lsn — restart
                # resumes there (at-least-once; acks never regress)
                db = self._db(rest[0])
                if db is None:
                    return
                self.server.ot_server.security.check(user, RES_RECORD, "read")
                from orientdb_tpu.cdc.feed import feed_of

                payload = json.loads(self._body() or b"{}")
                name = payload.get("cursor")
                if not name:
                    return self._error(400, "cursor name required")
                stored = feed_of(db).ack_cursor(
                    name, int(payload.get("lsn", 0))
                )
                return self._send(200, {"cursor": name, "lsn": stored})
            if head == "replication" and len(rest) == 2 and rest[1] == "apply":
                # quorum-push apply ([E] the distributed task execution
                # endpoint); admin-only like the pull stream
                self.server.ot_server.security.check(
                    user, "server.replication", "update"
                )
                db = self._db(rest[0])
                if db is None:
                    return
                from orientdb_tpu.parallel.replication import (
                    apply_pushed_entries,
                )

                payload = json.loads(self._body() or b"{}")
                floor = apply_pushed_entries(
                    db,
                    payload.get("entries", ()),
                    payload.get("term"),
                    checkpoint=payload.get("checkpoint"),
                )
                return self._send(200, {"applied_lsn": floor})
            if head == "document" and len(rest) == 1:
                db = self._db(rest[0])
                if db is None:
                    return
                self.server.ot_server.security.check(user, RES_RECORD, "create")
                from orientdb_tpu.storage.durability import _dec

                payload = json.loads(self._body() or b"{}")
                cls = payload.pop("@class", "O")
                # forwarded creates carry the record kind so an unknown
                # class is auto-created with the RIGHT type (a replica's
                # Vertex must not become a plain document class here)
                kind = payload.pop("@type", None)
                payload = {
                    k: _dec(v)
                    for k, v in payload.items()
                    if not k.startswith("@")
                }
                c = db.schema.get_class(cls)
                if kind == "blob" or cls == "OBlob":
                    doc = db.new_blob(payload.pop("data", b"") or b"")
                    if payload:
                        for k, v in payload.items():
                            doc.set(k, v)
                        db.save(doc)
                elif (c is not None and c.is_vertex_type) or (
                    c is None and kind == "vertex"
                ):
                    doc = db.new_vertex(cls, **payload)
                else:
                    doc = db.new_element(cls, **payload)
                return self._send(201, _doc_json(doc))
            if head == "batch" and len(rest) == 1:
                # [E] the REST /batch command: operations = script /
                # cmd / c(reate) / u(pdate) / d(elete), one session.
                # Transactional by default like the reference —
                # "transaction": false opts out (scripts managing their
                # OWN tx must opt out; BEGIN inside the wrapped tx
                # raises).
                db = self._db(rest[0])
                if db is None:
                    return
                payload = json.loads(self._body() or b"{}")
                ops = payload.get("operations", ())
                # authorize EVERYTHING up front: batch scripts carry
                # arbitrary statements, so each one classifies like a
                # single command would (DDL needs schema, GRANT needs
                # security, …) — no escalation through /batch
                from orientdb_tpu.exec.script import script_permissions

                for op in ops:
                    typ = op.get("type")
                    if typ == "script":
                        script = op.get("script", "")
                        if isinstance(script, list):
                            script = ";\n".join(script)
                        op["script"] = script
                        for resource, action in sorted(
                            script_permissions(script)
                        ):
                            self.server.ot_server.security.check(
                                user, resource, action
                            )
                    elif typ == "cmd":
                        resource, action = classify_sql(
                            op.get("command", "")
                        )
                        self.server.ot_server.security.check(
                            user, resource, action
                        )
                    elif typ in ("c", "u", "d"):
                        self.server.ot_server.security.check(
                            user,
                            RES_RECORD,
                            {"c": "create", "u": "update", "d": "delete"}[
                                typ
                            ],
                        )
                        if typ in ("u", "d"):
                            rec = op.get("record", {})
                            if not (
                                isinstance(rec, str)
                                or (isinstance(rec, dict) and "@rid" in rec)
                            ):
                                return self._error(
                                    400, f"batch '{typ}' op needs @rid"
                                )
                    else:
                        return self._error(
                            400, f"unknown batch op type {typ!r}"
                        )
                transactional = payload.get("transaction", True)
                if transactional:
                    db.begin()
                try:
                    results = [self._batch_op(db, op) for op in ops]
                    if transactional:
                        db.commit()
                except BaseException:
                    if transactional and db.tx is not None:
                        db.tx.rollback()
                    raise
                # created/updated docs render AFTER commit so their
                # rids are the adopted real ones, not tx temps
                rendered = [
                    _doc_json(r) if isinstance(r, Document) else r
                    for r in results
                ]
                return self._send(200, {"result": rendered})
            if head == "tx" and len(rest) == 1:
                # forwarded-transaction execution ([E] the distributed tx
                # task batch, SURVEY.md:126): the non-owner's buffered ops
                # run here inside ONE local transaction — all-or-nothing,
                # MVCC-checked against the forwarder's base versions
                db = self._db(rest[0])
                if db is None:
                    return
                from orientdb_tpu.parallel.twophase import execute_tx_ops

                payload = json.loads(self._body() or b"{}")
                ops = payload.get("ops", [])
                self._check_tx_ops(user, ops)
                results, _tm = execute_tx_ops(db, ops)
                return self._send(200, {"results": results})
            if head == "tx2pc" and len(rest) == 1:
                # 2-phase distributed tx participant ([E] SURVEY.md:126):
                # prepare validates + locks, commit executes the staged
                # batch as one local tx, abort releases — all keyed by
                # the coordinator's txid (parallel/twophase)
                db = self._db(rest[0])
                if db is None:
                    return
                from orientdb_tpu.parallel.twophase import (
                    TwoPhaseError,
                    get_registry,
                )

                payload = json.loads(self._body() or b"{}")
                phase = payload.get("phase")
                txid = payload.get("txid")
                if not txid:
                    return self._error(400, "txid required")
                reg = get_registry(db)
                if phase == "prepare":
                    ops = payload.get("ops", [])
                    self._check_tx_ops(user, ops)
                    reg.prepare(
                        txid, ops, ttl=float(payload.get("ttl", 60.0))
                    )
                    return self._send(200, {"prepared": txid})
                if phase == "commit":
                    self.server.ot_server.security.check(
                        user, RES_RECORD, "update"
                    )
                    try:
                        results, temp_map = reg.commit(
                            txid, rid_map=payload.get("rid_map")
                        )
                    except TwoPhaseError as e:
                        # expired/unknown: the coordinator maps 410 to
                        # in-doubt (participant presumed abort)
                        return self._error(410, str(e))
                    return self._send(
                        200, {"results": results, "temp_map": temp_map}
                    )
                if phase == "abort":
                    self.server.ot_server.security.check(
                        user, RES_RECORD, "update"
                    )
                    reg.abort(txid)
                    return self._send(200, {"aborted": txid})
                return self._error(400, f"unknown 2pc phase {phase!r}")
            if head == "edge" and len(rest) == 1:
                # forwarded edge create (parallel/forwarding): a typed
                # route instead of SQL so field values round-trip exactly
                db = self._db(rest[0])
                if db is None:
                    return
                self.server.ot_server.security.check(user, RES_RECORD, "create")
                from orientdb_tpu.storage.durability import _dec

                payload = json.loads(self._body() or b"{}")
                src = db.load(RID.parse(payload["from"]))
                dst = db.load(RID.parse(payload["to"]))
                if not isinstance(src, Vertex) or not isinstance(dst, Vertex):
                    return self._error(404, "edge endpoint not found")
                doc = db.new_edge(
                    payload["@class"], src, dst,
                    **{k: _dec(v) for k, v in payload.get("fields", {}).items()},
                )
                return self._send(201, _doc_json(doc))
            return self._error(404, f"no route for POST /{head}")
        except SecurityError as e:
            return self._error(403, str(e))
        except Exception as e:
            from orientdb_tpu.models.database import (
                ConcurrentModificationError,
            )

            from orientdb_tpu.parallel.twophase import TxOpError

            if isinstance(e, _DeferredHttpError):
                return self._error(e.code, e.msg)
            if isinstance(e, TxOpError):
                return self._error(e.code, e.msg)
            if isinstance(e, ConcurrentModificationError):
                # a forwarded tx losing an MVCC race maps back to the
                # forwarder's ConcurrentModificationError, not a 500
                return self._error(409, str(e))
            return self._error(500, f"{type(e).__name__}: {e}")

    @_traced
    def do_PUT(self):  # noqa: N802
        head, rest = self._route()
        # auth FIRST: an unauthenticated client must see its 401 (and
        # must not get its body parsed) even while the listener sheds
        user = self._auth()
        if user is None:
            return
        if self._shed_write(head, rest[0] if rest else None):
            return
        try:
            if head == "document" and len(rest) == 2:
                db = self._db(rest[0])
                if db is None:
                    return
                self.server.ot_server.security.check(user, RES_RECORD, "update")
                payload = json.loads(self._body() or b"{}")
                base = payload.get("@base_version")
                from orientdb_tpu.storage.durability import _dec

                if db._write_owner is not None:
                    # this node was demoted after the forwarder read its
                    # (now stale) ownership map: chain-forward to the
                    # real owner WITHOUT touching the local store and
                    # without holding db._lock across the network call
                    fields = {
                        k: _dec(v)
                        for k, v in payload.items()
                        if not k.startswith("@")
                    }
                    resp = db._write_owner.update(
                        RID.parse(rest[1]),
                        fields,
                        base_version=int(base) if base is not None else None,
                        replace=bool(payload.get("@replace")),
                    )
                    return self._send(200, resp)
                # Version check, field mutation, and save form ONE MVCC
                # critical section: two racing forwarded updates with the
                # same base version must resolve exactly like two racing
                # local saves (one wins, one 409s). _quorum_deferral sits
                # OUTSIDE the lock so replica pushes still flush after it
                # is released.
                err = None  # (code, message) — SENT OUTSIDE the lock: a
                # stalled client's socket must never block the database's
                # write path (the success path serializes inside and
                # sends outside for the same reason)
                with db._quorum_deferral():
                    with db._lock:
                        doc = db.load(RID.parse(rest[1]))
                        if doc is None:
                            err = (404, f"record {rest[1]} not found")
                        elif base is not None and int(base) != doc.version:
                            # forwarded saves carry their base version:
                            # MVCC must hold across the forward exactly
                            # as it does locally
                            err = (
                                409,
                                f"{doc.rid}: stored v{doc.version}"
                                f" != base v{base}",
                            )
                        if err is not None:
                            raise _DeferredHttpError(*err)
                        # mutate the LIVE stored object only with a way
                        # back: a failed save (mandatory/unique/hook
                        # violation) must not leave the owner's record
                        # torn with no version bump or WAL entry. The
                        # undo applies ONLY when the save did not take
                        # effect (mutation_epoch unmoved — it bumps
                        # right before the WAL append): after the WAL
                        # has the entry, reverting the live record
                        # would diverge it from its own durable log,
                        # so the error propagates over the new state
                        # exactly like a local save whose after-hook
                        # raised.
                        undo_fields = doc.fields()
                        undo_version = doc.version
                        epoch0 = db.mutation_epoch
                        try:
                            if payload.get("@replace"):
                                # forwarded full save: fields absent from
                                # the payload were removed at the
                                # forwarder — clear them so
                                # remove_field() propagates
                                sent = {
                                    k
                                    for k in payload
                                    if not k.startswith("@")
                                }
                                for k in list(doc.fields()):
                                    if k not in sent:
                                        doc.remove_field(k)
                            for k, v in payload.items():
                                if not k.startswith("@"):
                                    doc.set(k, _dec(v))
                            db.save(doc)
                        except Exception:
                            if db.mutation_epoch == epoch0:
                                doc._fields = undo_fields
                                doc.version = undo_version
                            raise
                        # serialize INSIDE the critical section: after
                        # the lock drops a later writer could bump the
                        # shared object and the forwarder would adopt
                        # that version number over ITS OWN field values
                        body = _doc_json(doc)
                return self._send(200, body)
            return self._error(404, f"no route for PUT /{head}")
        except SecurityError as e:
            return self._error(403, str(e))
        except Exception as e:
            # MVCC conflicts keep their status across a chain-forward:
            # the originating forwarder translates 409 back into
            # ConcurrentModificationError for its caller — a generic 500
            # would break retry-with-fresh-version loops during the
            # demotion window. Other owner-side HTTP errors (e.g. 404)
            # pass their code through for the same reason.
            from orientdb_tpu.models.database import (
                ConcurrentModificationError,
            )

            if isinstance(e, _DeferredHttpError):
                return self._error(e.code, e.msg)
            if isinstance(e, ConcurrentModificationError):
                return self._error(409, str(e))
            if isinstance(e, urllib.error.HTTPError):
                return self._error(
                    e.code, e.read().decode(errors="replace") or str(e)
                )
            return self._error(500, f"{type(e).__name__}: {e}")

    @_traced
    def do_DELETE(self):  # noqa: N802
        head, rest = self._route()
        # auth FIRST: an unauthenticated client must see its 401 (and
        # must not get its body parsed) even while the listener sheds
        user = self._auth()
        if user is None:
            return
        if self._shed_write(head, rest[0] if rest else None):
            return
        try:
            if head == "document" and len(rest) == 2:
                db = self._db(rest[0])
                if db is None:
                    return
                self.server.ot_server.security.check(user, RES_RECORD, "delete")
                doc = db.load(RID.parse(rest[1]))
                if doc is None:
                    return self._error(404, f"record {rest[1]} not found")
                db.delete(doc)
                self.send_response(204)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
            if head == "database" and rest:
                self.server.ot_server.security.check(user, RES_DATABASE, "delete")
                ok = self.server.ot_server.drop_database(rest[0])
                return self._send(200 if ok else 404, {"dropped": ok})
            return self._error(404, f"no route for DELETE /{head}")
        except SecurityError as e:
            return self._error(403, str(e))
        except Exception as e:
            return self._error(500, f"{type(e).__name__}: {e}")


class HttpListener:
    """Threaded HTTP listener bound to an ephemeral port by default."""

    def __init__(self, ot_server, port: int = 0) -> None:
        self.httpd = ThreadingHTTPServer(("127.0.0.1", port), _Handler)
        self.httpd.ot_server = ot_server
        # admission-control signal: requests currently being handled
        # (maintained by _traced, read by _shed_write)
        self.httpd.inflight = 0
        self.httpd.inflight_lock = threading.Lock()
        self.port = self.httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, name="http-listener", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
