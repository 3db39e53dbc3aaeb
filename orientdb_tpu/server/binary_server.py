"""Binary protocol listener.

Analog of [E] ONetworkProtocolBinary / OChannelBinaryServer (port 2424,
SURVEY.md §2 "Binary protocol"): a persistent, session-oriented channel —
each frame is a 4-byte big-endian length followed by a compact JSON
envelope. Record payloads travel either as JSON dicts (default; blob
bytes framed as {"@bytes": base64}) or, when the session negotiates
``serialization: "binary"`` at db_open, as the schema-aware binary
record format (`server/binser.py` — the ORecordSerializerNetwork
analog) base85-framed inside the envelope.

Requests: {"op": ..., ...}. Ops: connect, db_list, db_create, db_open,
query, query_batch, command, load, save, delete, live_subscribe,
live_unsubscribe, close. All ops after `connect` run under the
authenticated user's permissions. Live-query events are PUSHED as
unsolicited frames {"push": true, "event": {...}} on the same channel;
clients demultiplex by the "push" key ([E] the binary protocol's push
messages).

Throughput path (VERDICT r4 #1 — the wire must deliver the engine's
batched-dispatch speed, [E] the reference's server IS its wire path):

- ``query_batch`` ships N statements in ONE frame and runs them through
  the engine's group dispatch (`exec/engine.execute_query_batch`);
- single ``query`` ops route through the server's cross-session
  coalescer (`server/coalesce.py`): concurrent sessions' singles land
  in fingerprint-keyed dispatch lanes and merge into homogeneous
  micro-batches replaying one compiled plan;
- ``pipeline: true`` at db_open turns on out-of-order dispatch for this
  session: query ops run on a worker pool and respond by ``reqid`` when
  ready, so ONE client can keep many singles in flight (they coalesce
  server-side like separate sessions' would).
"""

from __future__ import annotations

import json
import socket
import struct
import threading
from typing import Optional

import orientdb_tpu.obs.critpath as critpath
from orientdb_tpu.chaos import fault
from orientdb_tpu.models.rid import RID
from orientdb_tpu.models.security import (
    RES_DATABASE,
    RES_RECORD,
    SecurityError,
    classify_sql,
)
from orientdb_tpu.utils.logging import get_logger

log = get_logger("binary")


def send_frame(sock: socket.socket, payload: dict) -> None:
    # bytes values (blob payloads) get the shared @bytes framing; other
    # non-JSON values keep the channel's historical stringification
    from orientdb_tpu.storage.durability import json_channel_default

    # critpath stamps are thread-local no-ops on the client side of the
    # wire (client/remote.py shares this helper but never opens a
    # record); server-side, the bin.send fault point sits INSIDE the
    # flush timing so an injected send delay blames flush, not marshal
    with critpath.segment("marshal"):
        data = json.dumps(payload, default=json_channel_default).encode()
    with critpath.segment("flush"):
        with fault.point("bin.send"):
            sock.sendall(struct.pack(">I", len(data)) + data)


def recv_frame_raw(sock: socket.socket) -> Optional[bytes]:
    """One length-prefixed frame's body, undecoded — the server read
    loop takes frames raw so the JSON decode lands inside the request's
    ``parse`` segment (the record opens at frame arrival)."""
    with fault.point("bin.recv"):
        head = _recv_exact(sock, 4)
    if head is None:
        return None
    (n,) = struct.unpack(">I", head)
    return _recv_exact(sock, n)


def recv_frame(sock: socket.socket) -> Optional[dict]:
    body = recv_frame_raw(sock)
    if body is None:
        return None
    return json.loads(body.decode())


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


def _req_is_read(req: dict) -> bool:
    """A command/script op whose every statement classifies as READ
    rides through admission shedding — degradation means read-only,
    not read-nothing."""
    try:
        if req.get("op") == "command":
            _res, action = classify_sql(req.get("sql", ""))
            return action == "read"
        if req.get("op") == "script":
            from orientdb_tpu.exec.script import script_permissions

            return all(
                action == "read"
                for _res, action in script_permissions(
                    req.get("script", "")
                )
            )
    except Exception:
        # unclassifiable (parse error): treat as a write — the error
        # itself surfaces on the direct execution path
        return False
    return False


class _CdcPump:
    """Push loop for one changefeed subscription on a binary session:
    drains the feed consumer's bounded queue and ships event batches as
    unsolicited ``{"push": true, "cdc": true}`` frames (riding the
    live-push framing and the session's send lock). A dead channel ends
    the pump with ONE warning — the events stay redeliverable from the
    consumer's cursor, which is the whole point of the plane."""

    def __init__(self, session: "_Session", consumer) -> None:
        self.session = session
        self.consumer = consumer
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run,
            name=f"cdc-push-{consumer.token}",
            daemon=True,
        )

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        """Idempotent; never joins (the pump may be blocked in a send
        the caller's socket close is about to break)."""
        self._stop.set()
        self.consumer.close()  # wakes a poll() wait
        self.consumer.feed.unregister(self.consumer.token)

    def _run(self) -> None:
        from orientdb_tpu.cdc.feed import CdcGapError
        from orientdb_tpu.obs.trace import span
        from orientdb_tpu.utils.metrics import metrics

        token = self.consumer.token
        while not self._stop.is_set():
            try:
                events = self.consumer.poll(max_events=256, timeout=0.25)
            except CdcGapError as e:
                # the resume point fell off retention: tell the client
                # loudly (it must resync), then end the subscription
                try:
                    self.session._send(
                        {
                            "push": True,
                            "cdc": True,
                            "token": token,
                            "error": str(e),
                            "resync": True,
                        }
                    )
                except OSError:
                    pass
                break
            if not events:
                continue
            if self._stop.is_set():
                # teardown raced the poll: the batch is NOT sent — it
                # remains redeliverable from the cursor, and a frame at
                # a closing socket would be an event to a dead callback
                break
            try:
                with span(
                    "cdc.push", transport="binary", events=len(events)
                ), fault.point("cdc.push"):
                    self.session._send(
                        {
                            "push": True,
                            "cdc": True,
                            "token": token,
                            "events": events,
                        }
                    )
                metrics.incr("cdc.delivered", len(events))
            except OSError:
                log.warning(
                    "cdc push failed for token %s (session gone); "
                    "consumer resumes from its cursor",
                    token,
                )
                break
        self.stop()


class _Session:
    def __init__(self, server, sock: socket.socket) -> None:
        self.server = server
        self.sock = sock
        self.user = None
        self.db = None
        #: responses and live-query push frames share the socket: the
        #: send lock keeps a push from interleaving mid-response ([E] the
        #: binary protocol's push messages ride the session channel too)
        self._send_lock = threading.Lock()
        #: token -> LiveQueryMonitor subscribed over THIS session
        self._live: dict = {}
        #: token -> _CdcPump for changefeed subscriptions on THIS session
        self._cdc: dict = {}
        #: pipeline mode (db_open {"pipeline": true}): query ops run on
        #: this pool and respond out-of-order by reqid
        self._pool = None

    def _send(self, payload: dict) -> None:
        with self._send_lock:
            send_frame(self.sock, payload)

    def _record_payload(self, doc) -> dict:
        """One record for the wire: schema-aware binary bytes
        (base85-framed, self-contained batch envelope carrying the
        class's property dictionary) for sessions that negotiated
        ``serialization: "binary"`` at db_open; plain JSON otherwise."""
        if getattr(self, "binser", False):
            import base64

            from orientdb_tpu.server.binser import encode_records

            return {
                "record_b85": base64.b85encode(
                    encode_records([doc])
                ).decode()
            }
        return {"record": doc.to_dict()}

    def _dispatch_async(self, req: dict) -> None:
        """Pipeline mode: run on the session worker pool, respond by
        reqid when ready (the client demultiplexes out-of-order)."""
        cp = critpath.begin_request("binary", req.get("sql"))
        with critpath.active(cp):
            resp = self._dispatch(req)
            resp["reqid"] = req["reqid"]
            try:
                self._send(resp)
            except OSError:
                pass  # client gone; the recv loop will notice
        critpath.commit(cp)

    def run(self) -> None:
        from orientdb_tpu.obs.trace import roles

        roles.declare("session")
        try:
            while True:
                raw = recv_frame_raw(self.sock)
                if raw is None:
                    break
                # the decomposition record opens at frame arrival so the
                # envelope decode is attributed as parse, not lost ahead
                # of the handler window
                cp = critpath.begin_request("binary")
                with critpath.active(cp):
                    with critpath.segment("parse"):
                        req = json.loads(raw.decode())
                    critpath.note_sql(req.get("sql"))
                if (
                    self._pool is not None
                    and req.get("op") in ("query", "query_batch")
                    and "reqid" in req
                ):
                    # pipelined session: don't block the read loop on
                    # the device — in-flight singles coalesce. The read
                    # loop's record is abandoned (never committed): the
                    # worker owns the request end-to-end and opens its
                    # own
                    self._pool.submit(self._dispatch_async, req)
                    continue
                with critpath.active(cp):
                    resp = self._dispatch(req)
                    # echo the client's correlation id so its channel
                    # can discard stale replies after a response timeout
                    # instead of desynchronizing (client/remote.py _call)
                    if "reqid" in req:
                        resp["reqid"] = req["reqid"]
                    self._send(resp)
                critpath.commit(cp)
                # a cdc_subscribe's pump starts only AFTER its response
                # is on the wire: a catch-up batch pushed ahead of the
                # response would land before the client knows the token
                # and could overflow its orphan buffer (lost events)
                pending = self.__dict__.pop("_pending_pump", None)
                if pending is not None:
                    pending.start()
                if req.get("op") == "close":
                    break
        except OSError:
            pass
        finally:
            if self._pool is not None:
                self._pool.shutdown(wait=False)
            # a dropped session must not leave dangling subscriptions.
            # cdc pumps stop FIRST (their consumers close, waking any
            # in-flight poll) so no event is pushed at the dying socket
            for pump in list(self._cdc.values()):
                pump.stop()
            self._cdc.clear()
            for m in list(self._live.values()):
                try:
                    m.unsubscribe()
                except Exception:
                    log.warning(
                        "live-query unsubscribe failed during "
                        "session teardown", exc_info=True,
                    )
            self._live.clear()
            try:
                self.sock.close()
            except OSError:
                pass
            roles.retire()

    def _dispatch(self, req: dict) -> dict:
        # the envelope's "trace" field is the binary channel's
        # propagation carrier (obs/propagation.inject_frame on the
        # client): this session thread CONTINUES the caller's trace
        from orientdb_tpu.obs.propagation import continue_trace

        with continue_trace(
            f"binary.{req.get('op')}", req.get("trace")
        ):
            return self._dispatch_inner(req)

    def _dispatch_inner(self, req: dict) -> dict:
        op = req.get("op")
        try:
            if op == "connect":
                u = self.server.security.authenticate(
                    req.get("user", ""), req.get("password", "")
                )
                if u is None:
                    return {"ok": False, "error": "invalid credentials"}
                self.user = u
                return {"ok": True, "user": u.name}
            if self.user is None:
                return {"ok": False, "error": "not authenticated"}
            if op == "db_list":
                return {"ok": True, "databases": sorted(self.server.databases)}
            if op == "db_create":
                self.server.security.check(self.user, RES_DATABASE, "create")
                self.server.create_database(req["name"])
                self.db = self.server.get_database(req["name"])
                return {"ok": True}
            if op == "db_open":
                db = self.server.get_database(req["name"])
                if db is None:
                    return {"ok": False, "error": f"no database '{req['name']}'"}
                self.db = db
                # record payload encoding for THIS session ([E] the
                # serialization-impl negotiation of the reference's
                # OPEN op): "binary" routes load/save record payloads
                # through the schema-aware binary format (binser.py)
                self.binser = req.get("serialization") == "binary"
                if req.get("pipeline") and self._pool is None:
                    from concurrent.futures import ThreadPoolExecutor

                    from orientdb_tpu.obs.trace import roles

                    self._pool = ThreadPoolExecutor(
                        max_workers=32,
                        thread_name_prefix="binq",
                        initializer=roles.declare,
                        initargs=("session",),
                    )
                return {"ok": True, "serialization": (
                    "binary" if self.binser else "json"
                )}
            if self.db is None and op != "close":
                return {"ok": False, "error": "no database open"}
            if op in (
                "command", "script", "save", "delete"
            ) and not _req_is_read(req):
                from orientdb_tpu.server.admission import db_pressure

                shed, retry_after = db_pressure(self.db)
                if shed is not None:
                    from orientdb_tpu.utils.metrics import metrics

                    metrics.incr("binary.shed")
                    resp = {
                        "ok": False,
                        "error": shed,
                        "code": 503,
                        "retry_after": retry_after,
                    }
                    if shed.startswith("device memory pressure"):
                        # flag device-domain sheds so clients can tell
                        # device pressure from host overload
                        resp["device"] = True
                    return resp
            if op == "query":
                self.server.security.check(self.user, RES_RECORD, "read")
                # singles ride the cross-session lane path: concurrent
                # sessions' same-shape queries merge into one micro-batch
                rows, engine = self.server.coalescer.submit(
                    self.db, req["sql"], req.get("params")
                )
                return {"ok": True, "result": rows, "engine": engine}
            if op == "query_batch":
                # N statements, ONE frame, one group dispatch ([E] the
                # reference's OQueryRequest has no batch op — this is
                # the TPU-first addition the engine's speed demands)
                self.server.security.check(self.user, RES_RECORD, "read")
                sqls = req.get("sqls") or []
                params_list = req.get("params_list") or [None] * len(sqls)
                if len(params_list) != len(sqls):
                    # a mismatch must not reach the per-item fallback,
                    # whose zip would silently truncate the batch
                    return {
                        "ok": False,
                        "error": "params_list length "
                        f"{len(params_list)} != sqls length {len(sqls)}",
                    }
                results = []
                try:
                    for rs in self.db.query_batch(sqls, params_list):
                        results.append(
                            {"result": rs.to_dicts(), "engine": rs.engine}
                        )
                except Exception:
                    # per-item isolation: one bad statement must not
                    # void its cohort — re-run individually
                    results = []
                    for sql, p in zip(sqls, params_list):
                        try:
                            rs = self.db.query(sql, p)
                            results.append(
                                {
                                    "result": rs.to_dicts(),
                                    "engine": rs.engine,
                                }
                            )
                        except Exception as e:
                            results.append(
                                {"error": f"{type(e).__name__}: {e}"}
                            )
                return {"ok": True, "results": results}
            if op == "command":
                resource, cop = classify_sql(req["sql"])
                self.server.security.check(self.user, resource, cop)
                rs = self.db.command(req["sql"], req.get("params"))
                return {"ok": True, "result": rs.to_dicts(), "engine": rs.engine}
            if op == "script":
                # SQL batch script ([E] the REQUEST_COMMAND script
                # payload): every embedded statement authorizes like a
                # single command — no escalation through scripts
                from orientdb_tpu.exec.script import script_permissions

                for resource, action in sorted(
                    script_permissions(req["script"])
                ):
                    self.server.security.check(self.user, resource, action)
                rs = self.db.execute(
                    req.get("language", "sql"),
                    req["script"],
                    req.get("params"),
                )
                return {
                    "ok": True,
                    "result": rs.to_dicts(),
                    "engine": getattr(rs, "engine", None),
                }
            if op == "load":
                self.server.security.check(self.user, RES_RECORD, "read")
                doc = self.db.load(RID.parse(req["rid"]))
                if doc is None:
                    return {"ok": True, "record": None}
                return {"ok": True, **self._record_payload(doc)}
            if op == "save":
                self.server.security.check(self.user, RES_RECORD, "update")
                from orientdb_tpu.storage.durability import _dec

                payload = dict(req.get("record") or {})
                cls = payload.pop("@class", "O")
                rid = payload.pop("@rid", None)
                payload = {
                    k: _dec(v)
                    for k, v in payload.items()
                    if not k.startswith("@")
                }
                if rid:
                    doc = self.db.load(RID.parse(rid))
                    if doc is None:
                        return {"ok": False, "error": f"record {rid} not found"}
                    for k, v in payload.items():
                        doc.set(k, v)
                    self.db.save(doc)
                else:
                    c = self.db.schema.get_class(cls)
                    if cls == "OBlob":
                        doc = self.db.new_blob(payload.pop("data", b"") or b"")
                        if payload:
                            for k, v in payload.items():
                                doc.set(k, v)
                            self.db.save(doc)
                    elif c is not None and c.is_vertex_type:
                        doc = self.db.new_vertex(cls, **payload)
                    else:
                        doc = self.db.new_element(cls, **payload)
                return {"ok": True, **self._record_payload(doc)}
            if op == "live_subscribe":
                # push delivery over the session channel ([E]
                # OLiveQueryHookV2 pushing to remote clients)
                self.server.security.check(self.user, RES_RECORD, "read")
                from orientdb_tpu.exec.live import live_query

                session = self

                def push(ev, session=session):
                    try:
                        session._send({"push": True, "event": ev})
                    except OSError:
                        pass  # client gone; cleanup happens on recv EOF

                m = live_query(self.db, req["sql"], push)
                self._live[m.token] = m
                return {"ok": True, "token": m.token}
            if op == "cdc_subscribe":
                # resumable changefeed push over the session channel
                # (orientdb_tpu/cdc): {"classes": [...], "where": "...",
                # "since": <lsn> | "cursor": "<name>", "policy":
                # "shed"|"block"} → events arrive as {"push": true,
                # "cdc": true, "token": t, "events": [...]} frames;
                # cdc_ack persists the cursor for reconnect resume
                self.server.security.check(self.user, RES_RECORD, "read")
                from orientdb_tpu.cdc.feed import feed_of, parse_where

                classes = req.get("classes") or None
                where = req.get("where")
                consumer = feed_of(self.db).register(
                    name=req.get("cursor"),
                    classes=classes,
                    where=parse_where(
                        where, classes[0] if classes else None
                    )
                    if where
                    else None,
                    since=req.get("since"),
                    policy=req.get("policy", "shed"),
                )
                pump = _CdcPump(self, consumer)
                self._cdc[consumer.token] = pump
                # started by the run loop AFTER the response is sent
                self._pending_pump = pump
                return {
                    "ok": True,
                    "token": consumer.token,
                    "since": consumer.resume_lsn,
                }
            if op == "cdc_ack":
                pump = self._cdc.get(req.get("token"))
                if pump is None:
                    return {"ok": False, "error": "unknown cdc token"}
                acked = pump.consumer.ack(int(req.get("lsn", 0)))
                return {"ok": True, "lsn": acked}
            if op == "cdc_unsubscribe":
                pump = self._cdc.pop(req.get("token"), None)
                if pump is None:
                    return {"ok": False, "error": "unknown cdc token"}
                pump.stop()
                return {"ok": True}
            if op == "live_unsubscribe":
                m = self._live.pop(req.get("token"), None)
                if m is None:
                    return {"ok": False, "error": "unknown live token"}
                m.unsubscribe()
                return {"ok": True}
            if op == "delete":
                self.server.security.check(self.user, RES_RECORD, "delete")
                doc = self.db.load(RID.parse(req["rid"]))
                if doc is not None:
                    self.db.delete(doc)
                return {"ok": True}
            if op == "close":
                return {"ok": True}
            return {"ok": False, "error": f"unknown op {op!r}"}
        except SecurityError as e:
            return {"ok": False, "error": str(e), "code": 403}
        except Exception as e:  # protocol errors must not kill the session
            # a device fault that escaped every fallback (quarantine
            # raced the oracle path, or relief itself failed) maps to a
            # retryable 503 with the ``device`` marker: by retry_after
            # the escalation ladder has quarantined the plan and the
            # retry lands on the oracle
            from orientdb_tpu.exec import devicefault

            if isinstance(
                e, (devicefault.DeviceFaultError, devicefault.DeviceQuarantined)
            ):
                return {
                    "ok": False,
                    "error": f"{type(e).__name__}: {e}",
                    "code": 503,
                    "retry_after": float(
                        getattr(e, "retry_after", None) or 0.5
                    ),
                    "device": True,
                }
            return {"ok": False, "error": f"{type(e).__name__}: {e}"}


class BinaryListener:
    def __init__(self, ot_server, port: int = 0) -> None:
        self.server = ot_server
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", port))
        self.sock.listen(16)
        self.port = self.sock.getsockname()[1]
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._accept_loop, name="binary-listener", daemon=True
        )
        self._thread.start()

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _addr = self.sock.accept()
            except OSError:
                break
            # one thread per accepted socket, like the reference's listener
            threading.Thread(
                target=_Session(self.server, conn).run, daemon=True
            ).start()

    def stop(self) -> None:
        self._stop.set()
        try:
            self.sock.close()
        except OSError:
            pass
