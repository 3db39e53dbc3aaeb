"""Closed-loop production traffic simulator with an SLO verdict.

Every scenario before this PR measures ONE query shape at a time; the
north star ("millions of users") is mixed traffic with failures. This
module drives the whole serving surface at once, deterministically:

- a **seeded schedule** (:func:`build_schedule`) of LDBC SNB
  interactive operations — the IS1–7 short reads and IC1/IC2/ICA
  complex reads from ``workloads/ldbc.py``, mixed with inserts/updates
  at the SNB update ratio (``workload_update_ratio``) and cross-owner
  2PC transactions — same seed, same schedule, byte for byte
  (``schedule_digest`` proves it);
- **many concurrent closed-loop client sessions** over BOTH transports
  (binary protocol sessions via ``client/remote``, HTTP sessions via
  the REST routes — every simulated HTTP request crosses the
  ``workload.http`` fault point) against a **real multi-member
  cluster** (primary + replicas, one class write-owned by a replica so
  transactions 2-phase commit across members), with live CDC consumers
  attached on both transports;
- a deterministic **chaos phase**: a seeded :class:`chaos.FaultPlan`
  armed for the traffic window plus a scheduled replica kill/restart
  (and optionally a mid-run primary failover), then a **settle phase**
  that keeps issuing light traffic so replicas catch up, tripped
  breakers half-open and close, and alerts resolve — the run must end
  *recovered*, not mid-incident;
- one **SLO verdict** (obs/slo): per-class p50/p99 and availability
  read from the query-stats histograms over this run's window, no
  alert left firing, error-budget burn in target. The report is
  machine-readable and reproducible: same seed, same verdict.

``TrafficSim(seed=7).run()`` returns the full run report (schedule
digest, per-kind op/error counts, CDC delivery counts, chaos fires,
and the SLO report under ``"slo"``).
"""

from __future__ import annotations

import json
import hashlib
import random
import threading
import time
import urllib.parse
import urllib.request
from typing import Dict, List, NamedTuple, Optional

from orientdb_tpu.chaos.faults import FaultPlan, fault
from orientdb_tpu.obs.slo import SloClass, SloSpec, engine as slo_engine
from orientdb_tpu.obs.stats import stats
from orientdb_tpu.obs.trace import span
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.logging import get_logger
from orientdb_tpu.utils.metrics import metrics
from orientdb_tpu.workloads.ldbc import IC_QUERIES, IS_QUERIES

log = get_logger("workload")

#: read-op kinds and their mix weights (short reads dominate, the SNB
#: interactive shape); writes are drawn separately at the update ratio
READ_KINDS = tuple(sorted(IS_QUERIES)) + tuple(sorted(IC_QUERIES))
READ_WEIGHTS = (4, 4, 4, 4, 4, 4, 4, 1, 1, 1)

#: write-op kinds drawn at the update ratio. Session 0 leans on the
#: cross-owner transaction (it alone runs the embedded 2PC path — one
#: database handle must not see concurrent begin()s); the other
#: sessions split inserts/updates
WRITE_KINDS = ("insert", "update", "tx2pc")
WRITE_WEIGHTS = (5, 3, 2)
WRITE_WEIGHTS_TX = (2, 1, 7)

#: the synthetic statement the cross-owner transaction's latency and
#: errors are recorded under (stats.record_external) — the SLO spec's
#: tx2pc class joins the stats table on its fingerprint
TX2PC_SQL = "COMMIT CROSS OWNER SIM"

#: id space for simulator-inserted messages, far above any generated id
_SIM_ID_BASE = 10_000_000


class Op(NamedTuple):
    kind: str  #: IS1..IS7 | IC1 | IC2 | ICA | insert | update | tx2pc
    sql: str  #: parameterized read, or a literal write statement
    params: Optional[Dict]


def _inline(sql: str, params: Optional[Dict]) -> str:
    """Render ``:name`` parameters as literals (the HTTP sessions'
    form — the REST routes take raw SQL). Longest names substitute
    first so a shared prefix can never corrupt a sibling."""
    if not params:
        return sql
    for k in sorted(params, key=len, reverse=True):
        v = params[k]
        lit = (
            "'" + str(v).replace("'", "\\'") + "'"
            if isinstance(v, str)
            else str(v)
        )
        sql = sql.replace(":" + k, lit)
    return sql


def build_schedule(
    seed: int,
    sessions: int,
    ops_per_session: int,
    update_ratio: float,
    n_persons: int,
    n_messages: int,
    first_name: str = "A",
) -> List[List[Op]]:
    """The deterministic event schedule: one op list per session, every
    draw from one ``random.Random(seed)`` in fixed order — same inputs,
    same schedule, regardless of how threads later interleave."""
    rng = random.Random(seed)
    next_id = _SIM_ID_BASE
    schedule: List[List[Op]] = []
    for s in range(sessions):
        ops: List[Op] = []
        for _i in range(ops_per_session):
            if rng.random() < update_ratio:
                kind = rng.choices(
                    WRITE_KINDS,
                    WRITE_WEIGHTS_TX if s == 0 else WRITE_WEIGHTS,
                )[0]
                if kind == "tx2pc" and s != 0:
                    kind = "insert"  # one embedded tx path (session 0)
                if kind == "insert":
                    next_id += 1
                    ops.append(
                        Op(
                            "insert",
                            f"INSERT INTO Post SET id = {next_id}, "
                            f"content = 'sim', creationDate = "
                            f"{1_000_000 + next_id}",
                            None,
                        )
                    )
                elif kind == "update":
                    pid = rng.randrange(max(n_persons, 1))
                    ops.append(
                        Op(
                            "update",
                            "UPDATE Person SET browserUsed = "
                            f"'sim{_i}' WHERE id = {pid}",
                            None,
                        )
                    )
                else:
                    next_id += 1
                    ops.append(Op("tx2pc", TX2PC_SQL, {"uid": next_id}))
                continue
            kind = rng.choices(READ_KINDS, READ_WEIGHTS)[0]
            sql = (
                IS_QUERIES[kind] if kind in IS_QUERIES else IC_QUERIES[kind]
            )
            if ":personId" in sql:
                p: Dict = {"personId": rng.randrange(max(n_persons, 1))}
            else:
                p = {"messageId": rng.randrange(max(n_messages, 1))}
            if kind == "IC1":
                p["firstName"] = first_name
            elif kind == "IC2":
                p["maxDate"] = 2**30 + rng.randrange(100_000)
            ops.append(Op(kind, sql, p))
        schedule.append(ops)
    return schedule


def schedule_digest(schedule: List[List[Op]]) -> str:
    """Stable digest of one schedule (the determinism receipt carried
    in the run report: same seed, same digest)."""
    doc = [[(o.kind, o.sql, o.params) for o in ops] for ops in schedule]
    return hashlib.blake2b(
        json.dumps(doc, sort_keys=True).encode(), digest_size=8
    ).hexdigest()


def default_slo_spec(
    first_name: str = "A",
    p50_ms: Optional[float] = None,
    p99_ms: Optional[float] = None,
    availability: Optional[float] = None,
    kinds: Optional[set] = None,
) -> SloSpec:
    """The spec a simulator run is judged against: one class per read
    kind (both the parameterized and literal-inlined spellings — the
    two transports fingerprint differently), plus the write and 2PC
    classes. Targets default to the ``slo_*`` config keys; the chaos-
    facing write/2PC classes check latency only by default (the chaos
    plan EXISTS to fail some of them — run-wide damage is bounded by
    the error-budget burn policy instead). ``kinds`` limits the spec
    to the op kinds one schedule actually drew (a short run must not
    fail ``no_traffic`` on a class it never scheduled)."""
    classes = []
    example = {
        "personId": 1,
        "messageId": 1,
        "firstName": first_name,
        "maxDate": 2**30,
    }
    for kind in READ_KINDS:
        if kinds is not None and kind not in kinds:
            continue
        sql = IS_QUERIES[kind] if kind in IS_QUERIES else IC_QUERIES[kind]
        used = {
            k: v
            for k, v in example.items()
            if ":" + k in sql
        }
        classes.append(
            SloClass(
                kind,
                [sql, _inline(sql, used)],
                p50_ms=p50_ms,
                p99_ms=p99_ms,
                availability=availability,
            )
        )
    writes = (
        ("insert", "INSERT INTO Post SET id = 1, content = 'sim', "
         "creationDate = 1"),
        ("update", "UPDATE Person SET browserUsed = 'sim1' WHERE id = 1"),
        ("tx2pc", TX2PC_SQL),
    )
    for kind, sql in writes:
        if kinds is not None and kind not in kinds:
            continue
        classes.append(
            SloClass(
                kind, [sql],
                p50_ms=p50_ms, p99_ms=p99_ms, availability=0.0,
            )
        )
    return SloSpec(classes)


class _HttpSession:
    """One closed-loop HTTP client: reads via ``GET /query``, writes
    via ``POST /command`` (raw SQL, parameters inlined). Knows both
    members' ports so it retries once against the sibling on a
    transport failure — the poor operator's failover client."""

    def __init__(self, ports: List[int], dbname: str, password: str) -> None:
        import base64

        self.urls = [f"http://127.0.0.1:{p}" for p in ports]
        self.dbname = dbname
        cred = base64.b64encode(f"admin:{password}".encode()).decode()
        self.headers = {"Authorization": f"Basic {cred}"}

    def _http_call(self, base: str, op: Op) -> None:
        sql = _inline(op.sql, op.params)
        if op.kind in ("insert", "update"):
            req = urllib.request.Request(
                f"{base}/command/{self.dbname}/sql",
                data=json.dumps({"command": sql}).encode(),
                headers=self.headers,
                method="POST",
            )
        else:
            q = urllib.parse.quote(sql, safe="")
            req = urllib.request.Request(
                f"{base}/query/{self.dbname}/sql/{q}",
                headers=self.headers,
            )
        with fault.point("workload.http"):
            with urllib.request.urlopen(req, timeout=15) as r:
                r.read()

    def run_op(self, op: Op) -> None:
        try:
            self._http_call(self.urls[0], op)
        except urllib.error.HTTPError:
            # a non-2xx is a DEFINITIVE server answer (the server-side
            # stats table already recorded any execution error) — never
            # replayed against the sibling: a non-idempotent write must
            # not run twice, and the error must not count twice
            raise
        except (urllib.error.URLError, OSError):
            # connection-level failure: one retry against the sibling
            # member, READS ONLY — a timed-out write may already have
            # executed on the first member (the response, not the
            # request, can be what was lost), and replaying it would
            # apply it twice
            if len(self.urls) < 2 or op.kind in ("insert", "update"):
                raise
            self._http_call(self.urls[1], op)

    def close(self) -> None:
        pass


class _BinarySession:
    """One closed-loop binary-protocol client (a FailoverDatabase when
    both members' ports are known, so a mid-run failover re-routes)."""

    def __init__(self, ports: List[int], dbname: str, password: str) -> None:
        from orientdb_tpu.client.remote import connect

        hosts = ";".join(f"127.0.0.1:{p}" for p in ports)
        self.db = connect(f"remote:{hosts}/{dbname}", "admin", password)

    def run_op(self, op: Op) -> None:
        if op.kind in ("insert", "update"):
            self.db.command(_inline(op.sql, op.params))
        else:
            self.db.query(op.sql, op.params).to_dicts()

    def close(self) -> None:
        self.db.close()


class TrafficSim:
    """One reproducible closed-loop run. Construction is cheap; the
    cluster builds and the sessions run inside :meth:`run`."""

    def __init__(
        self,
        seed: int = 0,
        persons: int = 120,
        sessions: Optional[int] = None,
        ops_per_session: Optional[int] = None,
        update_ratio: Optional[float] = None,
        replicas: int = 1,
        chaos: Optional[FaultPlan] = None,
        replica_outage: Optional[tuple] = (0.3, 0.6),
        promote_at: Optional[float] = None,
        cdc_consumers: int = 2,
        spec: Optional[SloSpec] = None,
        settle_s: Optional[float] = None,
        tick_s: float = 0.2,
        reset_alerts: bool = True,
        dbname: str = "simdb",
        password: str = "pw",
    ) -> None:
        self.seed = seed
        self.persons = persons
        self.sessions = (
            config.workload_sessions if sessions is None else sessions
        )
        self.ops_per_session = (
            config.workload_ops if ops_per_session is None else ops_per_session
        )
        self.update_ratio = (
            config.workload_update_ratio
            if update_ratio is None
            else update_ratio
        )
        self.replicas = max(replicas, 1)
        self.chaos = chaos
        self.replica_outage = replica_outage
        self.promote_at = promote_at
        self.cdc_consumers = cdc_consumers
        self.spec = spec
        self.settle_s = (
            config.workload_settle_s if settle_s is None else settle_s
        )
        self.tick_s = tick_s
        self.reset_alerts = reset_alerts
        self.dbname = dbname
        self.password = password
        # shared mutable run state: containers only (threads mutate
        # them under _mu; no attribute is rebound after __init__)
        self._mu = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._client_errors: Dict[str, int] = {}
        self._state = {"completed": 0, "cdc_events": 0, "stop": False}
        self._schedule: List[List[Op]] = []
        self._harness: Dict[str, object] = {}

    # -- counters (tiny lock sections; never I/O under _mu) -----------------

    def _bump(self, table: Dict[str, int], kind: str) -> None:
        with self._mu:
            table[kind] = table.get(kind, 0) + 1

    def _completed(self) -> int:
        with self._mu:
            self._state["completed"] += 1
            return self._state["completed"]

    # -- cluster harness ----------------------------------------------------

    def _build(self) -> None:
        """Generate the SNB graph on the primary, replicate it to every
        member, and hand one class's write ownership to a replica so
        the tx2pc ops actually cross members."""
        from orientdb_tpu.parallel.cluster import Cluster
        from orientdb_tpu.server.server import Server
        from orientdb_tpu.storage.ingest import generate_ldbc_snb

        servers = [
            Server(name=f"sim{i}", admin_password=self.password)
            for i in range(1 + self.replicas)
        ]
        for s in servers:
            s.startup()
        pdb = servers[0].create_database(self.dbname)
        cl = Cluster(
            self.dbname, user="admin", password=self.password,
            interval=0.1, down_after=10_000,
        )
        cl.set_primary("n0", servers[0], pdb)
        for i in range(1, 1 + self.replicas):
            cl.add_replica(f"n{i}", servers[i])
        cl.start()
        generate_ldbc_snb(db=pdb, n_persons=self.persons, seed=self.seed)
        pdb.schema.create_vertex_class("SimEvent")
        pdb.schema.create_vertex_class("SimAudit")
        n_messages = pdb.count_class("Post") + pdb.count_class("Comment")
        first = next(pdb.browse_class("Person")).get("firstName") or "A"
        # every replica must hold the dataset before traffic starts
        # (reads serve anywhere, and the 2PC owner validates schema)
        want = pdb.count_class("Person")
        deadline = time.monotonic() + 60
        for i in range(1, 1 + self.replicas):
            m = cl.members[f"n{i}"]
            while time.monotonic() < deadline:
                m.puller.pull_once()
                try:
                    if m.db.count_class("Person") >= want:
                        break
                except ValueError:
                    pass
                time.sleep(0.05)
        cl.assign_class_owner("SimAudit", "n1")
        self._harness.update(
            servers=servers, cluster=cl, pdb=pdb,
            n_messages=n_messages, first_name=first,
        )

    def _teardown(self) -> None:
        cl = self._harness.get("cluster")
        if cl is not None:
            try:
                cl.stop()
            except Exception:
                log.exception("cluster stop failed")
        for s in self._harness.get("servers", ()):
            try:
                s.shutdown()
            except Exception:
                log.exception("server shutdown failed")

    # -- op execution --------------------------------------------------------

    def _run_tx2pc(self, op: Op) -> None:
        """One cross-owner transaction from the embedded primary
        handle: a SimEvent (primary-owned) plus a SimAudit (replica-
        owned) commit all-or-nothing through parallel/twophase. Its
        latency and outcome fold into the stats table under
        :data:`TX2PC_SQL` so the SLO plane judges it like any query
        class."""
        pdb = self._harness["pdb"]
        uid = op.params["uid"]
        t0 = time.perf_counter()
        err: Optional[BaseException] = None
        try:
            pdb.begin()
            pdb.new_vertex("SimEvent", uid=uid)
            pdb.new_vertex("SimAudit", uid=uid)
            pdb.commit()
        except Exception as e:
            err = e
            tx = getattr(pdb, "tx", None)
            if tx is not None:
                try:
                    tx.rollback()
                except Exception:
                    log.exception("tx2pc rollback failed")
        stats.record_external(
            TX2PC_SQL, time.perf_counter() - t0, engine="tx2pc", error=err
        )
        if err is not None:
            raise err

    def _session_run(self, idx: int, client) -> None:
        """One closed-loop session: issue the next op when the previous
        completes; client-side transport failures count against the
        run (and fold into the stats table — the server never saw
        them, but the USER did)."""
        from orientdb_tpu.client.remote import (
            RemoteConnectionError,
            ServerOverloadedError,
        )

        with span("workload.session", session=idx):
            for op in self._schedule[idx]:
                self._bump(self._counts, op.kind)
                metrics.incr("workload.ops")
                try:
                    if op.kind == "tx2pc":
                        self._run_tx2pc(op)
                    else:
                        client.run_op(op)
                except urllib.error.HTTPError as e:
                    # a definitive HTTP status: an execution error was
                    # already recorded server-side — except a 503 shed,
                    # which the admission layer refuses BEFORE the
                    # engine front door, so the failed call is recorded
                    # here (availability must see shed traffic)
                    self._bump(self._client_errors, op.kind)
                    metrics.incr("workload.client_errors")
                    if e.code == 503 and op.kind != "tx2pc":
                        stats.record_external(
                            op.sql, 0.0, engine="client", error=e
                        )
                except (
                    ServerOverloadedError,
                    RemoteConnectionError,
                    urllib.error.URLError,
                    OSError,
                ) as e:
                    # transport-level failure (or a binary-channel
                    # shed): the server-side stats table never saw
                    # this op, so record the failed call here —
                    # availability must reflect what the client
                    # observed
                    self._bump(self._client_errors, op.kind)
                    metrics.incr("workload.client_errors")
                    if op.kind != "tx2pc":
                        stats.record_external(
                            op.sql, 0.0, engine="client", error=e
                        )
                except Exception:
                    # the server recorded this one (stats error path)
                    self._bump(self._client_errors, op.kind)
                    metrics.incr("workload.client_errors")
                self._completed()

    # -- chaos / control -----------------------------------------------------

    def _controller(self, watchdog, total_ops: int) -> None:
        """Ticks the watchdog through the run and executes the
        scheduled infrastructure events (replica kill/restart, the
        optional failover) at their op-count thresholds."""
        cl = self._harness["cluster"]
        kill_at = restart_at = promote_op = None
        if self.replica_outage is not None:
            kill_at = int(self.replica_outage[0] * total_ops)
            restart_at = int(self.replica_outage[1] * total_ops)
        if self.promote_at is not None:
            promote_op = int(self.promote_at * total_ops)
        killed = restarted = promoted = False
        while True:
            with self._mu:
                done = self._state["completed"]
                stop = self._state["stop"]
            if stop:
                return
            if kill_at is not None and not killed and done >= kill_at:
                killed = True
                log.warning("chaos: killing replica n1 (op %d)", done)
                cl.stop_replica("n1")
            if (
                restart_at is not None
                and killed
                and not restarted
                and done >= restart_at
            ):
                restarted = True
                log.warning("chaos: restarting replica n1 (op %d)", done)
                cl.restart_replica("n1")
            if promote_op is not None and not promoted and done >= promote_op:
                promoted = True
                log.warning("chaos: promoting n1 (op %d)", done)
                cl.promote("n1")
            try:
                watchdog.tick()
            except Exception:
                log.exception("watchdog tick failed mid-run")
            time.sleep(self.tick_s)

    def _settle(self, watchdog) -> Dict[str, object]:
        """Post-chaos recovery: light clean traffic (each round probes
        any tripped breaker and advances replication), replica
        catch-up, and watchdog ticks, until no alert is firing and no
        breaker is open — or the settle budget runs out. The verdict
        judges the END state, so an unrecovered run fails loudly."""
        from orientdb_tpu.obs.alerts import engine as alert_engine
        from orientdb_tpu.parallel.resilience import breaker_snapshot

        cl = self._harness["cluster"]
        deadline = time.monotonic() + self.settle_s
        rounds = 0
        uid = _SIM_ID_BASE + 900_000
        while True:
            rounds += 1
            for m in cl.members.values():
                if m.role == "REPLICA" and m.puller is not None:
                    try:
                        m.puller.pull_once()
                    except Exception:
                        log.exception("settle pull failed")
            open_breakers = [
                n
                for n, b in breaker_snapshot().items()
                if b["state"] == "open"
            ]
            if open_breakers:
                # one clean cross-owner tx probes the forward channel
                # (half-open after reset_s) so the breaker can close
                uid += 1
                try:
                    self._run_tx2pc(Op("tx2pc", TX2PC_SQL, {"uid": uid}))
                except Exception:
                    log.warning("settle probe tx failed (breaker warm-up)")
            try:
                watchdog.tick()
            except Exception:
                # a mid-recovery tick may race a half-restarted member;
                # the verdict must still be produced from the end state
                log.exception("watchdog tick failed during settle")
            firing = [
                a
                for a in alert_engine.active()
                if a["state"] == "firing"
            ]
            if not firing and not open_breakers:
                return {"rounds": rounds, "settled": True}
            if time.monotonic() > deadline:
                return {
                    "rounds": rounds,
                    "settled": False,
                    "firing": [a["rule"] for a in firing],
                    "open_breakers": open_breakers,
                }
            time.sleep(self.tick_s)

    # -- the run -------------------------------------------------------------

    def run(self) -> Dict[str, object]:
        from orientdb_tpu.obs.alerts import engine as alert_engine
        from orientdb_tpu.obs.watchdog import HealthWatchdog

        t_start = time.perf_counter()
        if self.reset_alerts:
            # the verdict judges THIS run: ambient alert lifecycle
            # state from earlier traffic must not leak into it
            alert_engine.reset()
        self._build()
        cdc_clients = []
        try:
            servers = self._harness["servers"]
            pdb = self._harness["pdb"]
            self._schedule = build_schedule(
                self.seed,
                self.sessions,
                self.ops_per_session,
                self.update_ratio,
                self.persons,
                self._harness["n_messages"],
                self._harness["first_name"],
            )
            digest = schedule_digest(self._schedule)
            kinds = {
                op.kind for ops in self._schedule for op in ops
            }
            spec = self.spec or default_slo_spec(
                self._harness["first_name"], kinds=kinds
            )
            slo_run = slo_engine.begin(spec)
            cdc_clients = self._attach_cdc(servers)
            watchdog = HealthWatchdog(servers[0])  # manual ticks
            http_ports = [s.http_port for s in servers[:2]]
            bin_ports = [s.binary_port for s in servers[:2]]
            clients = []
            for i in range(self.sessions):
                if i % 2 == 0:
                    clients.append(
                        _BinarySession(bin_ports, self.dbname, self.password)
                    )
                else:
                    clients.append(
                        _HttpSession(http_ports, self.dbname, self.password)
                    )
            total_ops = sum(len(ops) for ops in self._schedule)
            threads = [
                threading.Thread(
                    target=self._session_run,
                    args=(i, clients[i]),
                    name=f"sim-session-{i}",
                    daemon=True,  # a wedged session must not pin exit
                )
                for i in range(self.sessions)
            ]
            controller = threading.Thread(
                target=self._controller,
                args=(watchdog, total_ops),
                name="sim-controller",
                # daemon: even if the stop/join below is skipped by an
                # unexpected unwind, a ticking controller must never
                # pin the interpreter open at exit (the bench-headline
                # rc-124 failure mode)
                daemon=True,
            )
            try:
                with span(
                    "workload.run", seed=self.seed, sessions=self.sessions
                ):
                    controller.start()
                    if self.chaos is not None:
                        with fault.armed(self.chaos):
                            for t in threads:
                                t.start()
                            for t in threads:
                                t.join()
                    else:
                        for t in threads:
                            t.start()
                        for t in threads:
                            t.join()
                    settle = self._settle(watchdog)
            finally:
                # ANY unwind (a session crash, a harness interrupt)
                # must stop the controller before the cluster tears
                # down under it, and must close every client session
                with self._mu:
                    self._state["stop"] = True
                controller.join(timeout=10)
                for c in clients:
                    try:
                        c.close()
                    except Exception:
                        log.exception("session close failed")
            report = slo_engine.finish(
                slo_run,
                extra={
                    "seed": self.seed,
                    "schedule_digest": digest,
                },
            )
            with self._mu:
                counts = dict(self._counts)
                errors = dict(self._client_errors)
                cdc_events = self._state["cdc_events"]
            chaos_doc = None
            if self.chaos is not None:
                chaos_doc = {
                    "seed": self.chaos.seed,
                    "points": sorted(self.chaos.rules),
                    "fired": self.chaos.fired(),
                }
            return {
                "seed": self.seed,
                "sessions": self.sessions,
                "ops_per_session": self.ops_per_session,
                "update_ratio": self.update_ratio,
                "persons": self.persons,
                "schedule_digest": digest,
                "ops": counts,
                "client_errors": errors,
                "cdc": {
                    "consumers": len(cdc_clients),
                    "events": cdc_events,
                },
                "chaos": chaos_doc,
                "replica_outage": (
                    list(self.replica_outage)
                    if self.replica_outage
                    else None
                ),
                "settle": settle,
                "wall_s": round(time.perf_counter() - t_start, 3),
                "slo": report,
            }
        finally:
            self._detach_cdc(cdc_clients)
            self._teardown()

    # -- CDC consumers -------------------------------------------------------

    def _attach_cdc(self, servers) -> List:
        """Live changefeed consumers on both transports: binary push
        subscriptions counting deliveries, plus one HTTP long-poll
        consumer thread when two or more are requested."""
        from orientdb_tpu.client.remote import connect

        out = []

        def _on_event(_ev) -> None:
            with self._mu:
                self._state["cdc_events"] += 1

        n_binary = max(self.cdc_consumers - 1, 0)
        for _ in range(n_binary or (1 if self.cdc_consumers else 0)):
            c = connect(
                f"remote:127.0.0.1:{servers[0].binary_port}/{self.dbname}",
                "admin",
                self.password,
            )
            c.cdc_subscribe(_on_event)
            out.append(c)
        if self.cdc_consumers >= 2:
            stop = threading.Event()
            t = threading.Thread(
                target=self._http_cdc_poll,
                args=(servers[0].http_port, stop),
                name="sim-cdc-http",
            )
            t.start()
            out.append((stop, t))
        return out

    def _http_cdc_poll(self, port: int, stop: threading.Event) -> None:
        import base64

        cred = base64.b64encode(
            f"admin:{self.password}".encode()
        ).decode()
        since = None  # a fresh named cursor starts at the head
        while not stop.is_set():
            url = (
                f"http://127.0.0.1:{port}/changes/{self.dbname}"
                f"?cursor=sim-http&timeout=0.3&limit=200"
                + (f"&since={since}" if since is not None else "")
            )
            req = urllib.request.Request(
                url, headers={"Authorization": f"Basic {cred}"}
            )
            try:
                with fault.point("workload.http"):
                    with urllib.request.urlopen(req, timeout=10) as r:
                        doc = json.loads(r.read())
                since = max(
                    since or 0, int(doc.get("cursor", since or 0))
                )
                n = len(doc.get("events", ()))
                if n:
                    with self._mu:
                        self._state["cdc_events"] += n
            except Exception:
                # chaos may sever a poll; the loop resumes from its
                # cursor — exactly the consumer behavior CDC promises
                time.sleep(0.05)

    def _detach_cdc(self, cdc_clients) -> None:
        for c in cdc_clients:
            try:
                if isinstance(c, tuple):
                    stop, t = c
                    stop.set()
                    t.join(timeout=5)
                else:
                    c.close()
            except Exception:
                log.exception("cdc consumer teardown failed")


def default_chaos_plan(seed: int) -> FaultPlan:
    """The default seeded fault schedule: enough consecutive
    forward-channel drops to trip the ``fwd:`` breaker mid-run (2PC
    prepares retry through them, then fail fast while it is open),
    dropped replica pulls (lag builds, then heals), and jittered
    binary-frame delays — all replayable by seed."""
    return (
        FaultPlan(seed)
        .at("fwd.req", "drop", times=8, after=1)
        .at("repl.pull", "drop", times=3)
        .at("bin.send", "delay", times=12, delay_s=0.002)
    )
