"""The server process ([E] OServer / OServerMain, SURVEY.md §3.1).

Hosts named databases, a security manager, a plugin registry (the
OServerPluginAbstract seam the north star hooks into), and two listeners:
HTTP/REST (`http_server`, the port-2480 analog) and the length-prefixed
binary channel (`binary_server`, the port-2424 analog). Listeners bind
ephemeral ports by default so in-process multi-server tests work exactly
like the reference's multi-OServer-per-JVM distributed tests
(SURVEY.md §4).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from orientdb_tpu.models.database import Database
from orientdb_tpu.models.security import SecurityManager
from orientdb_tpu.utils.logging import get_logger

log = get_logger("server")


def _maybe_resume_scheduler(db) -> None:
    """Start the database's scheduler loop when OSchedule events exist
    ([E] the scheduler starts with the database). Shared by the open
    and server-restart paths; never blocks either."""
    try:
        from orientdb_tpu.exec.scheduler import SCHEDULE_CLASS

        if db.schema.exists_class(SCHEDULE_CLASS) and any(
            True for _ in db.browse_class(SCHEDULE_CLASS)
        ):
            db.scheduler.start()
    except Exception:  # pragma: no cover - never blocks open/startup
        log.exception("scheduler resume failed for '%s'", db.name)


class ServerPlugin:
    """Lifecycle SPI ([E] OServerPluginAbstract): subclass and register."""

    name = "plugin"

    def config(self, server: "Server", params: Dict) -> None:  # noqa: D401
        pass

    def startup(self) -> None:
        pass

    def shutdown(self) -> None:
        pass


class Server:
    def __init__(
        self,
        name: str = "orientdb-tpu",
        admin_password: str = "admin",
        http_port: int = 0,
        binary_port: int = 0,
    ) -> None:
        self.name = name
        self.databases: Dict[str, Database] = {}
        self.security = SecurityManager(admin_password)
        # audit trail ([E] the security module's auditing plugin): auth
        # events always; attach databases via audit.watch_database
        from orientdb_tpu.server.audit import AuditLog

        self.audit = AuditLog()
        self.security.audit = self.audit
        self.plugins: List[ServerPlugin] = []
        # cross-session query coalescing (server/coalesce.py): concurrent
        # sessions' single queries ride one batched device dispatch
        from orientdb_tpu.server.coalesce import QueryCoalescer

        self.coalescer = QueryCoalescer()
        #: the cluster coordinator this server is a member of, set by
        #: parallel/cluster.Cluster at registration — the aggregation
        #: endpoints (/cluster/health, /cluster/metrics; obs/
        #: cluster_view) read it; None for a standalone server
        self.cluster = None
        self._lock = threading.Lock()
        self._watchdog = None
        self._http = None
        self._binary = None
        self._http_port = http_port
        self._binary_port = binary_port
        self.running = False

    # -- databases ----------------------------------------------------------

    _DB_NAME_RE = None  # compiled lazily

    @classmethod
    def _check_db_name(cls, name: str) -> None:
        """Database names become directory names under wal_dir — reject
        anything that could traverse out of it (client-supplied via the
        HTTP/binary create-database endpoints)."""
        import re

        if cls._DB_NAME_RE is None:
            cls._DB_NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]*\Z")
        if (
            not name
            or len(name) > 128
            or ".." in name
            or not cls._DB_NAME_RE.match(name)
        ):
            raise ValueError(f"invalid database name {name!r}")

    def create_database(self, name: str) -> Database:
        with self._lock:
            self._check_db_name(name)
            if name in self.databases:
                raise ValueError(f"database '{name}' exists")
            from orientdb_tpu.utils.config import config

            if config.wal_enabled and config.wal_dir:
                # durable server databases: recover-or-create under
                # <wal_dir>/<name> (the plocal-analog path)
                from orientdb_tpu.storage.durability import open_database

                import os

                db = open_database(os.path.join(config.wal_dir, name), name)
            else:
                db = Database(name)
            # SQL GRANT/REVOKE/CREATE USER on this database mutate the
            # SERVER's security manager (exec/dml._security_of)
            db._security = self.security
            self.databases[name] = db
            # a durable database recovered with OSchedule events resumes
            # firing them ([E] the scheduler starts with the database)
            _maybe_resume_scheduler(db)
            return db

    def get_database(self, name: str) -> Optional[Database]:
        return self.databases.get(name)

    def drop_database(self, name: str) -> bool:
        with self._lock:
            db = self.databases.pop(name, None)
        if db is not None:
            # the coalescer's worker thread must not outlive (and pin)
            # the dropped database — nor may its scheduler keep firing
            # functions into a detached store
            self.coalescer.evict(db)
            sch = getattr(db, "_scheduler", None)
            if sch is not None:
                sch.stop()
        return db is not None

    def attach_database(self, db: Database) -> Database:
        with self._lock:
            old = self.databases.get(db.name)
            self.databases[db.name] = db
        if old is not None and old is not db:
            self.coalescer.evict(old)
        return db

    # -- plugins ------------------------------------------------------------

    def register_plugin(self, plugin: ServerPlugin, params: Optional[Dict] = None):
        plugin.config(self, params or {})
        self.plugins.append(plugin)
        if self.running:
            plugin.startup()
        return plugin

    # -- lifecycle ----------------------------------------------------------

    def startup(self) -> "Server":
        from orientdb_tpu.server.binary_server import BinaryListener
        from orientdb_tpu.server.coalesce import QueryCoalescer
        from orientdb_tpu.server.http_server import HttpListener

        if self.coalescer._stopped:
            # shutdown() stops the coalescer permanently; a restarted
            # server must not silently lose the cross-session group path
            self.coalescer = QueryCoalescer()
        # symmetric with shutdown()'s scheduler stop: databases still
        # attached with OSchedule events resume firing
        for db in list(self.databases.values()):
            _maybe_resume_scheduler(db)
        for p in self.plugins:
            p.startup()
        self._http = HttpListener(self, self._http_port)
        self._http.start()
        self._binary = BinaryListener(self, self._binary_port)
        self._binary.start()
        # scrape-time memory telemetry over this server's databases
        # (snapshot column/adjacency bytes, WAL segment bytes —
        # obs/profile refreshes them on every /metrics snapshot)
        from orientdb_tpu.obs.profile import register_server_telemetry

        self._telemetry_provider = register_server_telemetry(self)
        # health watchdog (obs/watchdog): periodic alert-rule
        # evaluation over this server's databases + cluster — started
        # and stopped with the server, like Cluster's probe thread
        from orientdb_tpu.utils.config import config

        if config.watchdog_enabled:
            from orientdb_tpu.obs.watchdog import HealthWatchdog

            self._watchdog = HealthWatchdog(self).start()
        # every garbage collection counted and timed while serving
        # (obs/trace.gc_clock), never in a client's process
        if not self.running:
            from orientdb_tpu.obs.trace import gc_clock

            gc_clock.install()
        self.running = True
        log.info(
            "server '%s' up: http=%d binary=%d",
            self.name,
            self.http_port,
            self.binary_port,
        )
        return self

    def shutdown(self) -> None:
        if self.running:
            from orientdb_tpu.obs.trace import gc_clock

            gc_clock.uninstall()
        self.running = False
        wd = self._watchdog
        if wd is not None:
            self._watchdog = None
            wd.stop()
        for p in self.plugins:
            try:
                p.shutdown()
            except Exception:
                log.exception("plugin %s shutdown failed", p.name)
        if self._http is not None:
            self._http.stop()
        if self._binary is not None:
            self._binary.stop()
        provider = getattr(self, "_telemetry_provider", None)
        if provider is not None:
            from orientdb_tpu.obs.profile import unregister_gauge_provider

            unregister_gauge_provider(provider)
            self._telemetry_provider = None
        self.coalescer.stop()
        for db in list(self.databases.values()):
            sch = getattr(db, "_scheduler", None)
            if sch is not None:
                sch.stop()

    @property
    def http_port(self) -> int:
        return self._http.port if self._http else self._http_port

    @property
    def binary_port(self) -> int:
        return self._binary.port if self._binary else self._binary_port

    def __enter__(self) -> "Server":
        return self.startup()

    def __exit__(self, *exc) -> None:
        self.shutdown()
