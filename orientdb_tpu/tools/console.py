"""Interactive console.

Analog of [E] OConsoleDatabaseApp (`console.sh`, SURVEY.md §2 "Console"):
connect to an embedded (`embedded:<name>`) or remote
(`remote:<host>:<port>/<db>`) database, run SQL, inspect schema, and
export/import portable JSON dumps.

Commands (case-insensitive; anything unrecognized is sent as SQL):
  CONNECT <url> [user] [password]     CREATE DATABASE <name>
  LIST DATABASES                      INFO
  CLASSES                             BROWSE CLASS <name>
  LOAD RECORD <rid>                   EXPORT DATABASE <path>
  IMPORT DATABASE <path>              DISCONNECT / QUIT / EXIT
  SLOWLOG [<n>|CLEAR]                 DIAG [<path>]
  STATS QUERIES [<k>]                 STATS PROFILE / STATS RESET
  CDC LIST                            CDC LAG
  ALERTS [<n>|HISTORY]                HEALTH
  SLO                                 TIMELINE [<n>]
  MEMORY [OWNERS|WATERMARK]           CRITPATH [<k>]
"""

from __future__ import annotations

import cmd
import shlex
import sys
from typing import Optional

from orientdb_tpu.models.database import Database


class Console(cmd.Cmd):
    intro = "orientdb-tpu console — CONNECT embedded:<name> to begin; QUIT to exit."
    prompt = "orientdb-tpu> "

    def __init__(self, stdout=None) -> None:
        super().__init__(stdout=stdout or sys.stdout)
        self.db = None
        self.remote = None
        self._embedded: dict = {}

    # -- helpers ------------------------------------------------------------

    def parseline(self, line):
        # commands are case-insensitive (CONNECT == connect); the raw line
        # still reaches default() untouched so SQL keeps its case
        c, arg, ln = super().parseline(line)
        return (c.lower() if c else c), arg, ln

    def _p(self, *lines) -> None:
        for ln in lines:
            print(ln, file=self.stdout)

    def _need_db(self) -> bool:
        if self.db is None and self.remote is None:
            self._p("!! not connected; use CONNECT embedded:<name>")
            return False
        return True

    def _run_sql(self, sql: str) -> None:
        try:
            target = self.remote if self.remote is not None else self.db
            rows = target.command(sql).to_dicts()
            for i, r in enumerate(rows):
                self._p(f"# {i}: {r}")
            self._p(f"({len(rows)} rows)")
        except Exception as e:
            self._p(f"!! {type(e).__name__}: {e}")

    # -- commands ------------------------------------------------------------

    def do_connect(self, arg: str) -> None:
        """CONNECT embedded:<name> | remote:<host>:<port>/<db> [user] [pw]"""
        parts = shlex.split(arg)
        if not parts:
            self._p("!! usage: CONNECT <url> [user] [password]")
            return
        url = parts[0]
        user = parts[1] if len(parts) > 1 else "admin"
        pw = parts[2] if len(parts) > 2 else "admin"
        try:
            if url.startswith("remote:"):
                from orientdb_tpu.client.remote import connect

                self.remote = connect(url, user, pw)
                self.db = None
                self._p(f"connected to {url}")
            else:
                name = url.split(":", 1)[1] if ":" in url else url
                self.db = self._embedded.setdefault(name, Database(name))
                self.remote = None
                self._p(f"connected to embedded database '{name}'")
        except Exception as e:
            self._p(f"!! {type(e).__name__}: {e}")

    def do_disconnect(self, _arg: str) -> None:
        if self.remote is not None:
            self.remote.close()
        self.db = self.remote = None
        self._p("disconnected")

    def do_create(self, arg: str) -> None:
        """CREATE DATABASE <name> (embedded); other CREATE ... goes to SQL."""
        parts = shlex.split(arg)
        if len(parts) == 2 and parts[0].lower() == "database":
            name = parts[1]
            self.db = self._embedded.setdefault(name, Database(name))
            self.remote = None
            self._p(f"database '{name}' created")
            return
        self.default(f"create {arg}")

    def do_list(self, arg: str) -> None:
        """LIST DATABASES"""
        if arg.lower().strip() == "databases":
            if self.remote is not None:
                self._p(*self.remote.databases())
            else:
                self._p(*sorted(self._embedded))
            return
        self.default(f"list {arg}")

    def do_info(self, _arg: str) -> None:
        if not self._need_db():
            return
        if self.remote is not None:
            self._p(f"remote database '{self.remote.name}'")
            return
        s = self.db.current_snapshot()
        self._p(
            f"database '{self.db.name}'",
            f"classes: {len(list(self.db.schema.classes()))}",
            f"mutation epoch: {self.db.mutation_epoch}",
            f"snapshot: {'attached' if s is not None else 'none'}"
            + (" (stale)" if self.db.snapshot_is_stale else ""),
        )

    def do_classes(self, _arg: str) -> None:
        if not self._need_db() or self.db is None:
            return
        for c in sorted(self.db.schema.classes(), key=lambda c: c.name):
            kind = "V" if c.is_vertex_type else "E" if c.is_edge_type else "O"
            n = 0 if c.abstract else self.db.count_class(c.name, polymorphic=False)
            self._p(f"{c.name:<24} {kind} abstract={c.abstract} records={n}")

    def do_browse(self, arg: str) -> None:
        """BROWSE CLASS <name>"""
        parts = shlex.split(arg)
        if len(parts) == 2 and parts[0].lower() == "class":
            self._run_sql(f"SELECT FROM {parts[1]}")
            return
        self.default(f"browse {arg}")

    def do_load(self, arg: str) -> None:
        """LOAD RECORD <rid>"""
        parts = shlex.split(arg)
        if len(parts) == 2 and parts[0].lower() == "record":
            if not self._need_db():
                return
            target = self.remote if self.remote is not None else self.db
            doc = target.load(parts[1])
            if doc is None:
                self._p(f"!! record {parts[1]} not found")
            else:
                self._p(str(doc.to_dict() if hasattr(doc, "to_dict") else doc))
            return
        self.default(f"load {arg}")

    def do_export(self, arg: str) -> None:
        """EXPORT DATABASE <path>"""
        parts = shlex.split(arg)
        if len(parts) == 2 and parts[0].lower() == "database":
            if not self._need_db() or self.db is None:
                return
            from orientdb_tpu.storage.ingest import export_database

            export_database(self.db, parts[1])
            self._p(f"exported to {parts[1]}")
            return
        self.default(f"export {arg}")

    def do_import(self, arg: str) -> None:
        """IMPORT DATABASE <path>"""
        parts = shlex.split(arg)
        if len(parts) == 2 and parts[0].lower() == "database":
            from orientdb_tpu.storage.ingest import import_database

            self.db = import_database(parts[1])
            self._embedded[self.db.name] = self.db
            self.remote = None
            self._p(f"imported database '{self.db.name}'")
            return
        self.default(f"import {arg}")

    def do_backup(self, arg: str) -> None:
        """BACKUP DATABASE <path> — online zip backup (frozen-window
        consistency; [E] the reference's BACKUP DATABASE)."""
        parts = shlex.split(arg)
        if len(parts) == 2 and parts[0].lower() == "database":
            if not self._need_db() or self.db is None:
                return
            from orientdb_tpu.storage.backup import backup_database

            backup_database(self.db, parts[1])
            self._p(f"backup written to {parts[1]}")
            return
        self.default(f"backup {arg}")

    def do_restore(self, arg: str) -> None:
        """RESTORE DATABASE <path>"""
        parts = shlex.split(arg)
        if len(parts) == 2 and parts[0].lower() == "database":
            from orientdb_tpu.storage.backup import restore_database

            self.db = restore_database(parts[1])
            self._embedded[self.db.name] = self.db
            self.remote = None
            self._p(f"restored database '{self.db.name}'")
            return
        self.default(f"restore {arg}")

    def do_fsck(self, arg: str) -> None:
        """FSCK <directory> | FSCK BACKUP <zip> — verify durable-state
        integrity: WAL CRC chains + segment continuity, checkpoint/
        delta/epoch content hashes, coldstore tails; BACKUP adds the
        archive's restore-and-rehash round trip (tools/fsck)."""
        parts = shlex.split(arg)
        from orientdb_tpu.tools.fsck import (
            format_report,
            fsck_backup,
            fsck_tree,
        )

        if len(parts) == 2 and parts[0].lower() == "backup":
            self._p(format_report(fsck_backup(parts[1])))
            return
        if len(parts) == 1 and parts[0]:
            self._p(format_report(fsck_tree(parts[0])))
            return
        self.default(f"fsck {arg}")

    def do_script(self, arg: str) -> None:
        """SCRIPT <sql batch>  — LET/IF/RETURN and ';'-separated
        statements in one session ([E] the console's script command)."""
        if not self._need_db():
            return
        try:
            target = self.remote if self.remote is not None else self.db
            rows = target.execute("sql", arg).to_dicts()
            for i, r in enumerate(rows):
                self._p(f"# {i}: {r}")
            self._p(f"({len(rows)} rows)")
        except Exception as e:
            self._p(f"!! {type(e).__name__}: {e}")

    def do_slowlog(self, arg: str) -> None:
        """SLOWLOG [<n>|CLEAR] — recent slow queries (most recent
        first; threshold = config.slow_query_ms, 0 disables)."""
        from orientdb_tpu.obs.slowlog import slowlog
        from orientdb_tpu.utils.config import config

        a = arg.strip().lower()
        if a == "clear":
            slowlog.clear()
            self._p("slowlog cleared")
            return
        limit = int(a) if a.isdigit() else 20
        entries = slowlog.entries(limit)
        if not entries:
            self._p(
                "slowlog empty "
                f"(threshold {config.slow_query_ms:g} ms; 0 = disabled)"
            )
            return
        for e in entries:
            trace = f" trace={e['trace_id']}" if e.get("trace_id") else ""
            # the fingerprint is the pivot into STATS QUERIES: one slow
            # query joins its shape's cumulative cost on this id
            fp = f" fp={e['fingerprint']}" if e.get("fingerprint") else ""
            cache = f" cache={e['cache']}" if e.get("cache") else ""
            self._p(
                f"{e['ms']:>9.1f} ms  [{e['engine']}]{fp}{cache}{trace}"
                f"  {e['sql']}"
            )
        self._p(f"({len(entries)} entries)")

    def do_stats(self, arg: str) -> None:
        """STATS QUERIES [<k>] — top-k query shapes by cumulative
        latency (fingerprint, calls, errors, mean ms, device/compile
        ms, cache hits); STATS PROFILE — per-stage self-time from the
        span aggregator; STATS RESET — clear both planes."""
        from orientdb_tpu.obs.profile import profiler
        from orientdb_tpu.obs.stats import stats

        parts = arg.split()
        sub = parts[0].lower() if parts else "queries"
        if sub == "reset":
            stats.reset()
            profiler.reset()
            self._p("query stats and profile reset")
            return
        if sub == "profile":
            rows = profiler.flat(20)
            if not rows:
                self._p("profile empty")
                return
            self._p(f"{'self ms':>12} {'total ms':>12} {'count':>8}  stage")
            for r in rows:
                self._p(
                    f"{r['self_ms']:>12.1f} {r['total_ms']:>12.1f} "
                    f"{r['count']:>8}  {r['name']}"
                )
            return
        if sub != "queries":
            self._p("!! usage: STATS QUERIES [<k>] | PROFILE | RESET")
            return
        k = int(parts[1]) if len(parts) > 1 and parts[1].isdigit() else 10
        rows = stats.top(k)
        if not rows:
            self._p("no recorded queries")
            return
        self._p(
            f"{'fingerprint':<16} {'calls':>7} {'err':>5} {'mean ms':>9} "
            f"{'p50 ms':>8} {'p99 ms':>8} "
            f"{'dev ms':>9} {'compile ms':>11} {'cache':>6}  query"
        )
        for r in rows:
            self._p(
                f"{r['fingerprint']:<16} {r['calls']:>7} {r['errors']:>5} "
                f"{r['mean_ms']:>9.2f} "
                f"{r['p50_ms']:>8.1f} {r['p99_ms']:>8.1f} "
                f"{r['device_s'] * 1000:>9.1f} "
                f"{r['compile_s'] * 1000:>11.1f} "
                f"{r['plan_cache_hits'] + r['result_cache_hits']:>6}  "
                f"{r['query'][:70]}"
            )
        self._p(f"({len(rows)} shapes)")

    def do_timeline(self, arg: str) -> None:
        """TIMELINE [<n>] — the dispatch flight recorder (obs/timeline):
        the overlap verdict over the recent window (device-idle /
        transfer-hidden fractions, ring savings, lane decomposition)
        followed by the last n dispatch records (default 10). The full
        Perfetto-loadable export is GET /debug/timeline."""
        from orientdb_tpu.obs.timeline import recorder
        from orientdb_tpu.utils.config import config

        a = arg.strip()
        n = int(a) if a.isdigit() else 10
        rep = recorder.overlap(window_s=config.timeline_window_s)
        if not rep.get("records"):
            self._p(
                "timeline empty (no dispatches in the last "
                f"{config.timeline_window_s:g} s; capacity "
                f"{config.timeline_capacity})"
            )
            return
        tr = rep.get("transfer", {})
        ring = rep.get("ring", {})
        pf = rep.get("prefetch", {})
        self._p(
            f"{rep['records']} dispatches over {rep['span_s']:.2f} s  "
            f"device idle {rep['device_idle_fraction']:.1%}  "
            f"transfer hidden {tr.get('transfer_hidden_fraction', 0.0):.1%} "
            f"({tr.get('hidden_bytes', 0)}/{tr.get('bytes', 0)} B)",
            f"ring hits {ring.get('hits', 0)}/"
            f"{ring.get('hits', 0) + ring.get('uploads', 0)}  "
            f"prefetch {pf.get('hits', 0)} hit / {pf.get('misses', 0)} "
            f"miss / {pf.get('starts', 0)} started  "
            f"paths {rep.get('paths', {})}",
        )
        lane = rep.get("lane")
        if lane:
            self._p(
                f"lane: queue {lane.get('queue_ms_mean')} ms  window "
                f"{lane.get('window_ms_mean')} ms  service "
                f"{lane.get('service_ms_mean')} ms "
                f"({lane['dispatches']} drains)"
            )
        recs = recorder.records(
            window_s=config.timeline_window_s, limit=n
        )
        for r in recs:
            dev_ms = sum(b - a_ for a_, b in r.get("device", [])) * 1e3
            nbytes = sum(t[2] for t in r.get("transfers", []))
            fp = r.get("fingerprint") or "-"
            self._p(
                f"#{r['seq']:<6} {r['path']:<8} n={r['n']:<4} fp={fp:<16} "
                f"device {dev_ms:>7.2f} ms  {nbytes:>8} B  "
                f"{len(r['events'])} events"
            )
        self._p(f"({len(recs)} records)")

    def do_critpath(self, arg: str) -> None:
        """CRITPATH [<k>] — per-request critical-path attribution
        (obs/critpath): per-SLO-class segment breakdowns with the
        dominant bottleneck, then the top-k fingerprints by cumulative
        wall (default 10) with their mean per-segment split. The full
        document (catalog, recent decompositions) is
        GET /stats/critpath."""
        from orientdb_tpu.obs.critpath import plane

        a = arg.strip()
        k = int(a) if a.isdigit() else 10
        rep = plane.report(k)
        if not rep["requests"]:
            state = "enabled" if rep["enabled"] else "disabled"
            self._p(f"no decompositions recorded (critpath {state})")
            return
        self._p(f"{rep['requests']} sampled requests decomposed")
        for name, c in rep["by_class"].items():
            segs = ", ".join(
                f"{s} {ms:.2f}" for s, ms in
                list(c["segments_ms_mean"].items())[:5]
            )
            self._p(
                f"class {name}: {c['requests']} req  mean "
                f"{c['wall_ms_mean']:.2f} ms  dominant "
                f"{c['dominant'] or '-'}  [{segs}]"
            )
        self._p(
            f"{'fingerprint':<16} {'req':>6} {'mean ms':>9} "
            f"{'dominant':<16} segments (mean ms)"
        )
        for r in rep["fingerprints"]:
            segs = ", ".join(
                f"{s} {ms:.2f}" for s, ms in
                list(r["segments_ms_mean"].items())[:4]
            )
            self._p(
                f"{r['fingerprint']:<16} {r['requests']:>6} "
                f"{r['wall_ms_mean']:>9.2f} "
                f"{(r['dominant'] or '-'):<16} {segs}"
            )
        self._p(f"({len(rep['fingerprints'])} shapes)")

    def do_memory(self, arg: str) -> None:
        """MEMORY [OWNERS|WATERMARK] — the device-memory ledger
        (obs/memledger): OWNERS (the default) prints the per-kind HBM
        rollup, the reconciliation verdict against jax.live_arrays,
        and lease/refusal state; WATERMARK prints the recent
        total-bytes watermark ring. The full document is
        GET /debug/memory."""
        from orientdb_tpu.obs.memledger import memledger

        sub = (arg.strip().split() or ["owners"])[0].lower()
        if sub not in ("owners", "watermark"):
            self._p("!! usage: MEMORY [OWNERS|WATERMARK]")
            return
        if sub == "watermark":
            marks = memledger.watermarks()
            if not marks:
                self._p("watermark ring empty (no device registrations)")
                return
            for ts, b in marks:
                self._p(f"{ts:>14.3f}  {b:>14} B  ({b / (1 << 20):8.2f} MiB)")
            self._p(
                f"({len(marks)} marks, peak {memledger.peak_total()} B)"
            )
            return
        rep = memledger.report()
        for kind, row in rep["owners"].items():
            self._p(
                f"{kind:<16} {row['bytes']:>12} B  "
                f"entries={row['entries']:<5} owners={row['owners']:<4} "
                f"oldest={row['oldest_s']:g}s"
            )
        self._p(
            f"total {rep['total_bytes']} B  peak {rep['peak_bytes']} B  "
            f"pinned {rep['pinned_bytes']} B  entries {rep['entries']}"
        )
        rec = rep.get("reconcile") or {}
        if rec:
            self._p(
                f"reconcile: {'ok' if rec.get('ok') else 'RESIDUE'}  "
                f"untracked={rec.get('untracked_bytes', 0)} B  "
                f"tracked_dead={rec.get('tracked_dead_bytes', 0)} B  "
                f"reclaimed={rec.get('reclaimed_bytes', 0)} B"
            )
        leases = rep.get("leases", {})
        stale = leases.get("stale", [])
        self._p(
            f"leases: {leases.get('outstanding', 0)} outstanding, "
            f"{len(stale)} stale"
        )
        for lease in stale:
            self._p(
                f"  !! epoch {lease['epoch']} held {lease['age_s']:g}s "
                f"trace={lease['trace_id'] or '-'}"
            )
        refusals = rep.get("refusals", {})
        if refusals.get("counts"):
            last = refusals.get("last") or {}
            self._p(
                f"refusals: {refusals['counts']}"
                + (
                    f"  last={last.get('reason')}: {last.get('detail')}"
                    if last
                    else ""
                )
            )

    def do_cdc(self, arg: str) -> None:
        """CDC LIST — changefeed consumers and durable cursors per
        connected embedded database; CDC LAG — head LSN and per-consumer
        lag / queue depth / shed counts (the slow-consumer triage
        view)."""
        sub = (arg.strip().split() or ["list"])[0].lower()
        if sub not in ("list", "lag"):
            self._p("!! usage: CDC LIST | CDC LAG")
            return
        dbs = list(self._embedded.values())
        if self.db is not None and self.db not in dbs:
            dbs.append(self.db)
        feeds = [
            (db, db.__dict__.get("_cdc_feed"))
            for db in dbs
            if db.__dict__.get("_cdc_feed") is not None
        ]
        if not feeds:
            self._p("no changefeeds (no database has subscribers)")
            return
        for db, feed in feeds:
            s = feed.stats()
            if sub == "list":
                self._p(
                    f"database '{db.name}': head_lsn={s['head_lsn']} "
                    f"consumers={len(s['consumers'])} "
                    f"cursors={len(s['cursors'])}"
                )
                for c in s["consumers"]:
                    name = c["name"] or "-"
                    cls = ",".join(c["classes"] or []) or "*"
                    self._p(
                        f"  #{c['token']:<4} {name:<16} classes={cls} "
                        f"mode={c['mode']} policy={c['policy']}"
                    )
                for name, cur in sorted(s["cursors"].items()):
                    self._p(f"  cursor {name:<16} lsn={cur['lsn']}")
            else:
                self._p(f"database '{db.name}': head_lsn={s['head_lsn']}")
                for c in s["consumers"]:
                    name = c["name"] or f"#{c['token']}"
                    self._p(
                        f"  {name:<16} lag={c['lag_entries']:<6} "
                        f"queue={c['queue_depth']:<6} "
                        f"unacked={c['unacked_entries']:<6} "
                        f"shed={c['shed_events']}"
                    )

    def do_alerts(self, arg: str) -> None:
        """ALERTS [<n>|HISTORY] — the alert plane (obs/alerts): active
        pending/firing alerts with exemplar trace ids; HISTORY lists
        recently resolved ones."""
        from orientdb_tpu.obs.alerts import engine

        a = arg.strip().lower()
        if a == "history":
            items = engine.history(20)
            if not items:
                self._p("no resolved alerts")
                return
            for e in items:
                self._p(
                    f"[resolved] {e['rule']}({e['key']}) "
                    f"value={e['value']:g} thr={e['threshold']:g}"
                    + (
                        f" trace={e['exemplar_trace_id']}"
                        if e.get("exemplar_trace_id")
                        else ""
                    )
                )
            self._p(f"({len(items)} resolved)")
            return
        limit = int(a) if a.isdigit() else 20
        items = engine.active()[:limit]
        if not items:
            self._p("no active alerts")
            return
        for e in items:
            trace = (
                f" trace={e['exemplar_trace_id']}"
                if e.get("exemplar_trace_id")
                else ""
            )
            self._p(
                f"[{e['state']:<7}] {e['severity']:<8} "
                f"{e['rule']}({e['key']}) value={e['value']:g} "
                f"thr={e['threshold']:g}{trace}  {e['detail']}"
            )
        self._p(f"({len(items)} active)")

    def do_slo(self, _arg: str) -> None:
        """SLO — the last traffic-simulator run's SLO verdict
        (obs/slo): pass/fail, error-budget burn, per-class windowed
        p50/p99 vs targets, and every failure naming its rule/key."""
        from orientdb_tpu.obs.slo import engine as slo_engine

        r = slo_engine.report()
        if r.get("verdict") == "none":
            self._p("no SLO run recorded (workloads.driver.TrafficSim)")
            return
        self._p(
            f"verdict: {r['verdict'].upper()}  burn={r['burn']:g}  "
            f"calls={r['calls']} errors={r['errors']}  "
            f"window={r['window_s']:g}s"
        )
        self._p(
            f"{'class':<10} {'calls':>7} {'err':>5} {'p50 ms':>9} "
            f"{'p99 ms':>9} {'targets (p50/p99/avail)':>26}"
        )
        for c in r["classes"]:
            t = c["targets"]
            self._p(
                f"{c['class']:<10} {c['calls']:>7} {c['errors']:>5} "
                f"{c.get('p50_ms', 0.0):>9.1f} {c.get('p99_ms', 0.0):>9.1f} "
                f"{t['p50_ms']:>10g}/{t['p99_ms']:g}/{t['availability']:g}"
            )
        for f in r["failures"]:
            self._p(f"FAIL {f['rule']}({f['key']}): {f['detail']}")
        if not r["failures"]:
            self._p("(no failures)")

    def do_health(self, _arg: str) -> None:
        """HEALTH — watchdog summary (rules/ticks/lifecycle totals),
        circuit-breaker states, and per-database in-doubt 2PC counts —
        the console's answer to GET /cluster/health."""
        from orientdb_tpu.obs.alerts import engine
        from orientdb_tpu.parallel.resilience import breaker_snapshot

        s = engine.summary()
        self._p(
            f"watchdog: rules={s['rules']} ticks={s['ticks']} "
            f"firing={s['firing']} pending={s['pending']} "
            f"fired_total={s['fired_total']} "
            f"resolved_total={s['resolved_total']} "
            f"baselines={s['baselines']}"
            + (
                f" tick_age={s['tick_age_s']:g}s"
                if s["tick_age_s"] is not None
                else " (no tick yet)"
            )
        )
        breakers = breaker_snapshot()
        for name, b in sorted(breakers.items()):
            self._p(f"breaker {name}: {b['state']}")
        if not breakers:
            self._p("no circuit breakers registered")
        dbs = list(self._embedded.values())
        if self.db is not None and self.db not in dbs:
            dbs.append(self.db)
        for db in dbs:
            reg = getattr(db, "_tx2pc_registry", None)
            staged = len(reg.staged_report()) if reg is not None else 0
            if staged:
                self._p(f"database '{db.name}': {staged} in-doubt 2pc")

    def do_diag(self, arg: str) -> None:
        """DIAG [<path>] — flight-recorder debug bundle (obs/bundle):
        recent traces assembled by trace id, the slowlog, a metrics
        snapshot, and in-doubt 2PC state. With a path, the full JSON
        artifact is written there; either way a summary prints."""
        import json

        from orientdb_tpu.obs.bundle import debug_bundle

        dbs = list(self._embedded.values())
        if self.db is not None and self.db not in dbs:
            dbs.append(self.db)
        bundle = debug_bundle(dbs=dbs, member="console")
        path = arg.strip()
        if path:
            with open(path, "w", encoding="utf-8") as f:
                json.dump(bundle, f, sort_keys=True, default=str)
            self._p(f"debug bundle written to {path}")
        traces = bundle["traces"]
        n_spans = sum(len(t["spans"]) for t in traces)
        indoubt = bundle["in_doubt_2pc"]
        staged = sum(len(v) for v in indoubt["staged"].values())
        self._p(
            f"traces: {len(traces)} ({n_spans} spans)",
            f"slowlog entries: {len(bundle['slowlog'])}",
            f"in-doubt 2pc: {staged} staged, "
            f"{len(indoubt['coordinator_reports'])} coordinator reports",
            f"metric counters: {len(bundle['metrics']['counters'])}",
        )
        for t in traces[-3:]:
            names = [s["name"] for s in t["spans"]]
            self._p(
                f"  {t['trace_id']}: "
                + " -> ".join(names[:8])
                + (" ..." if len(names) > 8 else "")
            )

    def do_quit(self, _arg: str) -> bool:
        return True

    do_exit = do_quit
    do_EOF = do_quit

    def default(self, line: str) -> None:
        if not self._need_db():
            return
        self._run_sql(line)

    def emptyline(self) -> None:
        pass


def main() -> None:  # pragma: no cover - interactive entry
    from orientdb_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    Console().cmdloop()


if __name__ == "__main__":  # pragma: no cover
    main()
