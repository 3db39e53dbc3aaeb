"""Query engine front door.

Analog of the reference's query dispatch ([E]
ODatabaseDocumentEmbedded.query/command → OStatementCache →
planner → step chain; SURVEY.md §3.2): parses (with a statement cache),
routes idempotent statements to an execution engine, and wraps rows in a
ResultSet.

Engine selection (the north star's per-session ``TRAVERSE_ENGINE`` switch):
- ``engine="oracle"`` — the pure-Python reference interpreter (parity oracle);
- ``engine="tpu"`` — the compiled batched engine over the attached snapshot
  (MATCH/TRAVERSE/SELECT subset); falls back to the oracle for statements it
  cannot compile unless ``strict=True``;
- ``engine="auto"`` (default, from config.traverse_engine) — tpu when a
  fresh snapshot is attached, oracle otherwise.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, Optional

from orientdb_tpu.exec.result import ResultSet
from orientdb_tpu.sql import ast as A
from orientdb_tpu.sql.parser import parse
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.logging import get_logger

log = get_logger("engine")

_ENGINES = ("auto", "tpu", "oracle")

# statement cache ([E] OStatementCache): sql text → AST. AST nodes are
# frozen dataclasses, so sharing across threads is safe; the cache dict
# itself needs the lock.
_stmt_cache: "OrderedDict[str, A.Statement]" = OrderedDict()
_stmt_cache_lock = threading.Lock()


def parse_cached(sql: str) -> A.Statement:
    with _stmt_cache_lock:
        stmt = _stmt_cache.get(sql)
        if stmt is not None:
            _stmt_cache.move_to_end(sql)
            return stmt
    stmt = parse(sql)
    with _stmt_cache_lock:
        _stmt_cache[sql] = stmt
        while len(_stmt_cache) > config.statement_cache_size:
            _stmt_cache.popitem(last=False)
    return stmt


def _normalize_params(params) -> Dict:
    if params is None:
        return {}
    if isinstance(params, dict):
        return params
    # positional list → {0: v0, 1: v1, …}
    return {i: v for i, v in enumerate(params)}


def _choose_engine(db, stmt: A.Statement, engine: Optional[str]) -> str:
    eng = engine or config.traverse_engine
    if eng not in _ENGINES:
        raise ValueError(f"unknown engine {eng!r}; expected one of {_ENGINES}")
    if eng == "auto":
        if (
            db.tx is None
            and db.current_snapshot(require_fresh=True) is not None
            and isinstance(
                stmt,
                (A.MatchStatement, A.TraverseStatement, A.SelectStatement),
            )
        ):
            return "tpu"
        return "oracle"
    return eng


def _run(
    db,
    stmt: A.Statement,
    params,
    engine: Optional[str],
    strict: bool,
    sql: Optional[str] = None,
):
    from orientdb_tpu.utils.metrics import metrics

    eng = _choose_engine(db, stmt, engine)
    if eng == "tpu":
        from orientdb_tpu.exec import tpu_engine
        from orientdb_tpu.exec.devicefault import domain as _fault_domain

        try:
            # device fault quarantine gate: a fingerprint whose plan
            # exhausted the escalation ladder serves the oracle until
            # its TTL expires; "probe" admits exactly this dispatch as
            # the re-admission trial (success inside execute() clears
            # the entry, a fault re-quarantines with a doubled TTL)
            if _fault_domain.admit(sql) == "quarantined":
                raise tpu_engine.Uncompilable(
                    "plan quarantined by device fault domain"
                )
            # an active tx means the snapshot no longer reflects this
            # session's view (tx-created/-deleted records) — the oracle is
            # the only engine that applies the tx overlay
            if db.tx is not None:
                raise tpu_engine.Uncompilable("active transaction on this thread")
            rows = tpu_engine.execute(db, stmt, params, sql=sql)
            from orientdb_tpu.exec import audit as _audit

            # audit.mismatch chaos crossing: corrupts SERVED rows only,
            # so the shadow-oracle auditor provably detects them
            rows = _audit.corrupt_point(rows)
            metrics.incr("query.tpu")
            return rows, "tpu"
        except tpu_engine.Uncompilable as e:
            if strict:
                raise
            metrics.incr("query.tpu.fallback")
            log.info("tpu engine fallback to oracle: %s", e)
    metrics.incr("query.oracle")
    import orientdb_tpu.obs.critpath as CP
    import orientdb_tpu.obs.timeline as TL
    from orientdb_tpu.exec.oracle import execute_statement

    # the oracle is a dispatch path too: its flight record carries no
    # device intervals (host interpreter), but its wall time shows up
    # in the timeline next to the compiled paths it is compared against
    rec = TL.recorder.begin("oracle")
    with TL.active(rec), CP.segment("host_compute"):
        rows = execute_statement(db, stmt, params)
    TL.recorder.commit(rec)
    return rows, "oracle"


def _result_set(rows, engine_used: str) -> ResultSet:
    rs = ResultSet(rows)
    rs.engine = engine_used  # type: ignore[attr-defined]
    return rs


def _observe_query(
    sql: str, t0: float, engine_used: str, trace_id, acc
) -> None:
    """Per-query accounting shared by query()/command(): the duration
    stat + histogram feed /metrics, the per-fingerprint stats table
    aggregates cost by query shape, and the slowlog keeps the tail —
    stamped with the fingerprint so slowlog ↔ stats ↔ trace join on
    one id."""
    import time

    import orientdb_tpu.obs.stats as S  # noqa: F401 (module)
    from orientdb_tpu.obs.registry import obs as _obs
    from orientdb_tpu.obs.slowlog import slowlog

    dur = time.perf_counter() - t0
    _obs.observe("query.latency_s", dur)
    plan_cache = None
    if acc is not None:
        if acc.plan_cache_hits:
            plan_cache = "hit"
        elif acc.plan_cache_misses:
            plan_cache = "miss"
        elif acc.result_cache_hits:
            plan_cache = "result-cache"
    rows = getattr(acc, "_rows", None) if acc is not None else None
    fid = S.stats.finish(acc, dur, engine=engine_used, rows=rows)
    slowlog.record(
        sql,
        dur,
        engine=engine_used,
        trace_id=trace_id,
        fingerprint=fid,
        cache=plan_cache,
    )


def _observe_error(sql: str, t0: float, acc, exc: BaseException) -> None:
    """A failing query still counts: calls + errors per fingerprint."""
    import time

    import orientdb_tpu.obs.stats as S

    S.stats.finish(acc, time.perf_counter() - t0, engine="?", error=exc)


def execute_query(
    db,
    sql: str,
    params=None,
    engine: Optional[str] = None,
    strict: bool = False,
) -> ResultSet:
    """Idempotent statements only ([E] ODatabaseSession.query contract).
    PROFILE executes its inner statement, so a PROFILE of a write is
    rejected here too."""
    import time

    import orientdb_tpu.obs.critpath as CP
    import orientdb_tpu.obs.stats as S
    from orientdb_tpu.obs.trace import span

    t0 = time.perf_counter()
    acc = S.stats.begin(sql)
    with CP.request("engine", sql) as cp:
        seg0 = cp.total() if cp is not None else 0.0
        try:
            with span("query", sql=sql[:120]) as sp:
                rs = _execute_query(db, sql, params, engine, strict)
                sp.set("engine", getattr(rs, "engine", None))
                rows = getattr(rs, "_rows", None)
                if hasattr(rows, "__len__"):
                    sp.set("rows", len(rows))
                    if acc is not None:
                        acc._rows = len(rows)  # type: ignore[attr-defined]
        except BaseException as e:
            _observe_error(sql, t0, acc, e)
            CP.fold_query(cp, time.perf_counter() - t0, acc, seg0)
            raise
        _observe_query(sql, t0, getattr(rs, "engine", "?"), sp.trace_id, acc)
        CP.fold_query(cp, time.perf_counter() - t0, acc, seg0)
        # shadow-oracle parity audit: rides the stats sampling decision
        # (acc) so stats/slowlog/timeline/audit cover the same subset.
        # One attribute read when auditing is off — the serving path
        # must not pay normalize/submit costs for a disabled auditor.
        if config.audit_sample_rate > 0.0:
            from orientdb_tpu.exec import audit as _audit

            _audit.auditor.maybe_submit(
                db, sql, _normalize_params(params), rs, sp.trace_id,
                acc is not None,
            )
    return rs


def _execute_query(
    db,
    sql: str,
    params=None,
    engine: Optional[str] = None,
    strict: bool = False,
) -> ResultSet:
    stmt = parse_cached(sql)
    if isinstance(stmt, A.ExplainStatement):
        inner_writes = stmt.profile and not stmt.inner.is_idempotent
        if inner_writes:
            raise ValueError(
                "cannot PROFILE a non-idempotent statement via query(); use command()"
            )
        return explain_statement(db, stmt, _normalize_params(params))
    if not stmt.is_idempotent:
        raise ValueError(
            f"cannot run non-idempotent {type(stmt).__name__} via query(); use command()"
        )
    norm = _normalize_params(params)
    # result cache ([E] OCommandCache, off by default): idempotent
    # queries outside a tx, keyed incl. engine AND strict (a cached
    # fallback result must not mask strict=True's Uncompilable contract)
    from orientdb_tpu.exec.command_cache import cache_for

    cache = cache_for(db) if db.tx is None else None
    key = cache.key(sql, norm, engine, strict) if cache is not None else None
    # capture the epoch BEFORE running: a write landing mid-query must
    # make the cache entry stale (not stamp post-write freshness onto
    # pre-write rows) and must block view admission (the CDC callback
    # cannot invalidate a view that is not registered yet)
    epoch = db.mutation_epoch
    if key is not None:
        hit = cache.get(key, epoch)
        if hit is not None:
            return _result_set(hit[0], hit[1])
    # materialized continuous views (exec/views): hot fingerprints'
    # results kept resident with CDC-EXACT invalidation — unlike the
    # epoch-keyed command cache, an unrelated write does not kill them
    vm = None
    if db.tx is None:
        from orientdb_tpu.exec.views import views_for

        vm = views_for(db)
        if vm is not None:
            view = vm.lookup(sql, norm, engine, strict)
            if view is not None:
                return _result_set(view.rows, view.engine)
    rows, used = _run(db, stmt, norm, engine, strict, sql=sql)
    if key is not None:
        cache.put(key, rows, used, epoch)
    if vm is not None:
        vm.observe(sql, norm, engine, strict, rows, used, epoch=epoch)
    return _result_set(rows, used)


def execute_command(
    db,
    sql: str,
    params=None,
    engine: Optional[str] = None,
    strict: bool = False,
) -> ResultSet:
    import time

    import orientdb_tpu.obs.critpath as CP
    import orientdb_tpu.obs.stats as S
    from orientdb_tpu.obs.trace import span

    t0 = time.perf_counter()
    acc = S.stats.begin(sql)
    with CP.request("command", sql) as cp:
        seg0 = cp.total() if cp is not None else 0.0
        try:
            with span("command", sql=sql[:120]) as sp:
                rs = _execute_command(db, sql, params, engine, strict)
                sp.set("engine", getattr(rs, "engine", None))
                rows = getattr(rs, "_rows", None)
                if acc is not None and hasattr(rows, "__len__"):
                    acc._rows = len(rows)  # type: ignore[attr-defined]
        except BaseException as e:
            _observe_error(sql, t0, acc, e)
            CP.fold_query(cp, time.perf_counter() - t0, acc, seg0)
            raise
        _observe_query(sql, t0, getattr(rs, "engine", "?"), sp.trace_id, acc)
        CP.fold_query(cp, time.perf_counter() - t0, acc, seg0)
        if config.audit_sample_rate > 0.0:
            from orientdb_tpu.exec import audit as _audit

            _audit.auditor.maybe_submit(
                db, sql, _normalize_params(params), rs, sp.trace_id,
                acc is not None,
            )
    return rs


def _execute_command(
    db,
    sql: str,
    params=None,
    engine: Optional[str] = None,
    strict: bool = False,
) -> ResultSet:
    stmt = parse_cached(sql)
    if isinstance(stmt, A.ExplainStatement):
        return explain_statement(db, stmt, _normalize_params(params))
    if stmt.is_idempotent:
        rows, used = _run(
            db, stmt, _normalize_params(params), engine, strict, sql=sql
        )
        return _result_set(rows, used)
    from orientdb_tpu.exec.oracle import execute_statement

    return _result_set(
        execute_statement(db, stmt, _normalize_params(params)), "oracle"
    )


def execute_query_batch(
    db,
    sqls,
    params_list=None,
    engine: Optional[str] = None,
    strict: bool = False,
) -> List[ResultSet]:
    """Run a batch of idempotent statements in ~one device round trip.

    The TPU-engine members dispatch together and overlap their
    device→host transfers (``tpu_engine.execute_batch``) — the DP-axis
    answer to the fixed cost every transfer carries. Per-statement
    Uncompilable failures fall back to the oracle (unless ``strict``).
    """
    import time

    import orientdb_tpu.obs.critpath as CP
    import orientdb_tpu.obs.stats as S
    from orientdb_tpu.obs.trace import span

    t0 = time.perf_counter()
    # a failing batch records NO per-statement stats: which statements
    # actually executed is unknowable here, and charging calls+errors
    # to all N shapes would fabricate exactly the aggregate evidence
    # this table exists to make trustworthy (the failure still lands in
    # query.latency_s / the caller's error path)
    import orientdb_tpu.obs.timeline as TL

    # one flight record for the whole in-frame batch (refined to
    # "group" when a vmapped group dispatch forms inside it)
    rec = TL.recorder.begin(
        "batch", sql=sqls[0] if sqls else None, n=len(sqls)
    )
    with CP.request("batch", sqls[0] if sqls else None) as cp:
        seg0 = cp.total() if cp is not None else 0.0
        with span("query_batch", n=len(sqls)) as bsp:
            # the capture collects the batch's device/transfer/compile
            # attribution (no per-query accumulator runs on a batch)
            with S.capture() as cap, TL.active(rec):
                out = _execute_query_batch(
                    db, sqls, params_list, engine, strict
                )
        TL.recorder.commit(rec)
        dur = time.perf_counter() - t0
        # per-statement stats with the batch's amortized wall clock:
        # device time overlaps across the whole batch, so per-item
        # attribution would be fiction — calls/rows/engine are what
        # aggregate honestly
        n = max(len(sqls), 1)
        per = dur / n
        per_segs = _amortized_segs(cp, dur, cap, seg0, n)
        auditing = config.audit_sample_rate > 0.0
        if auditing:
            from orientdb_tpu.exec import audit as _audit

        plist = params_list if params_list is not None else [None] * n
        for sql, p, rs in zip(sqls, plist, out):
            rows = getattr(rs, "_rows", None)
            S.stats.record_external(
                sql,
                per,
                engine=getattr(rs, "engine", "?"),
                rows=len(rows) if hasattr(rows, "__len__") else None,
            )
            if per_segs:
                S.stats.record_segments(sql, per_segs)
            # batch paths carry no per-query accumulator: the batch
            # capture is always on, so every member is audit-eligible
            if auditing:
                _audit.auditor.maybe_submit(
                    db, sql, _normalize_params(p), rs, bsp.trace_id, True
                )
    return out


def _amortized_segs(cp, dur: float, cap, seg0: float, n: int):
    """Fold one batch execution into the active critical-path record
    and return the per-statement amortized segment split for the stats
    table. The record takes the FULL batch cost (its segment sum must
    match the request's wall — the caller waited for the whole batch);
    the stats columns take the 1/n share next to record_external's
    amortized wall, and the record is marked so commit does not write
    the full-batch split over the amortized one."""
    import orientdb_tpu.obs.critpath as CP

    if cp is None:
        return None
    CP.fold_query(cp, dur, cap, seg0)
    cp.stats_recorded = True
    return {
        k: v / n
        for k, v in (
            ("queue", cap.queue_s),
            ("plan_resolve", cap.compile_s),
            ("device_compute", cap.device_s),
            ("result_transfer", cap.transfer_s),
            ("host_compute", max(
                0.0,
                dur - cap.queue_s - cap.compile_s - cap.device_s
                - cap.transfer_s,
            )),
        )
        if v > 0.0
    }


def _execute_query_batch(
    db,
    sqls,
    params_list=None,
    engine: Optional[str] = None,
    strict: bool = False,
) -> List[ResultSet]:
    n = len(sqls)
    if params_list is None:
        params_list = [None] * n
    if len(params_list) != n:
        raise ValueError("params_list length must match sqls length")
    items = []
    for sql, p in zip(sqls, params_list):
        stmt = parse_cached(sql)
        if isinstance(stmt, A.ExplainStatement) or not stmt.is_idempotent:
            raise ValueError(
                f"cannot run non-idempotent {type(stmt).__name__} via query_batch()"
            )
        items.append((stmt, _normalize_params(p)))
    engines = [_choose_engine(db, s, engine) for s, _ in items]
    out: List[Optional[ResultSet]] = [None] * n
    tpu_idx = [i for i, e in enumerate(engines) if e == "tpu"]
    if tpu_idx and db.tx is None:
        from orientdb_tpu.exec import tpu_engine
        from orientdb_tpu.exec.devicefault import domain as _fault_domain
        from orientdb_tpu.utils.metrics import metrics

        # per-item quarantine gate: quarantined fingerprints drop to
        # the oracle loop below; "probe" items ride the batch and clear
        # their entry on a clean result
        gates = {i: _fault_domain.admit(sqls[i]) for i in tpu_idx}
        if strict and any(g == "quarantined" for g in gates.values()):
            raise tpu_engine.Uncompilable(
                "plan quarantined by device fault domain"
            )
        run_idx = [i for i in tpu_idx if gates[i] != "quarantined"]
        if run_idx:
            batch = tpu_engine.execute_batch(
                db,
                [items[i] for i in run_idx],
                sqls=[sqls[i] for i in run_idx],
            )
            for i, res in zip(run_idx, batch):
                if isinstance(res, tpu_engine.Uncompilable):
                    if strict:
                        raise res
                    metrics.incr("query.tpu.fallback")
                    log.info("tpu batch fallback to oracle: %s", res)
                else:
                    out[i] = _result_set(res, "tpu")
                    if gates[i] == "probe":
                        _fault_domain.note_success(sqls[i])
    elif tpu_idx:  # active tx: snapshot cannot see the tx overlay
        if strict:
            from orientdb_tpu.exec.tpu_engine import Uncompilable

            raise Uncompilable("active transaction on this thread")
    from orientdb_tpu.exec.oracle import execute_statement

    for i in range(n):
        if out[i] is None:
            stmt, p = items[i]
            out[i] = _result_set(execute_statement(db, stmt, p), "oracle")
    return out


def dispatch_lane_batch(
    db,
    sqls,
    params_list=None,
    ring_state=None,
    enqueue_ts=None,
    window_s=None,
    min_epoch=None,
):
    """Lane front door (server/coalesce): NON-BLOCKING dispatch of one
    fingerprint lane's homogeneous micro-batch. Returns a handle whose
    ``collect()`` yields the ResultSets (folding per-item stats
    attribution — amortized wall/device/transfer plus each item's
    queue wait), or None when the lane fast path does not apply; the
    caller then runs ``execute_query_batch``, which also records first
    executions and oracle statements.

    ``ring_state`` is the lane's opaque per-plan staging state (a plain
    dict the engine keeps its :class:`tpu_engine.ParamRing` in), so the
    coalescer never has to import the device stack. ``enqueue_ts``
    (monotonic: the first rider's lane entry) and ``window_s`` (the
    collection window that formed this batch) stamp the dispatch's
    flight record (obs/timeline) so overlap accounting can decompose
    lane wait vs service."""
    n = len(sqls)
    if params_list is None:
        params_list = [None] * n
    items = []
    for sql, p in zip(sqls, params_list):
        stmt = parse_cached(sql)
        if isinstance(stmt, A.ExplainStatement) or not stmt.is_idempotent:
            return None
        items.append((stmt, _normalize_params(p)))
    if not items or _choose_engine(db, items[0][0], None) != "tpu":
        return None
    from orientdb_tpu.exec import tpu_engine
    from orientdb_tpu.exec.devicefault import domain as _fault_domain

    if _fault_domain.admit(sqls[0]) == "quarantined":
        # homogeneous lane, one fingerprint: the whole drain degrades
        # to the generic path, whose gate serves the oracle ("probe"
        # proceeds — the lane dispatch IS the re-admission trial)
        return None
    ring = None
    if ring_state is not None:
        ring = ring_state.get("ring")
        if ring is None:
            ring = ring_state["ring"] = tpu_engine.ParamRing()
    import orientdb_tpu.obs.critpath as CP

    # detached worker-side harvest record: ring staging stamps its
    # param_upload/ring_hit timing here (this lane worker thread has no
    # per-request record); collect() amortizes the harvest across the
    # batch members, whose dicts travel back to the submitting sessions
    harvest = CP.CritPath("lane") if config.critpath_enabled else None
    with CP.active(harvest):
        h = tpu_engine.dispatch_lane(
            db,
            items,
            ring=ring,
            sql=sqls[0],
            enqueue_ts=enqueue_ts,
            window_s=window_s,
            min_epoch=min_epoch,
        )
    if h is None:
        return None
    return _LaneHandle(
        sqls, h, harvest.segs if harvest else None,
        db=db, params_list=params_list,
    )


class _LaneHandle:
    """Wraps an in-flight ``tpu_engine.LaneDispatch``: ``collect()``
    blocks on the fetch, wraps rows in ResultSets, and attributes the
    batch's amortized cost to each member fingerprint."""

    __slots__ = (
        "sqls", "_h", "_stage_segs", "item_segs", "_db", "_params_list",
    )

    def __init__(
        self, sqls, h, stage_segs=None, db=None, params_list=None
    ) -> None:
        self.sqls = sqls
        self._h = h
        self._db = db
        self._params_list = params_list
        #: worker-side staging stamps (param_upload / ring_hit seconds
        #: for the whole batch) harvested by dispatch_lane_batch
        self._stage_segs = stage_segs
        #: per-item critical-path splits built by collect(), read by
        #: the coalescer and folded into each submitter's record
        self.item_segs: Optional[List[Dict[str, float]]] = None

    def collect(self, queue_waits=None) -> List[ResultSet]:
        import time

        import orientdb_tpu.obs.stats as S

        t0 = time.perf_counter()
        with S.capture() as cap:
            outs = self._h.collect()
        wall = time.perf_counter() - t0
        n = max(len(outs), 1)
        per = wall / n
        host_per = max(0.0, wall - cap.device_s - cap.transfer_s) / n
        stage = self._stage_segs or {}
        results = []
        self.item_segs = []
        for k, (sql, rows) in enumerate(zip(self.sqls, outs)):
            rs = _result_set(rows, "tpu")
            S.stats.record_external(
                sql,
                per,
                engine="tpu",
                rows=len(rows) if hasattr(rows, "__len__") else None,
                queue_s=queue_waits[k] if queue_waits else 0.0,
                device_s=cap.device_s / n,
                transfer_s=cap.transfer_s / n,
                bytes_fetched=cap.bytes_fetched // n,
            )
            segs = {
                "queue": queue_waits[k] if queue_waits else 0.0,
                "device_compute": cap.device_s / n,
                "result_transfer": cap.transfer_s / n,
                "host_compute": host_per,
            }
            for name, v in stage.items():
                segs[name] = segs.get(name, 0.0) + v / n
            self.item_segs.append(
                {k2: v for k2, v in segs.items() if v > 0.0}
            )
            if self._db is not None and config.audit_sample_rate > 0.0:
                from orientdb_tpu.exec import audit as _audit

                p = (
                    self._params_list[k]
                    if self._params_list is not None
                    and k < len(self._params_list)
                    else None
                )
                _audit.auditor.maybe_submit(
                    self._db, sql, _normalize_params(p), rs, None, True
                )
            results.append(rs)
        return results


def explain(db, sql: str, params=None) -> ResultSet:
    stmt = parse_cached(sql)
    if not isinstance(stmt, A.ExplainStatement):
        stmt = A.ExplainStatement(stmt, profile=False)
    return explain_statement(db, stmt, _normalize_params(params))


def explain_statement(db, stmt: A.ExplainStatement, params) -> ResultSet:
    from orientdb_tpu.exec.planner import explain_plan

    return explain_plan(db, stmt, params)
