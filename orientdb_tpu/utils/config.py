"""Typed global configuration.

Analog of OrientDB's ``OGlobalConfiguration`` enum of typed keys
([E] core/.../config/OGlobalConfiguration.java, SURVEY.md §5.6), redesigned as
a single dataclass with environment-variable overrides (``ORIENTTPU_<FIELD>``)
instead of JVM system properties.

The per-session ``TRAVERSE_ENGINE`` switch (north star: sessions set
``TRAVERSE_ENGINE=tpu`` to route MATCH through the TPU backend instead of the
interpreted per-record path) lives here as the *default*; sessions may
override it per query.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env(name: str, default, cast):
    raw = os.environ.get(f"ORIENTTPU_{name.upper()}")
    if raw is None:
        return default
    if cast is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return cast(raw)


@dataclasses.dataclass
class GlobalConfiguration:
    # Query engine selection: "tpu" (compiled batched path), "oracle"
    # (pure-Python reference interpreter — the parity oracle), or "auto"
    # (tpu when a snapshot is attached, oracle otherwise).
    traverse_engine: str = "auto"

    # Expansion/compaction buffers are padded to powers of two >= this to
    # bound recompilation while keeping buffers small (ops/csr.bucket).
    min_expansion_cap: int = 8
    # Hard ceiling on a single expansion output buffer (rows). Expansions
    # that would exceed it are chunked over the binding table
    # (tpu_engine._expand_one_dir_chunked).
    max_expansion_cap: int = 1 << 22

    # Byte budget for one variable-depth frontier bitmap chunk
    # ([rows, bucket(V)] bools): the chunk row count shrinks as the graph
    # grows so deep-traversal memory stays bounded at SF100-scale vertex
    # counts (SURVEY.md §5.7).
    var_depth_bitmap_budget: int = 1 << 26

    # Buffer headroom multiplier for recorded size schedules: compiled
    # plans size buffers at bucket(observed * headroom), so
    # parameter-generic replays tolerate result sets up to that much
    # larger before an overflow re-record. 1.0 = exact-bucket sizing.
    schedule_headroom: float = 2.0

    # Extra empty BFS levels recorded past frontier exhaustion in
    # variable-depth (WHILE) plans: replays whose walks go up to this many
    # levels deeper than the recording still execute in place instead of
    # re-recording (depth varies with the query parameter).
    var_depth_pad_levels: int = 2

    # Schedule variants kept per cached statement: parameter values whose
    # live sizes exceed every variant's capacities record a new variant
    # rather than thrash-replacing one plan.
    plan_variants: int = 8

    # Plan cache entries (analog of OExecutionPlanCache [E]).
    plan_cache_size: int = 256

    # Device-memory budget for a replay's pre-materialized result page
    # ladder (pow2 prefixes in int32+int16, ~12 bytes/slot total): plans
    # whose ladder would exceed this emit only the full-width buffers, so
    # wide plans never triple their result memory under deep batches.
    result_page_budget_bytes: int = 16 << 20

    # Full result buffers at or below this many bytes skip the
    # meta-gated page election entirely: the replay returns ONE fused
    # buffer (data + meta row) whose copy starts in the batch's first
    # transfer wave. Every buffer fetch carries a fixed cost, so for
    # few-KB results one fused copy beats the
    # meta-then-elected-page protocol (the round-3 LDBC IS3–IS7
    # regression); above the threshold the election's byte savings win.
    result_direct_bytes: int = 64 << 10

    # Root candidates seed from a host index when the root WHERE has an
    # equality over an indexed field ([E] the index-vs-scan choice):
    # point lookups become V-independent instead of hull scans.
    index_root_seed: bool = True

    # Row-returning plans join the vmapped group dispatch when one
    # lane's full int32 result stack fits this budget (the group stacks
    # B of them on device); bigger plans keep per-lane dispatch + page
    # election.
    result_group_lane_bytes: int = 4 << 20

    # Vmapped group lanes materialize O(E) int32 intermediates in the
    # fused edge-predicate select; the group width is capped so
    # lanes × 4E stays inside this budget (v5e chips carry 16 GB HBM;
    # the graph itself plus runtime overhead take the rest). Oversized
    # batches dispatch as several capped Executes instead of OOMing
    # the compile and falling back to per-lane.
    group_hbm_budget_bytes: int = 6 << 30

    # Per-query property-column pruning (SURVEY.md §7's SF100 memory
    # plan): property columns upload to HBM on a plan's first reference
    # instead of eagerly at snapshot attach — columns no query touches
    # never cost device memory. False restores eager uploads.
    column_prune: bool = True

    # Query RESULT cache ([E] OCommandCache) — rows of idempotent queries
    # keyed by (sql, params, engine), invalidated by the mutation epoch.
    # Disabled by default, matching the reference.
    command_cache_enabled: bool = False
    command_cache_size: int = 512
    # Parsed-statement cache entries (analog of OStatementCache [E]).
    statement_cache_size: int = 1024

    # Sharding: device-mesh axis names (parallel/mesh_graph.py shards the
    # CSR over the shard axis; replicas carry independent query streams).
    mesh_shard_axis: str = "shards"
    mesh_replica_axis: str = "replicas"

    # Observability (orientdb_tpu/obs): queries slower than this many
    # milliseconds enter the slow-query log (0 disables); the ring keeps
    # the most recent slowlog_capacity entries, and the span tracer keeps
    # the most recent trace_capacity finished spans.
    slow_query_ms: float = 1000.0
    slowlog_capacity: int = 256
    trace_capacity: int = 4096
    # Query statistics & continuous profiling (obs/stats, obs/profile):
    # fraction of queries/traces folded into the per-fingerprint stats
    # table and the span-profile aggregator (1.0 = everything, 0
    # disables); the table keeps the query_stats_capacity hottest
    # fingerprints (LRU).
    stats_sample_rate: float = 1.0
    query_stats_capacity: int = 512

    # Dispatch flight recorder (obs/timeline): bounded ring of
    # per-dispatch lifecycle records (enqueue → lane window → plan
    # resolve → upload/ring hit → device dispatch → compute done →
    # transfer → result delivered) feeding the overlap accounting pass,
    # GET /debug/timeline (Chrome-trace/Perfetto export), and the
    # orienttpu_overlap_* gauges. timeline_capacity is the ring size
    # (0 disables recording entirely); recording also rides the
    # stats_sample_rate sampling decision. timeline_window_s bounds the
    # default export/accounting window (scrape-time gauges, the HTTP
    # endpoint's default, the debug bundle's timeline section).
    timeline_capacity: int = 2048
    timeline_window_s: float = 120.0

    # Critical-path attribution (obs/critpath; README "Critical-path
    # attribution"): each sampled request (the stats_sample_rate
    # decision) becomes a waterfall of named segments feeding
    # GET /stats/critpath, the per-SloClass rollups, and the
    # latency_regression alert's blame annotation. critpath_enabled
    # turns the plane off entirely; critpath_capacity bounds the ring
    # of recent decompositions (0 keeps aggregates but no ring);
    # critpath_blame_ratio is the fractional per-segment growth
    # (current window vs older history) a segment must show before the
    # blame diff names it.
    critpath_enabled: bool = True
    critpath_capacity: int = 512
    critpath_blame_ratio: float = 0.25

    # Admission control (server/http_server, server/binary_server):
    # shed WRITE requests with 503 + Retry-After when the listener's
    # in-flight depth or a database's staged-2PC backlog crosses these
    # thresholds — bounded queues beat collapse under overload. The
    # internal replication/2PC routes are exempt (shedding a phase-2
    # commit would CREATE in-doubt transactions). 0 disables a check.
    http_max_inflight: int = 128
    tx2pc_staged_max: int = 256
    # the Retry-After hint handed to shed clients; the shared
    # RetryPolicy (parallel/resilience) honors it over its own backoff
    retry_after_s: float = 0.5

    # Cross-session micro-batching (server/coalesce): concurrent
    # sessions' single queries land in per-database dispatch LANES
    # keyed by query fingerprint, so a drain forms a homogeneous
    # micro-batch hitting one compiled plan. Each lane's collection
    # window adapts to recent arrival rate and device time per batch,
    # hard-capped at coalesce_window_max_ms — the cap bounds the p50 a
    # lone query can lose to batch formation. A drain takes at most
    # coalesce_max_batch items; a lane idle longer than
    # coalesce_lane_idle_s stops its worker thread (a fresh submit
    # rebuilds it), and a database keeps at most coalesce_lanes_max
    # lanes (least-recently-used lane reaped past that).
    coalesce_window_max_ms: float = 5.0
    coalesce_max_batch: int = 256
    coalesce_lane_idle_s: float = 30.0
    coalesce_lanes_max: int = 64

    # Change-data-capture (orientdb_tpu/cdc): per-consumer event queues
    # are bounded at cdc_queue_max — a slow consumer either blocks the
    # producer (policy "block", bounded by cdc_poll_timeout_s) or sheds
    # its queue and transparently catches back up from the WAL.
    # cdc_poll_timeout_s also caps the default HTTP /changes long-poll
    # wait. Durable named cursors idle longer than
    # cdc_cursor_retention_s seconds are pruned at the next ack
    # (0 disables pruning).
    cdc_queue_max: int = 1024
    cdc_poll_timeout_s: float = 10.0
    cdc_cursor_retention_s: float = 7 * 86400.0

    # Alerting & health watchdog (obs/alerts, obs/watchdog): the
    # watchdog thread starts with Server and evaluates the alert-rule
    # catalog every watchdog_interval_s seconds over the registry
    # snapshot — nothing runs on the query hot path. A rule must breach
    # for alert_pending_ticks consecutive ticks before its alert fires
    # (pending -> firing); resolved alerts land in a bounded history
    # ring of alert_history_capacity entries.
    watchdog_enabled: bool = True
    watchdog_interval_s: float = 5.0
    alert_pending_ticks: int = 2
    alert_history_capacity: int = 256
    # Per-rule thresholds (the built-in catalog; README "Alerting &
    # health watchdog" documents each rule):
    alert_repl_lag_entries: int = 64
    alert_indoubt_age_s: float = 30.0
    alert_cdc_queue_depth: int = 512
    alert_wal_bytes: int = 1 << 30
    alert_rss_bytes: int = 12 << 30
    alert_jax_buffer_bytes: int = 14 << 30
    alert_recompiles_per_min: float = 30.0
    # Latency-regression baseline: a fingerprint's per-tick mean must
    # exceed its online EWMA by alert_latency_mads deviations (EWMA of
    # absolute deviation, the online MAD analog) with at least
    # alert_latency_min_calls calls in the tick to breach.
    alert_latency_mads: float = 6.0
    alert_latency_min_calls: int = 20
    # Two-window error-budget burn rate: breach when the short AND long
    # window error rates both exceed alert_burn_factor x the SLO
    # error-rate target.
    alert_slo_error_rate: float = 0.05
    alert_burn_factor: float = 4.0
    # Overlap-regression rule (obs/timeline + obs/alerts): the
    # device-idle fraction over the recent timeline window must exceed
    # its online EWMA baseline by alert_overlap_idle_mads deviations to
    # breach, and only when the window holds at least
    # alert_overlap_min_records dispatch records (idle computed over
    # two dispatches is noise, not regression evidence).
    alert_overlap_idle_mads: float = 6.0
    alert_overlap_min_records: int = 16

    # Trace-correlated logging (utils/logging): the bounded in-memory
    # ring of recent structured log records fed into the debug bundle's
    # admin-only "logs" section.
    log_ring_capacity: int = 512

    # Traffic simulator (workloads/driver): defaults for the closed-
    # loop mixed LDBC driver — concurrent client sessions (split HTTP/
    # binary), operations per session, the SNB-shaped write fraction of
    # the mix, and the settle window after chaos clears (replicas catch
    # up, breakers half-open, alerts resolve) before the SLO verdict.
    workload_sessions: int = 8
    workload_ops: int = 50
    workload_update_ratio: float = 0.1
    workload_settle_s: float = 8.0
    # SLO verdicts (obs/slo): default per-query-class targets a spec
    # inherits when a class declares none — p50/p99 latency ceilings
    # (milliseconds, read from the query-stats histograms), minimum
    # per-class success rate, and the error-budget burn ceiling (run
    # error rate over alert_slo_error_rate; > slo_max_burn fails).
    slo_p50_ms: float = 500.0
    slo_p99_ms: float = 5000.0
    slo_availability: float = 0.99
    slo_max_burn: float = 1.0

    # Incremental HBM snapshot maintenance (storage/deltas): a
    # delta-maintained snapshot pre-allocates this many spare vertex
    # rows and per-edge-class spare edge slots; committed writes apply
    # as device-side scatter patches into them instead of detaching the
    # snapshot. When the fullest slab (or the tombstone fraction)
    # crosses delta_compact_ratio, the maintainer folds the slabs back
    # into a clean CSR (epoch compaction, storage/epochs idiom).
    delta_slab_vertex_rows: int = 1024
    delta_slab_edge_slots: int = 4096
    delta_compact_ratio: float = 0.75

    # Tiered snapshots (storage/tiering; README "Tiered snapshots &
    # HBM cap"): when tier_hbm_cap_bytes > 0 and a snapshot's flat
    # adjacency exceeds it, admission attaches a TierManager — the
    # adjacency pages between a device-resident hot pool and host-pinned
    # cold blocks instead of uploading flat. 0 disables tiering.
    # tier_block_edges sets the target edges per block (the quotient
    # blocking widens a block that lands on a hub vertex rather than
    # splitting it). alert_tier_thrash is the tier_thrash alert
    # threshold: thrash events (reload of a recently evicted block)
    # per thrash window before the rule fires.
    tier_hbm_cap_bytes: int = 0
    tier_block_edges: int = 65536
    alert_tier_thrash: float = 8.0

    # Device-memory ledger (obs/memledger; README "Device-memory
    # ledger"): every serving-path device allocation registers an
    # attributed entry. memledger_sample_rate throttles only the
    # trace-id capture (byte totals stay exact — the sampled fast path
    # that holds registration under the <1.35x overhead guard).
    # memledger_leak_s is the lease age past which an outstanding
    # snapshot retain() reads as an epoch leak (hbm_epoch_leak rule;
    # 0 disables). memledger_tolerance bounds the live-but-untracked
    # residue reconcile() accepts as an instrumentation gap.
    # memledger_headroom_fraction of tier.cap_bytes is where the
    # hbm_headroom rule fires.
    memledger_enabled: bool = True
    memledger_sample_rate: float = 1.0
    memledger_leak_s: float = 30.0
    memledger_watermark_capacity: int = 256
    memledger_tolerance: float = 0.25
    memledger_headroom_fraction: float = 0.9

    # Materialized continuous MATCH views (exec/views): results of hot
    # fingerprints (>= view_min_calls recorded calls in the stats
    # table) are kept resident and served at cache speed, invalidated
    # CDC-EXACTLY — only events touching a view's class footprint kill
    # it, so unrelated writes never cost a recompute (unlike the
    # epoch-keyed command cache). view_cache_size bounds entries per
    # database; 0 disables the plane.
    view_min_calls: int = 8
    view_cache_size: int = 64

    # Device fault domain (exec/devicefault; README "Failure modes &
    # recovery"): every dispatch/fetch path runs under an escalation
    # ladder — classify, retry (devicefault_retry_attempts attempts
    # within devicefault_retry_budget_s seconds under the shared
    # RetryPolicy), memledger-guided relief on OOM, then quarantine the
    # plan's fingerprint to the oracle for devicefault_quarantine_ttl_s
    # seconds (probe re-admission after; failed probes double the TTL).
    # When relief leaves the memledger total above
    # devicefault_headroom_fraction x tier_hbm_cap_bytes (or an OOM
    # survives relief), the admission plane sheds writes with 503 +
    # Retry-After for devicefault_shed_s seconds.
    # alert_device_faults_per_min is the device_fault_storm rule's
    # classified-faults-per-minute threshold.
    devicefault_retry_attempts: int = 3
    devicefault_retry_budget_s: float = 2.0
    devicefault_quarantine_ttl_s: float = 15.0
    devicefault_shed_s: float = 2.0
    devicefault_headroom_fraction: float = 0.9
    alert_device_faults_per_min: float = 60.0

    # Continuous correctness plane (exec/audit, storage/scrub; README
    # "Continuous correctness: parity audits, scrub & fsck"):
    # audit_sample_rate is the fraction of compiled results shadow-
    # re-executed on the pure-Python oracle and digest-compared (rides
    # the stats sampling decision; 0 disables the auditor — the
    # default, parity audits are opt-in per deployment). The bounded
    # audit queue holds audit_queue_max captures (overflow drops count
    # parity.audit_dropped); a divergence record samples up to
    # audit_diff_rows rows per side and the replayable divergence ring
    # keeps audit_history_capacity records. scrub_enabled runs one
    # budgeted device-state scrub rotation per watchdog tick,
    # re-hashing at most scrub_budget_bytes of resident device blocks
    # against host-truth checksums per sweep.
    audit_sample_rate: float = 0.0
    audit_queue_max: int = 256
    audit_diff_rows: int = 5
    audit_history_capacity: int = 64
    scrub_enabled: bool = True
    scrub_budget_bytes: int = 16 << 20

    # Alert threshold (obs/alerts delta_slab_pressure): fires when the
    # snapshot.delta.slab_fill gauge crosses this fraction — deltas are
    # outpacing compaction.
    alert_slab_fill: float = 0.9

    # WAL / durability for the host record store
    # (orientdb_tpu.storage.durability): when wal_enabled and wal_dir are
    # set, server-created databases recover-or-create durably under
    # <wal_dir>/<name>; embedded databases opt in via
    # enable_durability/open_database. wal_fsync fsyncs every append.
    wal_enabled: bool = False
    wal_dir: Optional[str] = None
    wal_fsync: bool = False
    # fsync'd appends route through the C++ group-commit appender
    # (native/walappend.cpp) when its build is available; False pins the
    # pure-Python write+fsync path.
    wal_native: bool = True

    @classmethod
    def from_env(cls) -> "GlobalConfiguration":
        c = cls()
        for f in dataclasses.fields(cls):
            cast = f.type if isinstance(f.type, type) else None
            if cast is None:
                # dataclass stores the annotation as a string under
                # `from __future__ import annotations`
                cast = {"str": str, "int": int, "bool": bool, "float": float}.get(
                    str(f.type), str
                )
            setattr(c, f.name, _env(f.name, getattr(c, f.name), cast))
        return c


# Process-wide instance (OGlobalConfiguration is a static enum in the
# reference; a module-level singleton is the honest analog).
config = GlobalConfiguration.from_env()
