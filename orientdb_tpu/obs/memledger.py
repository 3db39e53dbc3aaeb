"""Process-wide device-memory ledger: attributed HBM accounting.

The delta plane (PR 15) and the tier plane (PR 16) both allocate, pin,
grow, and defer-free device buffers on the serving path — yet nothing
could answer "what is in HBM right now, who owns it, and is anything
leaking?". This module is that answer: every device allocation on the
serving path registers an attributed entry (owner kind + id, byte
size, creation trace id, pin state), and three consumers sit on top:

- **reconciliation** (:meth:`MemLedger.reconcile`, span
  ``memledger.reconcile``): diff ledger totals against
  ``jax.live_arrays()`` and classify the residue — live-but-untracked
  bytes are an instrumentation gap (reported, bounded by
  ``memledger_tolerance``); tracked-but-dead persistent entries are
  leak candidates; dead TRANSIENT entries (result pages, speculative
  prefetch pages) self-heal out of the ledger as reclaimed bytes.
- **epoch-leak detection**: every ``GraphSnapshot.retain`` records a
  lease (ts, trace id, epoch); a lease still held past
  ``memledger_leak_s`` is stale — the ``hbm_epoch_leak`` alert rule
  (obs/alerts) fires with the retaining lease's trace id as exemplar.
  ``hbm_headroom`` fires when attributed bytes approach
  ``tier_hbm_cap_bytes``.
- **surfaces**: scrape-time ``hbm.ledger_*`` / ``hbm.owner.*`` gauges
  ride ``snapshot_all()`` into ``/metrics`` and the member-labeled
  ``/cluster/metrics`` fan-in; ``GET /debug/memory`` (admin-only),
  the debug bundle's ``memory`` section, and console
  ``MEMORY [OWNERS|WATERMARK]``.

Owner taxonomy (fixed — the per-kind gauges and rollups key on it):

========== ==============================================================
kind       allocation site
========== ==============================================================
snapshot        base CSR / column arrays (``DeviceGraph._put``,
                ``apply_patches`` overlays re-register in place)
tier_pool       tiered hot-pool pages + block indexes (``t:*`` keys;
                storage/tiering grow/load/evict re-register)
delta_slab      overlay bucket-index tables (``bk:*`` keys,
                storage/deltas)
param_ring      device-resident parameter ring slots
                (``tpu_engine.ParamRing.stage``)
prefetched_page speculatively prefetched result pages (transient)
plan_const      per-class id sets baked into plan executables
                (``DeviceGraph.class_ids``); arrays a plan's recording
                kept for its replays (``DeviceGraph.adopt_plan_const``,
                unregistered when the plan is collected)
result_page     elected result pages awaiting host copy (transient)
========== ==============================================================

Registration is an upsert keyed ``(kind, owner, key)`` — re-puts
(patches, pool growth) refresh bytes in place. Byte totals are always
exact; only the *trace-id capture* rides the sampled fast path
(``memledger_sample_rate``), which is what holds the hot-path overhead
under the established <1.35x guard. ``memledger_enabled=False``
no-ops every call.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional, Tuple

from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics

#: the fixed owner-kind taxonomy (see module docstring)
OWNER_KINDS: Tuple[str, ...] = (
    "snapshot",
    "tier_pool",
    "delta_slab",
    "param_ring",
    "prefetched_page",
    "plan_const",
    "result_page",
)

#: kinds whose entries die without an unregister hook — result and
#: prefetch pages between dispatches, ring slots when their lane
#: retires. A dead transient entry is RECLAIMED (pruned by reconcile),
#: never a leak candidate; the kinds with explicit drop hooks
#: (snapshot/tier_pool/delta_slab/plan_const via _free_device) are the
#: ones whose dead entries mean something went wrong.
TRANSIENT_KINDS = frozenset({"result_page", "prefetched_page", "param_ring"})


def _nbytes(arr) -> int:
    try:
        return int(getattr(arr, "nbytes", 0))
    except Exception:
        return 0


class _Entry:
    """One attributed device allocation."""

    __slots__ = (
        "kind",
        "owner",
        "key",
        "nbytes",
        "ts",
        "trace_id",
        "pinned",
        "transient",
        "ref",
        "arr_id",
    )

    def __init__(self, kind, owner, key, nbytes, ts, trace_id, pinned, transient, ref, arr_id):
        self.kind = kind
        self.owner = owner
        self.key = key
        self.nbytes = nbytes
        self.ts = ts
        self.trace_id = trace_id
        self.pinned = pinned
        self.transient = transient
        self.ref = ref  # weakref to the jax array when weakref-able
        self.arr_id = arr_id  # id() fallback identity

    def alive(self, live_ids: Dict[int, int]) -> bool:
        """Is the registered array still device-live? Weakref identity
        when available (immune to id() recycling); else id+size match
        against the live set."""
        if self.ref is not None:
            a = self.ref()
            if a is None:
                return False
            try:
                if a.is_deleted():
                    return False
            except Exception:
                pass
            return True
        return live_ids.get(self.arr_id) == self.nbytes


class MemLedger:
    """The process-wide ledger singleton (module-level ``memledger``)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, str, str], _Entry] = {}
        self._totals: Dict[str, int] = {k: 0 for k in OWNER_KINDS}
        self._pinned_total = 0  # maintained incrementally (tick-path O(1))
        self._peaks: Dict[str, int] = {k: 0 for k in OWNER_KINDS}
        self._peak_total = 0
        #: id(snapshot) -> deque of lease dicts (ts, trace_id, epoch)
        self._leases: Dict[int, deque] = {}
        self._lease_refs: Dict[int, object] = {}  # id -> weakref(snap)
        #: bounded (ts, total_bytes) ring, throttled ~4 Hz
        self._watermarks: deque = deque()
        self._wm_last = 0.0
        self._refusal_counts: Dict[str, int] = {}
        self._last_refusal: Optional[Dict] = None
        self._events: deque = deque(maxlen=32)
        self._last_reconcile: Optional[Dict] = None

    # -- registration (the hot path) ----------------------------------------

    def register(
        self,
        kind: str,
        owner: str,
        key: str,
        arr=None,
        nbytes: Optional[int] = None,
        pinned: bool = False,
    ) -> None:
        """Upsert one attributed allocation. Bytes are exact on every
        call; the trace-id capture samples (``memledger_sample_rate``)
        so full-rate registration stays off the dispatch critical
        path's profile."""
        if not config.memledger_enabled:
            return
        nb = _nbytes(arr) if nbytes is None else int(nbytes)
        tid = None
        rate = config.memledger_sample_rate
        if rate > 0:
            from orientdb_tpu.obs.stats import sampled
            from orientdb_tpu.obs.trace import current_trace_id

            if sampled(rate):
                tid = current_trace_id()
        ref = None
        arr_id = 0
        if arr is not None:
            arr_id = id(arr)
            try:
                ref = weakref.ref(arr)
            except TypeError:
                ref = None
        now = time.time()
        ident = (kind, owner, key)
        with self._lock:
            old = self._entries.get(ident)
            if old is not None:
                self._totals[kind] -= old.nbytes
                if old.pinned:
                    self._pinned_total -= old.nbytes
                if tid is None:
                    tid = old.trace_id
            self._entries[ident] = _Entry(
                kind, owner, key, nb, now, tid, pinned,
                kind in TRANSIENT_KINDS, ref, arr_id,
            )
            self._totals[kind] = self._totals.get(kind, 0) + nb
            if pinned:
                self._pinned_total += nb
            self._note_watermark_locked(now)

    def unregister(self, kind: str, owner: str, key: str) -> None:
        with self._lock:
            e = self._entries.pop((kind, owner, key), None)
            if e is not None:
                self._totals[e.kind] -= e.nbytes
                if e.pinned:
                    self._pinned_total -= e.nbytes
                self._note_watermark_locked(time.time())

    def drop_owner(self, kind: str, owner: str) -> int:
        """Drop every entry of one owner (a freed DeviceGraph, an
        evicted pool). Returns the bytes released."""
        freed = 0
        with self._lock:
            for ident in [
                i for i, e in self._entries.items()
                if e.kind == kind and e.owner == owner
            ]:
                e = self._entries.pop(ident)
                freed += e.nbytes
                if e.pinned:
                    self._pinned_total -= e.nbytes
            if freed:
                self._totals[kind] -= freed
                self._note_watermark_locked(time.time())
        return freed

    def drop_graph(self, dg) -> int:
        """Free-time hook (``GraphSnapshot._free_device``): every kind
        attributed through this DeviceGraph's owner id goes at once."""
        owner = getattr(dg, "_ledger_owner", None)
        if owner is None:
            return 0
        freed = 0
        for kind in ("snapshot", "tier_pool", "delta_slab", "plan_const"):
            freed += self.drop_owner(kind, owner)
        return freed

    def register_graph_array(self, dg, key: str, arr) -> None:
        """Classify + register one ``DeviceGraph`` array by its store
        key (the ``memory_report`` prefix taxonomy): ``t:*`` pages are
        the tier pool, ``bk:*`` tables are the delta overlay's bucket
        index, everything else is the snapshot itself."""
        if not config.memledger_enabled:
            return
        owner = getattr(dg, "_ledger_owner", None)
        if owner is None:
            owner = f"snap:{id(getattr(dg, 'snap', dg)):x}"
        if key.startswith("t:"):
            kind = "tier_pool"
        elif key.startswith("bk:"):
            kind = "delta_slab"
        else:
            kind = "snapshot"
        self.register(kind, owner, key, arr=arr)

    # -- epoch leases --------------------------------------------------------

    def lease_acquired(self, snap) -> None:
        """One ``retain()``/``try_retain()`` pin recorded with its
        trace id — the exemplar an ``hbm_epoch_leak`` alert joins."""
        if not config.memledger_enabled:
            return
        from orientdb_tpu.obs.trace import current_trace_id

        sid = id(snap)
        lease = {
            "ts": time.time(),
            "trace_id": current_trace_id(),
            "epoch": int(getattr(snap, "epoch", 0) or 0),
        }
        with self._lock:
            dq = self._leases.get(sid)
            if dq is None:
                dq = self._leases[sid] = deque()
                try:
                    self._lease_refs[sid] = weakref.ref(
                        snap, lambda _r, s=sid: self._forget_snap(s)
                    )
                except TypeError:
                    self._lease_refs[sid] = None
            dq.append(lease)

    def lease_released(self, snap) -> None:
        """Drop the OLDEST outstanding lease (FIFO — dispatches retire
        roughly in admission order; the exact pairing does not matter
        for leak detection, only the outstanding count and ages do)."""
        if not config.memledger_enabled:
            return
        sid = id(snap)
        with self._lock:
            dq = self._leases.get(sid)
            if dq:
                dq.popleft()
            if not dq:
                self._leases.pop(sid, None)
                self._lease_refs.pop(sid, None)

    def _forget_snap(self, sid: int) -> None:
        with self._lock:
            self._leases.pop(sid, None)
            self._lease_refs.pop(sid, None)

    def stale_leases(self) -> List[Dict]:
        """Leases outstanding longer than ``memledger_leak_s`` — a
        snapshot epoch whose refcount stays nonzero that long with no
        dispatch retiring it is the epoch-leak signature (a crashed
        dispatch path that skipped ``release()``, a lost lane)."""
        leak_s = config.memledger_leak_s
        if leak_s <= 0:
            return []
        now = time.time()
        out: List[Dict] = []
        with self._lock:
            for sid, dq in self._leases.items():
                # each deque is append-ordered by ts: the first lease
                # younger than the threshold ends the scan (keeps the
                # watchdog-tick cost O(stale), not O(outstanding))
                for lease in dq:
                    age = now - lease["ts"]
                    if age <= leak_s:
                        break
                    out.append(
                        {
                            "epoch": lease["epoch"],
                            "age_s": round(age, 3),
                            "trace_id": lease["trace_id"],
                            "outstanding": len(dq),
                        }
                    )
        return out

    def lease_count(self) -> int:
        with self._lock:
            return sum(len(dq) for dq in self._leases.values())

    # -- refusals (satellite: tiered+mesh / tiered+overlay telemetry) -------

    def note_refusal(self, reason: str, detail: str) -> None:
        """Count one tier-composition refusal (``tier.refusals`` total
        + per-reason ``tier.refusals.<reason>``) and remember the last
        one for ``/debug/memory`` — operators see WHY a snapshot did
        not tier, not just a raised ValueError in someone's log."""
        metrics.incr("tier.refusals")
        metrics.incr(f"tier.refusals.{reason}")
        with self._lock:
            self._refusal_counts[reason] = (
                self._refusal_counts.get(reason, 0) + 1
            )
            self._last_refusal = {
                "reason": reason,
                "detail": detail[:200],
                "ts": time.time(),
            }

    def note_event(self, kind: str, detail: str) -> None:
        """Breadcrumb ring for memory-plane lifecycle events (epoch
        compaction swaps, pool growth) shown in ``/debug/memory``."""
        with self._lock:
            self._events.append(
                {"kind": kind, "detail": detail[:200], "ts": time.time()}
            )

    # -- rollups / watermarks ------------------------------------------------

    def _note_watermark_locked(self, now: float) -> None:
        total = sum(self._totals.values())
        if total > self._peak_total:
            self._peak_total = total
        for k, v in self._totals.items():
            if v > self._peaks.get(k, 0):
                self._peaks[k] = v
        if now - self._wm_last >= 0.25:
            self._wm_last = now
            self._watermarks.append((round(now, 3), total))
            cap = max(int(config.memledger_watermark_capacity), 1)
            while len(self._watermarks) > cap:
                self._watermarks.popleft()

    def totals(self) -> Dict[str, int]:
        with self._lock:
            return {k: self._totals.get(k, 0) for k in OWNER_KINDS}

    def total_bytes(self) -> int:
        with self._lock:
            return sum(self._totals.values())

    def entry_count(self) -> int:
        with self._lock:
            return len(self._entries)

    def pinned_bytes(self) -> int:
        with self._lock:
            return self._pinned_total

    def telemetry(self) -> Dict:
        """Every scrape-time number in ONE lock acquisition — the
        watchdog ticks ``snapshot_all()`` at up to 50 Hz in tests, so
        the per-tick provider must not iterate entries or take the
        lock once per gauge."""
        with self._lock:
            return {
                "totals": {k: self._totals.get(k, 0) for k in OWNER_KINDS},
                "total": sum(self._totals.values()),
                "entries": len(self._entries),
                "pinned": self._pinned_total,
                "peak": self._peak_total,
            }

    def peaks(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._peaks)

    def peak_total(self) -> int:
        with self._lock:
            return self._peak_total

    def watermarks(self) -> List[Tuple[float, int]]:
        with self._lock:
            return list(self._watermarks)

    def owners(self) -> Dict[str, Dict]:
        """Per-kind rollup: bytes, entries, owners, oldest entry age —
        the ``/debug/memory`` OWNERS table."""
        now = time.time()
        with self._lock:
            out: Dict[str, Dict] = {
                k: {"bytes": 0, "entries": 0, "owners": set(), "oldest_s": 0.0}
                for k in OWNER_KINDS
            }
            for e in self._entries.values():
                row = out[e.kind]
                row["bytes"] += e.nbytes
                row["entries"] += 1
                row["owners"].add(e.owner)
                row["oldest_s"] = max(row["oldest_s"], now - e.ts)
        for row in out.values():
            row["owners"] = len(row["owners"])
            row["oldest_s"] = round(row["oldest_s"], 3)
        return out

    # -- reconciliation ------------------------------------------------------

    def reconcile(self) -> Dict:
        """Diff the ledger against ``jax.live_arrays()``:

        - ``untracked_bytes`` — live on device, not in the ledger: an
          instrumentation gap (reported; ``ok`` while it stays under
          ``memledger_tolerance`` × live bytes);
        - ``alias_bytes`` — live arrays that are the per-shard inner
          buffers (``Shard.data``) of a MATCHED entry's array:
          ``jax.live_arrays()`` enumerates both the outer ArrayImpl
          and its shard buffers, so without this credit every tracked
          byte would double-count as untracked;
        - ``tracked_dead`` — persistent entries whose array died
          without an unregister: leak candidates, one row each;
        - ``reclaimed_bytes`` — dead TRANSIENT entries (result /
          prefetch pages) pruned here, the ledger self-healing.
        """
        from orientdb_tpu.obs.trace import span

        with span("memledger.reconcile"):
            live_total = 0
            live_ids: Dict[int, int] = {}
            try:
                import jax

                for a in jax.live_arrays():
                    try:
                        if a.is_deleted():
                            continue
                    except Exception:
                        pass
                    nb = _nbytes(a)
                    live_ids[id(a)] = nb
                    live_total += nb
            except Exception:
                pass
            matched = 0
            reclaimed = 0
            alias_bytes = 0
            seen_alias: set = set()
            tracked_dead: List[Dict] = []
            with self._lock:
                for ident in list(self._entries):
                    e = self._entries[ident]
                    if e.alive(live_ids):
                        matched += e.nbytes
                        a = e.ref() if e.ref is not None else None
                        if a is not None:
                            try:
                                for sh in a.addressable_shards:
                                    d = sh.data
                                    did = id(d)
                                    if (
                                        d is not None
                                        and did != id(a)
                                        and did in live_ids
                                        and did not in seen_alias
                                    ):
                                        seen_alias.add(did)
                                        alias_bytes += live_ids[did]
                            except Exception:
                                pass
                    elif e.transient:
                        reclaimed += e.nbytes
                        del self._entries[ident]
                        self._totals[e.kind] -= e.nbytes
                        if e.pinned:
                            self._pinned_total -= e.nbytes
                    else:
                        tracked_dead.append(
                            {
                                "kind": e.kind,
                                "owner": e.owner,
                                "key": e.key,
                                "bytes": e.nbytes,
                                "age_s": round(time.time() - e.ts, 3),
                                "trace_id": e.trace_id,
                            }
                        )
            untracked = max(0, live_total - matched - alias_bytes)
            tol = config.memledger_tolerance
            ok = (
                untracked <= live_total * tol
                if live_total > 0
                else True
            )
            report = {
                "live_bytes": live_total,
                "ledger_bytes": self.total_bytes(),
                "matched_bytes": matched,
                "alias_bytes": alias_bytes,
                "untracked_bytes": untracked,
                "reclaimed_bytes": reclaimed,
                "tracked_dead_bytes": sum(
                    r["bytes"] for r in tracked_dead
                ),
                "tracked_dead": tracked_dead[:16],
                "tolerance": tol,
                "ok": ok,
                "ts": round(time.time(), 3),
            }
            with self._lock:
                self._last_reconcile = report
            return report

    # -- surfaces ------------------------------------------------------------

    def report(self, reconcile: bool = True) -> Dict:
        """The ``GET /debug/memory`` / debug-bundle document."""
        rec = self.reconcile() if reconcile else None
        with self._lock:
            last_rec = self._last_reconcile
            refusals = dict(self._refusal_counts)
            last_refusal = self._last_refusal
            events = list(self._events)
            leases = sum(len(dq) for dq in self._leases.values())
        return {
            "owners": self.owners(),
            "totals": self.totals(),
            "total_bytes": self.total_bytes(),
            "peak_bytes": self.peak_total(),
            "peak_by_owner": self.peaks(),
            "pinned_bytes": self.pinned_bytes(),
            "entries": self.entry_count(),
            "watermarks": [
                {"ts": ts, "bytes": b} for ts, b in self.watermarks()
            ],
            "reconcile": rec if rec is not None else last_rec,
            "leases": {
                "outstanding": leases,
                "stale": self.stale_leases(),
            },
            "refusals": {
                "counts": refusals,
                "last": last_refusal,
            },
            "events": events,
        }

    def reset(self) -> None:
        """Test hook: forget everything (entries, leases, peaks,
        refusals) — the singleton survives across tests."""
        with self._lock:
            self._entries.clear()
            self._totals = {k: 0 for k in OWNER_KINDS}
            self._pinned_total = 0
            self._peaks = {k: 0 for k in OWNER_KINDS}
            self._peak_total = 0
            self._leases.clear()
            self._lease_refs.clear()
            self._watermarks.clear()
            self._wm_last = 0.0
            self._refusal_counts.clear()
            self._last_refusal = None
            self._events.clear()
            self._last_reconcile = None


#: the process-wide ledger
memledger = MemLedger()


def ledger_telemetry() -> None:
    """Scrape-time gauge provider (rides ``registry.snapshot_all`` →
    ``/metrics`` as ``orienttpu_hbm_*`` and the member-labeled
    ``/cluster/metrics`` fan-in)."""
    if not config.memledger_enabled:
        return
    t = memledger.telemetry()
    metrics.gauge("hbm.ledger_bytes", float(t["total"]))
    metrics.gauge("hbm.ledger_entries", float(t["entries"]))
    metrics.gauge("hbm.ledger_pinned_bytes", float(t["pinned"]))
    metrics.gauge("hbm.ledger_peak_bytes", float(t["peak"]))
    metrics.gauge("hbm.leak_leases", float(len(memledger.stale_leases())))
    for kind in OWNER_KINDS:
        metrics.gauge(f"hbm.owner.{kind}_bytes", float(t["totals"][kind]))


def _install() -> None:
    from orientdb_tpu.obs.profile import register_gauge_provider

    register_gauge_provider(ledger_telemetry)


_install()
