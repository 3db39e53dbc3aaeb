"""Crash-safe evidence streaming: an append-only, fsync'd JSONL sink.

A run that writes its results only at process exit leaves nothing when
it is killed at a time limit. Here every completed block's result is
appended as one JSON line and flushed + fsync'd immediately, so a
SIGKILL mid-run still leaves every finished block on disk.
``tools/dryrun.py`` emits after every parity query.

The format is one JSON object per line::

    {"seq": 3, "ts": 1754…, "elapsed_s": 41.2, "block": "ldbc_is",
     "data": {…}}

:func:`read_evidence` tolerates a torn final line (the record being
written when the process died) by skipping anything that does not
parse — mirroring the WAL's torn-tail discipline.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional


class EvidenceSink:
    """Append-only JSONL writer; every record is durable before
    :meth:`emit` returns."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._lock = threading.Lock()
        self._fh = None
        self._seq = 0
        self._t0 = time.perf_counter()

    def emit(self, block: str, data) -> Dict:
        """Append one evidence record for ``block``; returns it."""
        with self._lock:
            self._seq += 1
            rec = {
                "seq": self._seq,
                "ts": round(time.time(), 3),
                "elapsed_s": round(time.perf_counter() - self._t0, 3),
                "block": block,
                "data": data,
            }
            line = json.dumps(rec, sort_keys=True) + "\n"
            if self._fh is None:
                d = os.path.dirname(os.path.abspath(self.path))
                os.makedirs(d, exist_ok=True)
                self._fh = open(self.path, "a", encoding="utf-8")
            self._fh.write(line)
            self._fh.flush()
            os.fsync(self._fh.fileno())
        return rec

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


def read_evidence(path: str) -> List[Dict]:
    """Parse an evidence stream; a torn/corrupt line is skipped (the
    record being written when the process died)."""
    if not os.path.exists(path):
        return []
    out: List[Dict] = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue
    return out


def evidence_sink(default_path: Optional[str]) -> Optional[EvidenceSink]:
    """Sink at ``$ORIENTTPU_EVIDENCE`` (overrides), else at
    ``default_path``; None when both are unset — callers no-op."""
    path = os.environ.get("ORIENTTPU_EVIDENCE") or default_path
    return EvidenceSink(path) if path else None
