"""Query results.

Parity layer for OrientDB's ``OResult`` / ``OResultInternal`` / ``OResultSet``
([E] core/.../sql/executor/OResultInternal.java, SURVEY.md §1 layer 5): a
result is either an *element* (a record) or a *projection* (a computed row of
named properties); a result set is a forward-only stream with ``has_next`` /
``next`` plus pythonic iteration.

The TPU engine marshals device arrays back into these rows (the
"OResultInternal-parity rows" requirement of the north star), so parity tests
compare `[sorted] list(rs.to_dicts())` across engines.
"""

from __future__ import annotations

import hashlib
from collections import Counter
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

from orientdb_tpu.models.record import Document
from orientdb_tpu.models.rid import RID


# ---------------------------------------------------------------------------
# result canonicalization (THE parity definition)
# ---------------------------------------------------------------------------
# Every parity check (the shadow-oracle auditor in exec/audit, the tests)
# must agree on what "the same result set" means; all import these helpers
# so the definitions cannot drift apart.


def canonical_rows(rows: Iterable[Dict[str, object]]) -> List[Tuple]:
    """Order-insensitive canonical form of a list of plain-dict rows
    (the ``to_dicts()`` shape): each row becomes a sorted item tuple,
    the rows sort as a multiset. Mixed-type rows that defeat tuple
    ordering fall back to a repr sort key — multiset equality is
    preserved either way (same deterministic key on both sides)."""
    items = [tuple(sorted(r.items())) for r in rows]
    try:
        return sorted(items)
    except TypeError:
        return sorted(items, key=repr)


def result_digest(rows: Iterable[Dict[str, object]]) -> str:
    """Stable 64-bit hex digest of :func:`canonical_rows` — what the
    auditor compares (and divergence records carry) instead of keeping
    both row sets alive."""
    h = hashlib.blake2b(digest_size=8)
    for row in canonical_rows(rows):
        h.update(repr(row).encode())
        h.update(b"\x00")
    return h.hexdigest()


def rows_diff_sample(
    served: Iterable[Dict[str, object]],
    oracle: Iterable[Dict[str, object]],
    limit: int = 5,
) -> Dict[str, List[str]]:
    """Row-level divergence sample for a replayable divergence record:
    up to ``limit`` canonical rows present only on each side."""
    ca = Counter(repr(t) for t in canonical_rows(served))
    cb = Counter(repr(t) for t in canonical_rows(oracle))
    return {
        "only_served": list((ca - cb).elements())[:limit],
        "only_oracle": list((cb - ca).elements())[:limit],
    }


class Result:
    """One row: wraps a record or a projection map."""

    __slots__ = ("_element", "_props", "_metadata")

    def __init__(
        self,
        element: Optional[Document] = None,
        props: Optional[Dict[str, object]] = None,
    ) -> None:
        self._element = element
        self._props: Dict[str, object] = props or {}
        self._metadata: Dict[str, object] = {}

    # -- OResult surface ---------------------------------------------------

    @property
    def is_element(self) -> bool:
        return self._element is not None and not self._props

    @property
    def element(self) -> Optional[Document]:
        return self._element

    def get_property(self, name: str, default=None):
        if name in self._props:
            return self._props[name]
        if self._element is not None:
            return self._element.get(name, default)
        return default

    def property_names(self) -> List[str]:
        if self._props:
            return list(self._props.keys())
        if self._element is not None:
            return self._element.field_names()
        return []

    def set_property(self, name: str, value) -> None:
        self._props[name] = value

    def set_metadata(self, name: str, value) -> None:
        self._metadata[name] = value

    def get_metadata(self, name: str, default=None):
        return self._metadata.get(name, default)

    @property
    def rid(self) -> Optional[RID]:
        return self._element.rid if self._element is not None else None

    def __getitem__(self, name: str):
        return self.get_property(name)

    def to_dict(self) -> Dict[str, object]:
        """Plain-python row; records are rendered as their RID string (the
        stable identity used by parity comparisons)."""
        if self.is_element:
            assert self._element is not None
            return self._element.to_dict()
        return {k: _plain(v) for k, v in self._props.items()}

    def __repr__(self) -> str:
        if self.is_element:
            return f"Result({self._element!r})"
        return f"Result({self._props!r})"


def _plain(v):
    if isinstance(v, Document):
        return str(v.rid) if v.rid.is_persistent else v.to_dict()
    if isinstance(v, RID):
        return str(v)
    if isinstance(v, Result):
        return v.to_dict()
    if isinstance(v, (list, tuple)):
        return [_plain(x) for x in v]
    if isinstance(v, dict):
        return {k: _plain(x) for k, x in v.items()}
    return v


class ColumnarRows:
    """Projection rows kept as decoded object columns.

    The TPU engine's columnar fast path (`tpu_engine._fast_rows`) decodes
    device columns into per-projection object arrays; building a `Result`
    per row up front costs more host time than the whole device solve for
    large result sets. This sequence materializes `Result` objects only if
    a caller actually iterates, and `to_dicts()` goes straight from the
    columns (the common parity/serialization consumer)."""

    __slots__ = ("names", "cols", "n")

    def __init__(self, names: List[str], cols: List, n: int) -> None:
        self.names = names
        self.cols = cols
        self.n = n

    def __len__(self) -> int:
        return self.n

    def __getitem__(self, i):
        """List-compatible access (int index or slice), materializing
        `Result` objects on demand — callers annotated `List[Result]`
        must not explode just because the fast path produced the rows."""
        if isinstance(i, slice):
            idx = range(*i.indices(self.n))
            return [self._row(j) for j in idx]
        j = i + self.n if i < 0 else i
        if not 0 <= j < self.n:
            raise IndexError(i)
        return self._row(j)

    def _row(self, j: int) -> Result:
        return Result(
            props={n: c[j] for n, c in zip(self.names, self.cols)}
        )

    def __iter__(self) -> Iterator[Result]:
        names = self.names
        if not self.cols:
            for _ in range(self.n):
                yield Result(props={})
            return
        for row in zip(*self.cols):
            yield Result(props=dict(zip(names, row)))

    def to_dicts(self) -> List[Dict[str, object]]:
        names = self.names
        if not self.cols:
            return [{} for _ in range(self.n)]
        return [dict(zip(names, row)) for row in zip(*self.cols)]


class ResultSet:
    """Forward-only row stream ([E] OResultSet), with an attached execution
    plan for EXPLAIN/PROFILE."""

    def __init__(self, rows: Iterable[Result], plan=None) -> None:
        self._rows = rows
        self._it: Optional[Iterator[Result]] = None
        self._peeked: Optional[Result] = None
        self._exhausted = False
        self.plan = plan

    def has_next(self) -> bool:
        if self._peeked is not None:
            return True
        if self._exhausted:
            return False
        if self._it is None:
            self._it = iter(self._rows)
        try:
            self._peeked = next(self._it)
            return True
        except StopIteration:
            self._exhausted = True
            return False

    def next(self) -> Result:
        if not self.has_next():
            raise StopIteration
        row, self._peeked = self._peeked, None
        assert row is not None
        return row

    def __iter__(self) -> Iterator[Result]:
        while self.has_next():
            yield self.next()

    def __next__(self) -> Result:
        return self.next()

    def to_list(self) -> List[Result]:
        return list(self)

    def to_dicts(self) -> List[Dict[str, object]]:
        # bulk path: untouched columnar rows skip Result materialization
        # entirely (consumes the stream, like the row-by-row path below)
        if (
            self._it is None
            and not self._exhausted
            and isinstance(self._rows, ColumnarRows)
        ):
            self._exhausted = True
            return self._rows.to_dicts()
        return [r.to_dict() for r in self]

    def close(self) -> None:  # API parity; nothing to release host-side
        self._exhausted = True
        self._peeked = None
