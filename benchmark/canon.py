"""The canonical form in which an answer is compared: the same few
lines run on the client's rows (in the load generator) and on the
reference's rows. No JAX, no numpy."""

from __future__ import annotations

import json
import zlib
from typing import List, Sequence


def digest(rows: List[Sequence], ordered: bool) -> list:
    """``[row count, crc32 of the rows, first value]`` of rows given as
    tuples in the shape's column order; sorted first unless the
    statement orders them itself. The first value makes a differing
    COUNT readable in the record."""
    tuples = [list(r) for r in rows]
    if not ordered:
        tuples.sort()
    first = tuples[0][0] if tuples and tuples[0] else None
    return [len(tuples), zlib.crc32(json.dumps(tuples).encode()), first]


def rows_of(dicts: List[dict], columns: List[str]) -> List[list]:
    return [[d.get(c) for c in columns] for d in dicts]
