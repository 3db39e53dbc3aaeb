"""One general traffic generator, driven by a file under
``benchmark/traffic/``.

A mix is a closed loop of ``sessions`` clients over a list of statement
shapes with whole-number weights. The weights are realised as ONE
repeating block in a fixed interleaving, which every session walks from
its own fixed offset: the order of shapes never depends on ``--seed``.
With ``"walk": "pinned"`` a session does not walk: it stays at its
offset's shape, one client with one statement of its own, so that with
as many sessions as the block is long every shape has one session and
every lane of the server one client.
The seed draws the parameters only, and for a rooted shape it draws them
from a stated band (quantiles of that graph's own distribution of the
count that sets the answer's size), as LDBC's parameter curation does,
so that one template's run time is a narrow distribution.

A parameter of a shape is one of

- ``{"const": 30}`` or ``{"const": "Jan"}``
- ``{"int": [lo, hi]}`` uniform whole numbers, both ends included;
  with ``"lead": v`` the pool's first tuple carries ``v`` (the value of
  the largest answer), see ``draw_pool``
- ``{"root": "<measure>", "band": [q_lo, q_hi]}`` a root whose measure
  lies between the two quantiles of the candidates' measures; the
  measures are the methods of the configuration's kinds module's
  ``Measures``. A measure returns one value per candidate, and the
  candidate's index is the parameter (a person); or it returns
  ``(values, candidates)``, one row of parameter values per candidate
  (a pair of persons, curated by their distance; a person and a first
  name), and the parameter's key is the row's names joined by commas
  (``"person1Id,person2Id"``). The candidates are a 2-D integer array,
  a sequence of tuples, or a sequence of numpy columns, one per name.

Every parameter keeps its type from draw to statement to reference: an
integer column gives Python ``int``, a string column Python ``str``.

No (statement, parameters) pair is drawn twice while the domain lasts.

``"think_ms"`` is the pause of a session before each send: a number of
milliseconds (0: none), or ``{"uniform": [lo, hi]}``, drawn anew for
every request from ``--seed`` and the session's index
(``loadgen.pauses``), so that a seed's sequence of pauses is fixed.
"""

from __future__ import annotations

import json
import os
import zlib
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(kind: str, name: str, root: str = HERE) -> dict:
    """``benchmark/<kind>/<name>.json``: configurations and mixes are
    found by the name ``BENCHMARK.json`` gives them."""
    path = os.path.join(root, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def realise_block(weights: List[int]) -> List[int]:
    """Shape indices of one block: ``weights[i]`` occurrences of ``i``,
    spread evenly (smooth weighted round-robin, ties to the lower index).
    A pure function of the weights."""
    if not weights or any(int(w) != w or w < 1 for w in weights):
        raise ValueError(f"weights must be whole numbers >= 1: {weights}")
    total = sum(weights)
    credit = [0] * len(weights)
    block = []
    for _ in range(total):
        credit = [c + w for c, w in zip(credit, weights)]
        i = max(range(len(weights)), key=lambda j: (credit[j], -j))
        credit[i] -= total
        block.append(i)
    return block


def session_offsets(sessions: int, block_len: int) -> List[int]:
    """Where in the block each session starts: spread over the block, so
    that at any moment the sessions stand at different shapes."""
    return [(s * block_len) // sessions % block_len for s in range(sessions)]


def think_range(spec) -> List[float]:
    """``[lo, hi]`` milliseconds from a mix's ``think_ms``."""
    lo, hi = spec["uniform"] if isinstance(spec, dict) else (spec, spec)
    if not 0.0 <= float(lo) <= float(hi):
        raise ValueError(f"think_ms must be 0 <= lo <= hi: {spec}")
    return [float(lo), float(hi)]


def band_members(values: np.ndarray, band) -> np.ndarray:
    """Indices whose value lies within the band's two quantiles of
    ``values`` (both ends included)."""
    q_lo, q_hi = float(band[0]), float(band[1])
    if not 0.0 <= q_lo <= q_hi <= 1.0:
        raise ValueError(f"band must be 0 <= lo <= hi <= 1: {band}")
    lo, hi = np.quantile(values, [q_lo, q_hi])
    return np.flatnonzero((values >= lo) & (values <= hi))


def _value_kind(v) -> str:
    if isinstance(v, str):
        return "str"
    if isinstance(v, (int, np.integer)) and not isinstance(v, (bool, np.bool_)):
        return "int"
    return type(v).__name__


def _typed_column(values, where: str) -> np.ndarray:
    """One parameter column as an array whose ``tolist()`` gives Python
    ``int`` (an integer column) or Python ``str`` (a string column), and
    nothing else: a column keeps its own type beside the others."""
    if not (isinstance(values, np.ndarray) and values.dtype.kind in "iuU"):
        # numpy would read a mix of numbers and strings as strings
        kinds = {_value_kind(v) for v in values}
        if kinds not in ({"int"}, {"str"}):
            raise TypeError(f"{where}: a parameter column holds integers or strings: {kinds}")
        values = np.asarray(values, np.int64 if kinds == {"int"} else str)
    if values.ndim != 1:
        raise ValueError(f"{where}: a parameter column is one-dimensional")
    return values


def _candidate_columns(candidates, names: List[str], n: int, where: str) -> List[np.ndarray]:
    """A tuple measure's candidates as one typed column per name, ``n``
    values each. Three forms: a 2-D integer array (a row per candidate),
    a sequence of numpy arrays (a column per name), or a sequence of
    tuples (a row per candidate, integers and strings side by side)."""
    if isinstance(candidates, np.ndarray):
        if candidates.ndim != 2 or candidates.dtype.kind not in "iu":
            raise TypeError(
                f"{where}: an array of candidates is 2-D and of integers; "
                "give strings as columns or as tuples"
            )
        cols = list(candidates.T)
    elif all(isinstance(c, np.ndarray) for c in candidates):
        cols = list(candidates)
    else:
        if any(len(row) != len(names) for row in candidates):
            raise ValueError(f"{where}: a candidate holds one value per name {names}")
        cols = list(zip(*candidates))
    if len(cols) != len(names) or any(len(c) != n for c in cols):
        raise ValueError(f"{where}: one column per name {names}, one value per candidate ({n})")
    return [_typed_column(c, where) for c in cols]


def draw_pool(shape: dict, measures, seed: int, want: int) -> Dict:
    """Up to ``want`` distinct parameter tuples for one shape, as
    ``{"names": [...], "rows": [[...], ...]}``. ``measures`` is the kinds
    module's ``Measures`` over the reference. Seeded by ``seed`` and the
    shape's name, so that adding a shape to a mix moves no other shape's
    parameters.

    Each parameter keeps its type: a value in ``rows`` is a Python
    ``int`` or ``str``, as its column holds it (``_candidate_columns``).

    The first tuple is the one warm-up records the shape's plan with, and
    a plan keeps the buffer sizes of the answer it was recorded on
    (``exec/tpu_engine.SizeSchedule``): it carries each parameter's
    ``lead`` value and the band's largest root, the largest answer of the
    pool, so that every seed records the same sizes and no later request
    outgrows them."""
    rng = np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, zlib.crc32(shape["name"].encode())]
    )
    names = [n for key in shape["params"] for n in key.split(",")]
    cols = []
    for name, spec in shape["params"].items():
        if "const" in spec:
            value = spec["const"]
            if isinstance(value, str):
                cols.append(np.full(want, value))
            else:
                cols.append(np.full(want, int(value), np.int64))
        elif "int" in spec:
            lo, hi = spec["int"]
            col = rng.integers(int(lo), int(hi) + 1, want)
            if "lead" in spec:
                col[0] = int(spec["lead"])
            cols.append(col)
        elif "root" in spec:
            measure = getattr(measures, spec["root"], None)
            if measure is None or spec["root"].startswith("_"):
                raise KeyError(f"no root measure {spec['root']!r}")
            values, candidates = measure(), None
            if isinstance(values, tuple):
                values, candidates = values
            members = band_members(values, spec["band"])
            if members.size == 0:
                raise ValueError(f"{shape['name']}: empty band {spec}")
            picks = rng.permutation(members)
            # the band's largest member leads the pool
            top = int(np.argmax(values[picks]))
            picks[[0, top]] = picks[[top, 0]]
            reps = -(-want // picks.size)
            col = np.tile(picks, reps)[:want]
            if candidates is None:
                cols.append(col)
            else:
                where = f"{shape['name']}.{name}"
                columns = _candidate_columns(candidates, name.split(","), len(values), where)
                cols.extend(c[col] for c in columns)
        else:
            raise ValueError(f"{shape['name']}.{name}: unknown draw {spec}")
    # keep the first occurrence of every tuple, in drawn order
    rows = dict.fromkeys(zip(*(c.tolist() for c in cols)) if cols else [()] * want)
    return {"names": names, "rows": [list(r) for r in rows]}


def build_plan(mix: dict, measures, seed: int, pool_size: int) -> dict:
    """Everything the load generator needs for one cell and seed: the
    statements, the block, each session's offset and each shape's pool
    of parameters."""
    shapes = mix["shapes"]
    if mix.get("loop", "closed") != "closed":
        raise ValueError("only closed loops are generated so far")
    walk = mix.get("walk", "block")
    if walk not in ("block", "pinned"):
        raise ValueError(f"walk must be 'block' or 'pinned': {walk!r}")
    block = realise_block([int(s["weight"]) for s in shapes])
    sessions = int(mix["sessions"])
    offsets = session_offsets(sessions, len(block))
    everyone = list(range(sessions))
    if walk == "pinned" and {block[o] for o in offsets} != set(block):
        raise ValueError(f"mix {mix.get('name')}: a pinned shape has no session")
    return {
        "sessions": sessions,
        "seed": int(seed),
        "think_ms": think_range(mix.get("think_ms", 0)),
        "block": block,
        "offsets": offsets,
        "stride": 1 if walk == "block" else 0,
        # the sessions that can have shape i in flight at one moment: the
        # largest batch its lane can meet
        "shape_sessions": [
            everyone if walk == "block" else [s for s in everyone if block[offsets[s]] == i]
            for i in range(len(shapes))
        ],
        "shapes": [
            {
                "name": s["name"],
                "sql": s["sql"],
                "columns": s["columns"],
                "ordered": bool(s.get("ordered", False)),
                "reference": s["reference"],
                "pool": draw_pool(s, measures, seed, pool_size),
            }
            for s in shapes
        ],
    }
