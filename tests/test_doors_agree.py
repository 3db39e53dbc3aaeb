"""The doors give one answer on the statements the cells send.

``exec/tpu_engine`` reaches the device through several front doors
(ROADMAP C1): the single ``execute`` behind ``db.query``, the batch
behind ``db.query_batch`` (singles, and the vmapped group from
``_GROUP_MIN`` same-plan items on), the lane door the server's
coalescer calls, and ``profile_execute`` behind ``PROFILE``. The
benchmark's cells use the lane door alone. Before a door may be deleted
something has to say that all of them answer alike: this file does, on
the seven statements of ``benchmark/traffic/{scan_4s,rooted_16s,
ic13_16s,bfs_1s}.json`` (the SQL is copied here, over this graph's
classes; nothing of ``benchmark`` is imported), over one small
array-native graph, against plain numpy over the snapshot's own arrays.
"""

import numpy as np
import pytest

import orientdb_tpu.exec.engine as E
import orientdb_tpu.obs.timeline as TL
from orientdb_tpu.exec import tpu_engine
from orientdb_tpu.exec.tpu_engine import _GROUP_MIN, drain_warmups
from orientdb_tpu.storage.bigshape import (
    build_snb_shape,
    numpy_1hop_count,
    numpy_2hop_count,
    numpy_config5_count,
)
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics

PERSONS = 300

STATEMENTS = {
    "config5": (
        "MATCH {class:Person, as:p, where:(age > :minAge)}"
        ".outE('knows'){where:(creationDate > :d)}"
        ".inV(){as:f, where:(age < :maxAge)}, "
        "{class:Message, as:m}-hasCreator->{as:f} RETURN count(*) AS n"
    ),
    "creator_1hop": (
        "MATCH {class:Message, as:m, where:(length > :minLen)}"
        "-hasCreator->{as:p, where:(age < :maxAge)} RETURN count(*) AS n"
    ),
    "knows_2hop": (
        "MATCH {class:Person, as:p, where:(age > :minAge)}"
        "-knows->{as:f}-knows->{as:g, where:(age < :maxAge)} "
        "RETURN count(*) AS n"
    ),
    "knows_1hop": (
        "MATCH {class:Person, as:p, where:(age > :minAge)}"
        "-knows->{as:f, where:(age < :maxAge)} RETURN count(*) AS n"
    ),
    "friends": (
        "MATCH {class:Person, as:p, where:(uid = :personId)}-knows-{as:f} "
        "RETURN f.uid AS personId, f.age AS age"
    ),
    "path_len": (
        "MATCH {class:Person, as:a, where:(uid = :person1Id)}, "
        "{class:Person, as:b, where:(uid = :person2Id)} "
        "RETURN shortestPath(a, b, 'BOTH', 'knows').size() - 1 AS len"
    ),
    "bfs_levels": (
        "SELECT $depth AS depth, count(*) AS n FROM (TRAVERSE both('knows') "
        "FROM (SELECT FROM Person WHERE uid = :source) STRATEGY BREADTH_FIRST) "
        "GROUP BY $depth"
    ),
}


# -- the plain references: numpy over the snapshot's arrays -------------------


class Reference:
    def __init__(self, snap) -> None:
        self.snap = snap
        knows = snap.edge_classes["knows"]
        self.knows = knows
        self.knows_src = np.repeat(
            np.arange(snap.num_vertices), np.diff(knows.indptr_out)
        )
        age = snap.v_columns["age"]
        self.age, self.person = age.values, age.present
        self.length = snap.v_columns["length"].values

    def config5(self, p):
        # the traffic file pins the two ages the reference has as literals
        assert (p["minAge"], p["maxAge"]) == (40, 30)
        return [(numpy_config5_count(self.snap, p["d"]),)]

    def creator_1hop(self, p):
        hc = self.snap.edge_classes["hasCreator"]
        message = np.repeat(
            np.arange(self.snap.num_vertices), np.diff(hc.indptr_out)
        )
        hit = (self.length[message] > p["minLen"]) & (
            self.age[hc.dst] < p["maxAge"]
        )
        return [(int(hit.sum()),)]

    def _masks(self, p):
        return (
            (self.age > p["minAge"]) & self.person,
            (self.age < p["maxAge"]) & self.person,
        )

    def knows_2hop(self, p):
        src, dst = self._masks(p)
        return [(numpy_2hop_count(self.snap, src, self.person, dst),)]

    def knows_1hop(self, p):
        src, dst = self._masks(p)
        return [(numpy_1hop_count(self.snap, src, dst),)]

    def friends(self, p):
        u, k = p["personId"], self.knows
        both = np.concatenate(
            [
                k.dst[k.indptr_out[u] : k.indptr_out[u + 1]],
                k.src[k.indptr_in[u] : k.indptr_in[u + 1]],
            ]
        )
        return sorted((int(f), int(self.age[f])) for f in both)

    def path_len(self, p):
        a, b = p["person1Id"], p["person2Id"]
        seen = np.zeros(self.snap.num_vertices, bool)
        seen[a] = True
        level, hops = np.array([a]), 0
        while level.size and not seen[b]:
            nxt = np.concatenate(
                [
                    self.knows.dst[np.isin(self.knows_src, level)],
                    self.knows_src[np.isin(self.knows.dst, level)],
                ]
            )
            level = np.unique(nxt[~seen[nxt]])
            seen[level] = True
            hops += 1
        return [(hops if seen[b] else -1,)]

    def bfs_levels(self, p):
        depth = np.full(self.snap.num_vertices, -1)
        depth[p["source"]] = 0
        level, hops = np.array([p["source"]]), 0
        while level.size:
            nxt = np.concatenate(
                [
                    self.knows.dst[np.isin(self.knows_src, level)],
                    self.knows_src[np.isin(self.knows.dst, level)],
                ]
            )
            level = np.unique(nxt[depth[nxt] < 0])
            hops += 1
            depth[level] = hops
        return list(enumerate(np.bincount(depth[depth >= 0]).tolist()))


def _canon(dicts):
    """Rows as sorted tuples, columns in the order of RETURN."""
    return sorted(tuple(d.values()) for d in dicts)


@pytest.fixture(scope="module")
def graph():
    db, snap = build_snb_shape(
        PERSONS, msgs_per_person=3, avg_knows=5, seed=33, name="doors_agree"
    )
    was = config.view_min_calls
    # a materialized view would answer before any door reached the device
    config.view_min_calls = 1 << 30
    yield db, Reference(snap)
    config.view_min_calls = was
    drain_warmups()
    db.detach_snapshot()


def _draws(name: str, ref: Reference, n: int):
    """n seeded parameter sets of one statement, none with an empty
    answer by construction of the ranges."""
    rng = np.random.default_rng(sorted(STATEMENTS).index(name))
    k = ref.knows
    loops = set(k.dst[ref.knows_src == k.dst].tolist())
    persons = [u for u in range(PERSONS) if u not in loops]
    out = []
    for _ in range(n):
        if name == "config5":
            p = {"minAge": 40, "d": int(rng.integers(10_000, 16_000)), "maxAge": 30}
        elif name == "creator_1hop":
            p = {
                "minLen": int(rng.integers(200, 1500)),
                "maxAge": int(rng.integers(30, 60)),
            }
        elif name in ("knows_2hop", "knows_1hop"):
            p = {
                "minAge": int(rng.integers(20, 50)),
                "maxAge": int(rng.integers(35, 70)),
            }
        elif name == "friends":
            p = {"personId": int(rng.choice(persons))}
        elif name == "bfs_levels":
            p = {"source": int(rng.choice(persons))}
        else:
            a, b = rng.choice(persons, 2, replace=False)
            p = {"person1Id": int(a), "person2Id": int(b)}
        out.append(p)
    return out


# -- the doors: each takes (db, sql, list of parameter sets) and returns
# one list of row dicts a parameter set, every answer from the device ------


def _single(db, sql, plist):
    out = []
    for p in plist:
        rs = db.query(sql, params=p, engine="tpu", strict=True)
        assert rs.engine == "tpu"
        out.append(rs.to_dicts())
    return out


def _batch(db, sql, plist):
    rss = db.query_batch([sql] * len(plist), plist, engine="tpu", strict=True)
    assert [rs.engine for rs in rss] == ["tpu"] * len(plist)
    return [rs.to_dicts() for rs in rss]


def _batch_singles(db, sql, plist):
    assert len(plist) < _GROUP_MIN
    return _batch(db, sql, plist)


def _batch_group(db, sql, plist):
    assert len(plist) >= _GROUP_MIN
    _batch(db, sql, plist)
    drain_warmups()  # a first group compiles behind the answer
    TL.recorder.reset()
    out = _batch(db, sql, plist)
    paths = [r["path"] for r in TL.recorder.records()]
    assert paths == ["group"], f"the batch ran as {paths}, not as one group"
    return out


def _lane(db, sql, plist):
    for _ in range(8):
        h = E.dispatch_lane_batch(db, [sql] * len(plist), plist, ring_state={})
        if h is not None:
            rss = h.collect()
            assert [rs.engine for rs in rss] == ["tpu"] * len(plist)
            return [rs.to_dicts() for rs in rss]
        drain_warmups()  # the lane program was still compiling
    raise AssertionError("the lane fast path never became available")


def _profile(db, sql, plist):
    out = []
    for p in plist:
        row = db.query("PROFILE " + sql, params=p).to_dicts()[0]
        assert row["engine"] == "tpu" and "fallback" not in row, row
        rows, phases = tpu_engine.profile_execute(db, E.parse_cached(sql), p)
        assert phases["mode"] in ("replay", "record")
        dicts = E._result_set(rows, "tpu").to_dicts()
        assert row["rows"] == len(dicts)
        out.append(dicts)
    return out


DOORS = {
    "query": (_single, 3),
    "batch_singles": (_batch_singles, 2),
    "batch_group": (_batch_group, _GROUP_MIN + 1),
    "lane": (_lane, _GROUP_MIN + 1),
    "profile": (_profile, 3),
}


@pytest.mark.parametrize("door", list(DOORS))
@pytest.mark.parametrize("name", list(STATEMENTS))
def test_a_door_answers_as_the_reference_does(graph, name, door):
    db, ref = graph
    run, n = DOORS[door]
    sql = STATEMENTS[name]
    plist = _draws(name, ref, n)
    # recorded once through the plain door, so that every door replays
    db.query(sql, params=plist[0], engine="tpu", strict=True)
    drain_warmups()
    fallback = metrics.counter("query.tpu.fallback")
    got = run(db, sql, plist)
    assert metrics.counter("query.tpu.fallback") == fallback
    want = [getattr(ref, name)(p) for p in plist]
    assert [_canon(g) for g in got] == want, (name, door, plist)
    assert any(w and w != [(0,)] for w in want), "every draw answered empty"
