"""Plain references: exact integer and row arithmetic in numpy over the
benchmark's own seeded arrays (``datagen.Raw``). Imports nothing of the
program and reads nothing the program made.

One function per *kind* of statement; a traffic file names the kind of
each of its shapes (``"reference": "<kind>"``), so a new mix of these
kinds needs no code. Every function takes the request's parameters by
the names the statement uses and returns the rows a client must see, as
tuples in the column order the traffic file states.

The COUNT kinds are the arithmetic of ``storage/bigshape.numpy_*``
(PR 22 proved those against the chip at 8 M persons), written over the
raw edge list; the rooted kind is CSR slicing. ``-knows-`` walks an
edge both ways, and parallel edges count once each (``count(*)`` counts
paths, rows are a multiset).
"""

from __future__ import annotations

import numpy as np

from benchmark.datagen import Raw


def _indptr(counts: np.ndarray) -> np.ndarray:
    out = np.zeros(counts.shape[0] + 1, np.int64)
    np.cumsum(counts, out=out[1:])
    return out


def _seg_sum(vals: np.ndarray, indptr: np.ndarray) -> np.ndarray:
    tot = np.zeros(vals.shape[0] + 1, np.int64)
    np.cumsum(vals, dtype=np.int64, out=tot[1:])
    return tot[indptr[1:]] - tot[indptr[:-1]]


class Reference:
    """The references of one graph. Derived arrays (the in-direction of
    ``knows``, messages per creator, the tables the scans sum
    over) are built once, on first use."""

    def __init__(self, raw: Raw) -> None:
        self.raw = raw
        self.out_ptr = _indptr(raw.knows_deg)
        self._lazy: dict = {}

    # -- derived arrays -----------------------------------------------------

    def _get(self, key: str, build):
        if key not in self._lazy:
            self._lazy[key] = build()
        return self._lazy[key]

    @property
    def edge_src(self) -> np.ndarray:
        return self._get(
            "edge_src",
            lambda: np.repeat(
                np.arange(self.raw.P, dtype=np.int32), self.raw.knows_deg
            ),
        )

    @property
    def knows_in(self):
        """(indptr, sources) of ``knows`` grouped by target."""

        def build():
            dst = self.raw.knows_dst
            order = np.argsort(dst, kind="stable")
            ptr = _indptr(np.bincount(dst, minlength=self.raw.P))
            return ptr, self.edge_src[order]

        return self._get("knows_in", build)

    @property
    def msg_count(self) -> np.ndarray:
        return self._get(
            "msg_count",
            lambda: np.bincount(
                self.raw.creator, minlength=self.raw.P
            ).astype(np.int64),
        )

    @property
    def len_age_table(self) -> np.ndarray:
        """``t[l, a]``: messages of length ``l`` whose creator is ``a``
        years old, so that a (minLen, maxAge) COUNT is a table sum."""

        def build():
            r = self.raw
            key = r.length.astype(np.int64) * 128 + r.age[r.creator]
            return np.bincount(key, minlength=2048 * 128).reshape(2048, 128)

        return self._get("len_age_table", build)

    def neighbours(self, p: int) -> np.ndarray:
        """Both directions of ``knows`` at ``p``: out-targets, then
        in-sources; a parallel or mutual edge appears once per edge."""
        ptr_in, src_in = self.knows_in
        return np.concatenate(
            [
                self.raw.knows_dst[self.out_ptr[p] : self.out_ptr[p + 1]],
                src_in[ptr_in[p] : ptr_in[p + 1]],
            ]
        ).astype(np.int64)

    def degree_both(self) -> np.ndarray:
        """Undirected degree per person (the 1-hop's result size)."""
        ptr_in, _ = self.knows_in
        return self.raw.knows_deg + np.diff(ptr_in)

    # -- whole-graph COUNT kinds ----------------------------------------------

    def config5_count(self, minAge: int, d: int, maxAge: int) -> list:
        """Σ over knows edges p→f with age(p) > minAge, creationDate > d,
        age(f) < maxAge, of the number of messages f created."""
        r = self.raw
        f = r.knows_dst
        w = ((r.age[f] < maxAge) & (r.knows_cdate > d)) * self.msg_count[f]
        per_src = _seg_sum(w, self.out_ptr)
        return [(int(per_src[r.age > minAge].sum()),)]

    def creator_1hop_count(self, minLen: int, maxAge: int) -> list:
        """Messages longer than minLen whose creator is under maxAge."""
        t = self.len_age_table
        return [(int(t[max(minLen + 1, 0) :, : max(maxAge, 0)].sum()),)]

    def knows_1hop_count(self, minAge: int, maxAge: int) -> list:
        r = self.raw
        w = _seg_sum(r.age[r.knows_dst] < maxAge, self.out_ptr)
        return [(int(w[r.age > minAge].sum()),)]

    def knows_2hop_count(self, minAge: int, maxAge: int) -> list:
        r = self.raw
        w2 = _seg_sum(r.age[r.knows_dst] < maxAge, self.out_ptr)
        w1 = _seg_sum(w2[r.knows_dst], self.out_ptr)
        return [(int(w1[r.age > minAge].sum()),)]

    # -- rooted kinds -----------------------------------------------------------

    def friends_rows(self, personId: int) -> list:
        """(uid, age) of every friend, either direction."""
        f = self.neighbours(personId)
        return list(zip(f.tolist(), self.raw.age[f].tolist()))

    def answer(self, kind: str, params: dict) -> list:
        fn = getattr(self, kind, None)
        if fn is None or kind.startswith("_"):
            raise KeyError(f"no reference of kind {kind!r}")
        return fn(**{k: int(v) for k, v in params.items()})
