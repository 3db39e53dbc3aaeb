"""The table of peaks and the least bytes each kind of statement must
move: the denominators of ``hbm_roofline_share``. Kept with the
benchmark so that a PR which replaces a kernel is read against the
same work.
"""

from __future__ import annotations

#: Published peaks per chip, keyed by ``jax.devices()[0].device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" system architecture
#: (16 GB HBM2e at 819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8).
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "source": "cloud.google.com/tpu/docs/v5e, TPU v5e system architecture",
    },
}


def peak_for(device_kind: str) -> dict:
    """A device that is not in the table is an error, not a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def least_bytes(kind: str, P: int, M: int, E: int) -> float:
    """Bytes the *query* needs for one request of a reference kind on a
    graph of ``P`` persons, ``M`` messages and ``E`` directed ``knows``
    edges: every int32 CSR array and column the statement must read,
    once, and every result value written, once. A function of the
    graph's sizes alone: never of what the present kernels move.

    Rooted kinds are reckoned at the graph's mean degrees
    (``d = E / P`` out, ``2 d`` both ways), which the curated roots stay
    near."""
    d = E / P
    w = 4  # every id, pointer and property column is int32
    table = {
        # knows: indptr, dst, creationDate; age of both ends; messages per
        # person = the in-direction pointers of hasCreator over persons
        "config5_count": w * (P + 2 * E + P + P) + w,
        # length of every message, its creator, the creators' ages
        "creator_1hop_count": w * (2 * M + P) + w,
        "knows_1hop_count": w * (P + E + P) + w,
        # the second hop's per-vertex weights must be complete before the
        # first hop sums them: the edge list is read twice
        "knows_2hop_count": w * (2 * P + 2 * E + P) + w,
        # two pointer pairs, 2d neighbour ids, their ages; 2 values a row out
        "friends_rows": w * (4 + 2 * d + 2 * d) + w * 4 * d,
    }
    if kind not in table:
        raise KeyError(f"no byte count for reference kind {kind!r}")
    return float(table[kind])
