"""The table of published peaks: what the chip can do, the denominator
of ``hbm_roofline_share``. It belongs to the chip, not to a deployment;
the least bytes a kind of statement must move are its kinds module's
(``benchmark/kinds/<module>.least_bytes``). Kept with the benchmark so
that a PR which replaces a kernel is read against the same work.
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
