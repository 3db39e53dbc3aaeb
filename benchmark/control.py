"""The controls: what has to make ``correct`` come out false.

``lower_precision``
    The program computed in the nearest precision below the one it
    states. Its exact prefix sums run on the MXU as one triangular f32
    matmul at ``Precision.HIGHEST`` (``ops/csr._block_scan_f32``); at the
    default precision the MXU rounds f32 operands to bfloat16, which is
    30 % faster (PERF.md, PR 22) and wrong as soon as a summed value
    passes 256. The control rounds the operands to bfloat16 itself, so
    that it is the same computation on the chip and in a CPU test.

``stale_snapshot``
    A guarantee of the configuration broken: the server holds what the
    configuration's kinds module makes of the data with its planted
    fault (``<module>.stale(raw, seed)``; for the SNB arrays the snapshot
    of one batch of updates ago), where the configuration states reads
    of THE immutable snapshot, exact. For cells whose sums never pass
    256, where the lower precision is exact.

Run on the chip at a cell's own size:

    python3 -m benchmark.control --workload <cell> --seeds 1,2,3 \\
        --seconds 10 --control lower_precision|stale_snapshot

The benchmark's own runs never come here.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

CONTROLS = ("lower_precision", "stale_snapshot")


@contextlib.contextmanager
def lower_precision():
    """Patch the program's MXU prefix sum down to one bfloat16 pass."""
    import jax
    import jax.numpy as jnp

    from orientdb_tpu.ops import csr

    def block_scan_bf16(vals_f32):
        rows = vals_f32.reshape(-1, csr._CS_BLOCK).astype(jnp.bfloat16)
        tri = jnp.triu(jnp.ones((csr._CS_BLOCK,) * 2, jnp.bfloat16))
        return jnp.dot(rows, tri, preferred_element_type=jnp.float32)

    sound_scan, sound_cumsum = csr._block_scan_f32, csr.value_cumsum

    def cumsum_blocked(vals, force_blocked=False):
        # the CPU takes the plain cumsum unless forced; the chip never does
        return sound_cumsum(vals, True)

    jax.clear_caches()
    csr._block_scan_f32 = block_scan_bf16
    csr.value_cumsum = cumsum_blocked
    try:
        yield
    finally:
        csr._block_scan_f32, csr.value_cumsum = sound_scan, sound_cumsum
        jax.clear_caches()


def main(argv=None) -> int:
    from benchmark import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", choices=CONTROLS, required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for seed in (int(s) for s in args.seeds.split(",")):
        one = argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=0
        )
        result = run.run_cell(one, bench, control=args.control)
        print(
            json.dumps(
                {
                    "control": args.control,
                    "workload": args.workload,
                    "seed": seed,
                    "correct": result["correct"],
                    "attempted": result["attempted"],
                    "failed": result["failed"],
                    "compared": result["compared"],
                    "metrics": result["metrics"],
                }
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
