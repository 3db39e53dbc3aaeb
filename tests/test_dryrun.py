"""The driver-facing multichip dryrun must be hermetic and green.

Two past driver runs failed on TPU-client state (libtpu version skew inside
``jax.device_put``) even though the dryrun itself only needs virtual CPU
devices. These tests pin the contract: the dryrun body runs the full
sharded-parity corpus on a CPU mesh, and the `__graft_entry__` wrapper runs
it in a subprocess that can never construct a TPU client.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_body_in_process():
    # conftest already pinned this process to 8 CPU devices — run the real
    # body directly (fast path; exercises the same code the driver hits).
    from orientdb_tpu.tools.dryrun import run_body

    run_body(8)


@pytest.mark.slow  # ~75s; the driver invokes dryrun_multichip itself,
# and tier-1 already runs the identical corpus via the in-process body
def test_graft_entry_dryrun_subprocess_is_cpu_pinned():
    # The wrapper must succeed even when the calling process exports a
    # non-CPU JAX_PLATFORMS.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "definitely-not-a-platform"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import __graft_entry__ as g; g.dryrun_multichip(4)",
        ],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "hermetic" in proc.stdout
