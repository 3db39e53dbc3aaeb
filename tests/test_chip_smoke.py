"""``chip_smoke.py`` off a TPU: it must fail, and never print ``"ok": true``.

The driver runs the script where JAX finds no accelerator and expects a
non-zero exit and no result; the tiny ``--rehearse`` run (what a builder
runs on the CPU before a chip call) drives every phase and still must
not claim success. The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says — no other directory is set in code.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, tmp_path, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env,
        cwd=str(tmp_path),
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_no_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    proc = _run([], tmp_path, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "no TPU" in proc.stderr


def test_tiny_rehearsal_runs_every_phase_but_refuses_ok(tmp_path):
    proc = _run(
        ["--rehearse", "--snb-persons", "60", "--scale-persons", "2000"],
        tmp_path,
        timeout=600,
    )
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert '"ok": true' not in proc.stdout
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    phases = {ln.get("phase"): ln for ln in lines[:-1]}
    # every phase ran to its end and the device-did-the-work proof held
    # on the CPU device — only the platform check refused
    assert {"served", "scale", "proof"} <= set(phases), proc.stderr[-2000:]
    assert phases["served"]["oracle_parity"] == "ok"
    assert phases["scale"]["numpy_parity"] == "ok"
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    # the cache went where the environment said
    assert phases["start"]["compile_cache_dir"] == str(tmp_path / "cache")
    assert phases["proof"]["compile_cache_dir"] == str(tmp_path / "cache")
