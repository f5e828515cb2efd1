"""chip_smoke.py without the chip: its CPU rehearsal runs every phase at the
reduced config, and without a TPU (or without the repo beside it) the
script fails and prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _run(*args, cwd=None, script=SCRIPT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script), *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def _last_json(stdout: str):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None


def test_rehearsal_runs_every_phase():
    r = _run("--rehearse")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = r.stdout
    for marker in ("logits prefill", "logits decode", "phase encode",
                   "phase prefill", "phase decode", "migrations:",
                   "compiles:"):
        assert marker in out, marker
    assert "'E->P'" in out and "'P->D'" in out
    assert out.count("request ") >= 8
    last = _last_json(out)
    assert last is not None and last.get("rehearsal") == "passed"
    assert "ok" not in last


def test_without_tpu_fails_and_prints_no_result():
    r = _run()
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    last = _last_json(r.stdout)
    assert not (isinstance(last, dict) and last.get("ok"))


def test_alone_in_a_directory_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    r = _run(cwd=tmp_path, script=lone)
    assert r.returncode != 0
    assert _last_json(r.stdout) is None
