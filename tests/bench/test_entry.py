"""The entry point's traced path on the CPU, and its refusal to run
without a TPU or outside a checkout of the program."""
import json
import os
import shutil
import subprocess
import sys

from bench import run
from bench.cell import ROOT, load_cell

CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0][
    "name"]


def test_traced_rehearsal_reads_the_host_spans():
    cell = load_cell(CELL)
    result, _ = run.run_cell(cell, 5, 3.0, True, rehearse=True)
    assert result["correct"]
    names = set(result["metrics"])
    assert {"front.submit_block_p90_ms", "sched.queue_wait_p90_ms"} <= names
    # no device trace on the CPU: the device readers find nothing to read
    assert not names & {"mfu.prefill", "mfu.decode", "device.idle_share"}
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def _run(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU chip" in p.stderr


def test_the_benchmark_alone_is_not_runnable(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in spec["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
