"""scripts/whatif128_reading.py: the hand reading of the what-if batch no
benchmark cell measures. Off the TPU it refuses; its rehearsal runs the
whole path at a tiny size and gives no rate."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "whatif128_reading.py"),
         *args],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300, cwd=ROOT,
    )


def test_refuses_off_the_tpu():
    out = _run("--batches", "1")
    assert out.returncode == 1 and out.stdout == ""
    assert "no TPU" in out.stderr


def test_rehearsal_runs_the_path_and_gives_no_rate():
    out = _run("--rehearse", "--batches", "2")
    assert out.returncode == 0, out.stderr[-2000:]
    row = json.loads(out.stdout.splitlines()[-1])
    assert row["device"] == "cpu" and row["engine"] == "v3"
    assert row["completions_on"] and len(row["batch_s"]) == 2
    assert row["placements_per_s_best"] is None
    assert row["placements_per_s_median"] is None
    assert row["total_placed"] == row["placed_sum_check"] > 0
