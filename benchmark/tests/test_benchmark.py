"""The benchmark's own tests, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

Tier-1 (`pytest tests/`) does not collect this directory: a benchmark PR may
add files only under the benchmark's own paths (PERF.md §7).
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(BENCH)]

import roofline  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELL = "borg10k-replay1"


def harness(*args, cwd=ROOT, script=BENCH / "run.py"):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, str(script), *args], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=600)


def rehearse(monkeypatch, capsys, *extra):
    """Everything of a run but the look for a chip, in this process."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", "2147483664", "--seconds",
                   "1", "--trace", "0", "--rehearse", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-2]), json.loads(lines[-1])


def test_reducer_on_a_recorded_trace():
    """Two traced batches of borg10k-replay1 from the chip, device ops merged
    into busy intervals to keep it small; the device's trace buffer overflowed
    8.4 s in, where the window ends: 56 chunk programs of 768 waves."""
    events = json.loads((BENCH / "testdata" / "trace_cut.json").read_text())
    expect = events.pop("expect")
    red = trace_reduce.Reduced(events)
    ctx = {"trace": red, "shape": expect["shape"], "device_kind": "TPU v5 lite"}
    got = {m: run.load_part("layer_metrics", m).read(ctx)
           for m in expect["metrics"]}
    assert got == pytest.approx(expect["metrics"], rel=1e-9)
    assert got["device_idle_share"] == pytest.approx(
        100 * (1 - red.busy_s / red.window_s))
    bd = red.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert 0 < red.busy_s < red.window_s
    assert got["chunk_ms_per_wave"] == pytest.approx(0.19518, abs=1e-5)


def test_rehearsed_run_prints_the_contract_line():
    p = harness("--workload", CELL, "--seed", "2147483664", "--seconds", "1",
                "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    batches, res = json.loads(lines[-2]), json.loads(lines[-1])
    assert batches["kind"] == "batches" and set(res) == RESULT_KEYS
    assert res["correct"] is True and res["attempted"] >= 2
    assert res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"placements_per_s", "setup_s"}
    # every placement of the window over all of its seconds
    assert res["metrics"]["placements_per_s"]["value"] == pytest.approx(
        sum(batches["placed_per_batch"]) / batches["window_s"])
    assert batches["window_s"] >= sum(batches["seconds"])


def test_refuses_off_the_tpu_and_without_the_program(tmp_path):
    p = harness("--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    # a directory that holds only BENCHMARK.json and the files under paths
    subprocess.run(["cp", "-r", str(BENCH), str(ROOT / "BENCHMARK.json"),
                    str(tmp_path)], check=True)
    p = harness("--workload", CELL, "--seed", "1", "--seconds", "1", "--trace",
                "0", "--rehearse", cwd=tmp_path,
                script=tmp_path / "benchmark" / "run.py")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_json_names_units_and_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in b["configs"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        config = json.loads((ROOT / c["file"]).read_text())
        assert (BENCH / "generators" / f"{config['generator']}.py").is_file()
        assert (BENCH / "references" / f"{config['reference']}.py").is_file()
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        traffic = json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (BENCH / "engines" / f"{traffic['engine']}.py").is_file()
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()


def test_peaks_raise_on_an_unknown_device_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_the_reference_in_bfloat16_is_not_correct(monkeypatch, capsys):
    """The control: the plain reference computed in bfloat16, put in the
    program's place, fails the comparison that decides ``correct``."""
    rc, batches, res = rehearse(monkeypatch, capsys, "--control", "bf16")
    share = {n: v for n, v, *_ in batches["checks"]}[
        "ref.choices_not_the_references_share"]
    assert rc == 0 and res["correct"] is False and share > 0.06


@pytest.mark.parametrize("fault", ["moved", "unchanged_state", "dropped"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, capsys, fault):
    """The timed path broken underneath: one task in a hundred answered one
    node further on; a step that returns its state unchanged (every task
    sees the empty cluster); a part of the batch left out."""
    engines = run.load_part("engines", "replay")
    sound = engines.Engine.answers

    def broken(self, result):
        out = sound(self, result)
        a = out["assignments"]
        if fault == "moved":
            a[::100] = (a[::100] + 1) % 64
        elif fault == "unchanged_state":
            a[:] = a[0]
        else:
            a[-len(a) // 8:] = -1
            out["placed"] = [int((a >= 0).sum())]
        return out

    monkeypatch.setattr(engines.Engine, "answers", broken)
    monkeypatch.setattr(run, "load_part", lambda kind, name: (
        engines if (kind, name) == ("engines", "replay")
        else LOAD(kind, name)))
    rc, batches, res = rehearse(monkeypatch, capsys)
    assert rc == 0 and res["correct"] is False, batches["checks"]


LOAD = run.load_part
