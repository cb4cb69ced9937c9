"""The four-chip north-star cell's own tests (``northstar-mesh4``), on the
CPU, run by hand like their siblings (four CPU devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``, or the eight that
tests/conftest.py gives):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_northstar_mesh_cell.py -q
"""

import json
import os
import re
import sys
from pathlib import Path

import numpy as np
import pytest

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(BENCH)]

import run  # noqa: E402
import whatif_scenarios  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELL = "northstar-mesh4"
SEED = 2147483999
BORROWED = {
    "northstar_release_ms_per_boundary": "whatif_release_ms_per_boundary",
    "northstar_mesh_handback_ms_per_batch": "mesh_handback_ms_per_batch",
    "northstar_mesh_fetch_ms_per_batch": "mesh_fetch_ms_per_batch",
    "northstar_mesh_device_skew_share": "mesh_device_skew_share"}


def rehearse(monkeypatch, capsys, *extra):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                   "1", "--trace", "0", "--rehearse", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    batches, res = json.loads(lines[-2]), json.loads(lines[-1])
    return rc, {n: v for n, v, *_ in batches["checks"]}, res


def test_the_rehearsal_is_correct(monkeypatch, capsys):
    rc, checks, res = rehearse(monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and res["attempted"] >= 2
    assert set(res["metrics"]) == {"placements_per_s", "setup_s"}
    assert checks["ref.choices_not_the_references_share"] == 0.0
    assert checks["window.compiles"] == 0
    assert checks["ref.placements_on_down_or_injected_taint_nodes"] == 0
    assert checks["ref.placed_differs_from_answers_max"] == 0
    assert checks["ref.scenario_choices_compared_short_of_min"] == 0


@pytest.mark.parametrize("control", ["bf16", "unperturbed"])
def test_a_control_is_not_correct(monkeypatch, capsys, control):
    rc, checks, res = rehearse(monkeypatch, capsys, "--control", control)
    assert rc == 0 and res["correct"] is False
    assert checks["ref.choices_not_the_references_share"] > 0.05


def test_the_meshed_batch_answers_the_unmeshed_one():
    """The cell's adapter over four devices and ``engines/whatif.py`` over
    one, on the same rehearsal trace and scenarios: the same placements,
    array for array, on the device-release path both."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    _, _, config, traffic = run.load_cell(CELL)
    trace, config, engine = run.prepare(config, traffic, SEED, True, {})
    assert engine.engine.release_path == "device"
    assert engine.engine.mesh.devices.size == 4
    assert engine.scenarios_per_chip * 4 == traffic["rehearse"]["scenarios"]
    meshed = engine.answers(engine.batch())
    generator = run.load_part("generators", config["generator"])
    ec, ep = generator.to_program(trace, config)
    one = run.load_part("engines", "whatif").Engine(
        ec, ep, config, traffic, traffic["rehearse"]["chunkWaves"])
    plain = one.answers(one.batch())
    np.testing.assert_array_equal(meshed["assignments"], plain["assignments"])
    assert meshed["placed"] == plain["placed"]


def test_a_chips_scenarios_are_the_one_chip_cells():
    """The first 128 of the 512 are ``borg10k-whatif128``'s own: weak scaling
    compares like with like."""
    _, _, config, traffic = run.load_cell(CELL)
    _, _, config1, traffic1 = run.load_cell("borg10k-whatif128")
    assert config == config1 and traffic["tasks"] == traffic1["tasks"]
    assert traffic["scenarios"] == 4 * traffic1["scenarios"] == 512
    many = whatif_scenarios.sample(config, 10000, 512)
    few = whatif_scenarios.sample(config, 10000, 128)
    for a, b in zip(many, few):
        for k in ("down", "scaled", "tainted"):
            np.testing.assert_array_equal(a[k], b[k])
        assert a["factor"] == b["factor"]


def test_names_units_and_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert cell["chips"] == 4 and cell["config"] == "borg2019-10k-whatif"
    assert cell["traffic"] == "whatif-512-mesh4" and len(cell["why"]) <= 200
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert traffic["chips"] == 4 and traffic["engine"] == "whatif_release_mesh"
    assert (BENCH / "engines" / f"{traffic['engine']}.py").is_file()
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 2)
    metrics = {m["name"]: m for m in b["per_layer"]}
    for name, lender in BORROWED.items():
        m = metrics[name]
        assert NAME.match(name) and m["workloads"] == [CELL]
        assert m["moves"] == "placements_per_s"
        for key in ("unit", "better", "source", "layer"):
            assert m[key] == metrics[lender][key]
        mine = run.load_part("layer_metrics", name)
        assert mine.read.__module__.endswith(lender)
    # every metric that lists no cell is read in this cell too
    assert [m["name"] for m in b["per_layer"] if "workloads" not in m] == [
        "encode_s", "compile_s", "chunk_gap_ms", "chunk_ms_per_wave",
        "chunk_roofline", "device_idle_share"]


@pytest.mark.parametrize("fault, says", [
    ({"release_path": None}, "not on the device-release path"),
    ({"release_path": "host"}, "not on the device-release path"),
    ({"chunk_waves": 8}, "a chunk of 8 waves"),
    ({"mesh": None}, "mesh holds 0 devices"),
])
def test_the_adapter_refuses_another_program_before_any_batch(
        monkeypatch, fault, says):
    import kubernetes_simulator_tpu.sim.whatif as program

    class Mesh:
        class devices:
            size = 4

    class Other:
        release_path, chunk_waves, mesh = "device", 16, Mesh

        def __init__(self, *a, **kw):
            for k, v in fault.items():
                setattr(self, k, v)

    _, _, config, traffic = run.load_cell(CELL)
    generator = run.load_part("generators", config["generator"])
    trace = generator.generate(config, 64, 256, 1)
    ec, ep = generator.to_program(trace, config)
    monkeypatch.setattr(program, "WhatIfEngine", Other)
    adapter = run.load_part("engines", traffic["engine"])
    with pytest.raises(RuntimeError, match=says):
        adapter.Engine(ec, ep, config, traffic, 16)
