"""The single replay of the default plugin set (``k8s5k-replay1``), on the
CPU, run by hand like its siblings:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_k8s5k_replay_cell.py -q

The configuration, its generator and its reference are ``k8s5k-whatif256``'s
(``test_k8s5k_cell.py`` holds them); new here are the traffic mix, the adapter
that answers one replay as a batch of one scenario, and two readers under a
name that lists this cell.
"""

import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(BENCH)]

import run  # noqa: E402
import trace_reduce  # noqa: E402
from kubernetes_simulator_tpu.utils import profiling  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELL = "k8s5k-replay1"
NEW_METRICS = ("replay5k_chunk_count_planes_ms_per_wave",
               "replay5k_chunk_reads_ms_per_wave")


def rehearse(monkeypatch, capsys, *extra):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", "2147483999", "--seconds",
                   "1", "--trace", "0", "--rehearse", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    batches, res = json.loads(lines[-2]), json.loads(lines[-1])
    return rc, {n: v for n, v, *_ in batches["checks"]}, res


def test_the_rehearsal_is_correct(monkeypatch, capsys):
    rc, checks, res = rehearse(monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and res["attempted"] >= 2
    assert set(res["metrics"]) == {"placements_per_s", "setup_s"}
    assert checks["ref.choices_not_the_references_share"] == 0.0
    assert checks["ref.choices_compared_short_of_min"] == 0
    assert checks["window.compiles"] == 0
    for row in ("anti_affinity", "zone_affinity", "spread_skew"):
        assert checks[f"ref.{row}_terms_broken"] == 0


@pytest.mark.parametrize("control, least", [
    ("bf16", 0.05), ("no-interpod", 0.01), ("no-spread", 0.01)])
def test_a_control_is_not_correct(monkeypatch, capsys, control, least):
    """The reference's controls that say something of ONE cluster: bfloat16 in
    the program's place, and the reference without a plugin. (``unperturbed``
    puts scenario 0's table in every scenario's place, and a batch of one
    scenario IS scenario 0: it comes out correct here and holds nothing.)"""
    rc, checks, res = rehearse(monkeypatch, capsys, "--control", control)
    assert rc == 0 and res["correct"] is False
    assert checks["ref.choices_not_the_references_share"] > least


def test_the_one_scenario_is_the_base_cluster():
    import whatif_scenarios

    _, _, config, traffic = run.load_cell(CELL)
    assert traffic["scenarios"] == 1 == traffic["rehearse"]["scenarios"]
    (only,) = whatif_scenarios.sample(config, config["cluster"]["nodes"], 1)
    assert not any(len(only[k]) for k in ("down", "scaled", "tainted"))
    assert only["factor"] == 1.0


def test_the_replay_answers_as_scenario_0_of_the_what_if_batch():
    """The adapter hands the replay's nodes back as one scenario's row, and
    they are what scenario 0 of the same trace's what-if batch answers."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    _, _, config, traffic = run.load_cell(CELL)
    trace, config, engine = run.prepare(config, traffic, 7, True, {})
    one = engine.answers(engine.batch())
    assert one["assignments"].shape == (1, len(trace["tasks"]["arrival"]))
    assert one["assignments"].dtype == np.int32
    assert len(one["placed"]) == len(one["unschedulable"]) == 1
    assert one["placed"][0] == int((one["assignments"][0] >= 0).sum())
    assert engine.scenarios_per_chip == 1 and engine.offered == one[
        "assignments"].shape[1]
    _, _, _, batch_traffic = run.load_cell("k8s5k-whatif256")
    _, _, batch = run.prepare(
        json.loads((ROOT / "benchmark/configs/k8s5k-default-plugins.json").read_text()),
        {**batch_traffic, "rehearse": {**traffic["rehearse"], "scenarios": 2}},
        7, True, {})
    many = batch.answers(batch.batch())
    np.testing.assert_array_equal(many["assignments"][0], one["assignments"][0])


def test_names_units_and_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert cell["config"] == "k8s5k-default-plugins" and cell["chips"] == 1
    assert cell["traffic"] == "replay-1-arrivals" and len(cell["why"]) <= 200
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    # a pair of configuration and traffic appears once
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (BENCH / "engines" / f"{traffic['engine']}.py").is_file()
    config = json.loads((ROOT / "benchmark/configs/k8s5k-default-plugins.json").read_text())
    assert traffic["check_samples"] >= config["limits"]["choices_compared_min"]
    metrics = {m["name"]: m for m in b["per_layer"]}
    for name in NEW_METRICS:
        m, lender = metrics[name], metrics[name[len("replay5k_"):]]
        assert m["workloads"] == [CELL] and m["moves"] == "placements_per_s"
        for key in ("unit", "better", "source", "layer"):
            assert m[key] == lender[key]
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()


US = 1000
TABLE = {"jit_chunk_fn": {
    "fusion.1": "ksim.reads", "dynamic-slice.2": "ksim.gather",
    "fusion.3": "ksim.corrections", "fusion.4": "ksim.commit",
    "fusion.5": "ksim.select", "fusion.6": "ksim.derive",
}}


def made_up_trace():
    ops, modules = [], []
    body = [("fusion.1", 10), ("dynamic-slice.2", 5), ("fusion.3", 20),
            ("fusion.4", 8), ("fusion.5", 15), ("fusion.6", 6)]
    for start in (100, 400):
        modules.append(["jit_chunk_fn(7)", start * US, 200 * US])
        t = start
        for name, us in body:
            ops.append([f"%{name} = s32[]{{:T(128)}} fusion(%a, %b)", t * US, us * US])
            t += us + 1
    return {"devices": [{"modules": modules, "ops": ops, "dropped": []}],
            "host": [["bench:batch:0", 0, 850 * US]]}


def read_all(events):
    ctx = {"trace": trace_reduce.Reduced(events), "device_kind": "TPU v5 lite",
           "shape": {"scenarios_per_chip": 1, "nodes": 5000, "resources": 3,
                     "wave_width": 8, "chunk_waves": 2}}
    return {m: run.load_part("layer_metrics", m).read(ctx) for m in NEW_METRICS}


def test_the_new_readers_on_a_made_up_trace(monkeypatch):
    monkeypatch.setattr(profiling, "stage_tables", lambda: TABLE, raising=False)
    got = read_all(made_up_trace())
    # two executions of two waves: corrections + commit; reads, gather, derive
    assert got["replay5k_chunk_count_planes_ms_per_wave"] == pytest.approx(
        2 * 0.028 / 4)
    assert got["replay5k_chunk_reads_ms_per_wave"] == pytest.approx(
        2 * (0.010 + 0.005 + 0.006) / 4)
    monkeypatch.delattr(profiling, "stage_tables", raising=False)
    assert read_all(made_up_trace()) == dict.fromkeys(NEW_METRICS)


def test_the_adapter_refuses_a_trace_that_releases():
    _, _, config, traffic = run.load_cell(CELL)
    generator = run.load_part("generators", config["generator"])
    trace = generator.generate(config, 64, 256, 1)
    ec, ep = generator.to_program(trace, config)
    adapter = run.load_part("engines", traffic["engine"])
    with pytest.raises(RuntimeError, match="arrivals only"):
        adapter.Engine(ec, ep, {**config, "engine": {**config["engine"],
                                                     "completions": True}},
                       traffic, 16)
